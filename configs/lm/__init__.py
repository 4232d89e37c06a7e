"""Language-model dataset + training recipe (no reference counterpart: the
DGC reference trains conv nets only). Synthetic token rows from the seed
(``dgc_tpu.data.SyntheticTokens``); momentum SGD, because DGC's momentum
correction is defined for it (Lin et al. 2018) and the system has no
AdamW."""

from dgc_tpu.data import SyntheticTokens
from dgc_tpu.utils.config import Config, configs

# dataset: packed rows of seq_len tokens
configs.dataset = Config(SyntheticTokens)
configs.dataset.seq_len = 2048
configs.dataset.vocab_size = 200064
configs.dataset.synthetic_size = 64

# training: one packed row a chip a step
configs.train.num_epochs = 1
configs.train.batch_size = 1

# optimizer; no scheduler: the rate stays constant after the warm-up
configs.train.optimizer.lr = 1e-2
configs.train.optimizer.weight_decay = 1e-4

"""Config module of the rehearsal's token model: an embedding, one gated
MLP with a residual, an untied head; 50,560 parameters. For the CPU
rehearsals and the loader tests only; never a cell. Composed after
``configs/__init__.py`` (this directory is no package, so nothing else
runs before it)."""

from typing import Any

import flax.linen as nn

from dgc_tpu.utils.config import Config, configs


class TinyLM(nn.Module):
    vocab_size: int
    hidden: int = 40
    mlp: int = 80
    dtype: Any = None          # compute dtype; configs/bf16.py narrows it

    @nn.compact
    def __call__(self, tokens, train=True):
        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype,
                            name=name)

        x = nn.Embed(self.vocab_size, self.hidden, dtype=self.dtype,
                     name="embed")(tokens)
        x = x + dense(self.hidden, "down")(
            nn.silu(dense(self.mlp, "gate")(x)) * dense(self.mlp, "up")(x))
        # token-major logits, [B*S, V]: the labels the step's micro-batch
        # cut hands the loss are flat (training/step.py)
        return dense(self.vocab_size, "head")(x).reshape(-1, self.vocab_size)


configs.dataset = Config()
configs.dataset.seq_len = 16
configs.dataset.vocab_size = 512

configs.train.num_epochs = 1
configs.train.batch_size = 2
configs.train.optimizer.lr = 0.1
configs.train.optimizer.weight_decay = 0.0

configs.model = Config(TinyLM)
configs.model.vocab_size = configs.dataset.vocab_size

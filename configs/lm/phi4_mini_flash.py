"""Phi-4-mini-flash-reasoning (3.8B) as published: 32 layers, the whole
vocabulary. A job that holds one pipeline stage and a vocabulary slice
overrides ``model.layers`` (``[[kind, published index], ...]``),
``model.vocab_size`` and ``dataset.vocab_size``
(benchmark/configs/phi4_mini_flash.json: layers 15-18, 25,008 rows)."""

from dgc_tpu.models import phi4_mini_flash
from dgc_tpu.utils.config import Config, configs

# model
configs.model = Config(phi4_mini_flash)
configs.model.vocab_size = configs.dataset.vocab_size

from dgc_tpu.data.datasets import (
    CIFAR,
    ImageNet,
    Synthetic,
    ArraySplit,
    SyntheticSplit,
    SyntheticTokens,
    SyntheticTokenSplit,
)
from dgc_tpu.data.native import Prefetcher, native_available, stage_ahead
from dgc_tpu.data.sampler import epoch_batches, num_steps_per_epoch

__all__ = ["CIFAR", "ImageNet", "Synthetic", "ArraySplit", "SyntheticSplit",
           "SyntheticTokens", "SyntheticTokenSplit",
           "epoch_batches", "num_steps_per_epoch",
           "Prefetcher", "native_available", "stage_ahead"]

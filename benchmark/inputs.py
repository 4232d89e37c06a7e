"""The traffic generator: one general feed, driven by the traffic file.

Images and labels are made from ``--seed`` by the harness itself: a pool of
uint8 images (``pool_batches`` global batches) and integer labels. No
``SyntheticSplit``: its class-prototype table is 602 MB of set-up that no
request needs. The pool then goes through the program's own input path:

* ``input: pipeline`` — exactly ``train.py``'s loop: ``ArraySplit(...,
  train=True, augment=False)`` (the host ``_normalize`` of the program's
  synthetic ImageNet split) -> ``epoch_batches`` -> ``Prefetcher`` ->
  ``stage_ahead(host_local_to_global)``, epoch after epoch without end;
* ``input: resident`` — ``n`` global batches go through the same
  ``get_batch`` and ``host_local_to_global`` once, during set-up, and are
  cycled from the device.
"""

import itertools
from typing import Iterator, List, Tuple

import numpy as np


def make_pool(seed: int, n: int, image_size: int, num_classes: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, size=(n, image_size, image_size, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, num_classes, size=(n,), dtype=np.int32)
    return images, labels


class Feed:
    """An endless iterator of (images, labels) global device arrays."""

    def __init__(self, iterator: Iterator, close=lambda: None):
        self._it = iterator
        self._close = close

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        self._close()


def _split(seed, global_batch, pool_batches, image_size, num_classes):
    from dgc_tpu.data import ArraySplit
    from dgc_tpu.data.datasets import IMAGENET_MEAN, IMAGENET_STD
    images, labels = make_pool(seed, pool_batches * global_batch,
                               image_size, num_classes)
    return ArraySplit(images, labels, IMAGENET_MEAN, IMAGENET_STD,
                      train=True, augment=False, seed=seed)


def _endless_batches(n: int, global_batch: int, seed: int):
    from dgc_tpu.data import epoch_batches
    for epoch in itertools.count():
        yield from epoch_batches(n, global_batch, epoch=epoch, seed=seed)


def _to_mesh(batch, mesh):
    from dgc_tpu.parallel.multihost import host_local_to_global
    return (host_local_to_global(batch[0], mesh),
            host_local_to_global(batch[1], mesh))


def pipeline_feed(seed, global_batch, pool_batches, image_size, num_classes,
                  mesh) -> Feed:
    from dgc_tpu.data import Prefetcher, stage_ahead
    split = _split(seed, global_batch, pool_batches, image_size, num_classes)
    batches = Prefetcher(split, _endless_batches(len(split), global_batch,
                                                 seed))
    staged = stage_ahead(batches, lambda b: _to_mesh(b, mesh))
    return Feed(staged, close=batches.close)


def resident_batches(seed, global_batch, n, image_size, num_classes, mesh
                     ) -> List[Tuple]:
    """``n`` global batches on the device, made once."""
    split = _split(seed, global_batch, n, image_size, num_classes)
    index_iter = _endless_batches(len(split), global_batch, seed)
    return [_to_mesh(split.get_batch(next(index_iter)), mesh)
            for _ in range(n)]


def resident_feed(batches: List[Tuple]) -> Feed:
    return Feed(itertools.cycle(batches))


def scan_feed(batches: List[Tuple], mesh) -> Feed:
    """Loop 'scan': every dispatch gets the same [n, global_batch, ...]
    stacks (batch axis sharded as the step reads it) and cycles through
    them on the device."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    axes = tuple(mesh.axis_names)

    def stack(parts, spec):
        return jax.jit(lambda *xs: jax.numpy.stack(xs),
                       out_shardings=NamedSharding(mesh, spec))(*parts)

    images = stack([b[0] for b in batches], P(None, axes))
    labels = stack([b[1] for b in batches], P(None, axes))
    return Feed(itertools.repeat((images, labels)))

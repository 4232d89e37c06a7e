"""The traffic generator: one general feed, driven by the configuration's
``dataset`` block (what an example is) and the traffic file (how many, how
they reach the step, and for tokens how they are drawn).

Everything is made from ``--seed`` by the harness itself; the program
receives only the arrays, staged by its own ``host_local_to_global``.

``kind: images`` — a pool of uint8 images (``pool_batches`` global batches)
and integer labels. No ``SyntheticSplit``: its class-prototype table is
602 MB of set-up that no request needs. The pool goes through the
program's own input path:

* ``input: pipeline`` — exactly ``train.py``'s loop: ``ArraySplit(...,
  train=True, augment=False)`` (the host ``_normalize`` of the program's
  synthetic ImageNet split) -> ``epoch_batches`` -> ``Prefetcher`` ->
  ``stage_ahead(host_local_to_global)``, epoch after epoch without end;
* ``input: resident`` — ``n`` global batches go through the same
  ``get_batch`` and ``host_local_to_global`` once, during set-up, and are
  cycled from the device.

``kind: tokens`` — one stream of documents joined by ``eos_id`` and cut
into rows of ``seq_len + 1``; inputs are ``row[:-1]`` [B, S], labels
``row[1:]`` token-major [B*S] (a sequence's tokens stay together, so the
batch axis still shards by sequence; the program's micro-batch cut takes
labels of one axis, and a token model returns its logits [B*S, V]),
int32, and ``per_chip_batch`` counts sequences. Token ranks are drawn with
P(r) ~ r^-``zipf_s`` over the non-EOS ids (0: uniform; natural text is
near 1) and mapped to ids by a permutation made from the seed; document
lengths are log-normal round ``doc_len_median`` with ``doc_len_sigma``,
clipped to [1, seq_len] (null median: one document per row). ``input:
resident`` only: the program has no token split for ``pipeline`` to drive.

``host_batches`` records the harness's own spans round the making of each
global batch (arm ``setup``): ``input.batch`` round ``split.get_batch``
or round a token batch's cut, and for tokens ``input.pool`` round the one
``make_tokens`` draw its batches share. ``input.produce_ms`` reads them
where the program recorded no ``input.get_batch`` span of its own
(``program_records.batch_seconds``).
"""

import itertools
from typing import Any, Dict, Iterator, List, Tuple

import numpy as np

from benchmark.spans import Spans


def example_shapes(dataset: Dict[str, Any], n: int):
    """((shape, dtype) of ``n`` inputs, (shape, dtype) of their labels) as
    the step receives them. ``dataset`` is the configuration file's block
    of that name with the built model's sizes (``build_arm``)."""
    if dataset["kind"] == "images":
        size = dataset["image_size"]
        return ((n, size, size, 3), np.float32), ((n,), np.int32)
    seq = dataset["seq_len"]
    return ((n, seq), np.int32), ((n * seq,), np.int32)


def sample_input(dataset):
    """The one example ``model.init`` is traced on."""
    import jax.numpy as jnp
    shape, dtype = example_shapes(dataset, 1)[0]
    return jnp.zeros(shape, dtype)


def make_pool(seed: int, n: int, image_size: int, num_classes: int
              ) -> Tuple[np.ndarray, np.ndarray]:
    rng = np.random.default_rng(seed)
    images = rng.integers(0, 256, size=(n, image_size, image_size, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, num_classes, size=(n,), dtype=np.int32)
    return images, labels


def token_ids_by_rank(seed: int, vocab_size: int, eos_id: int) -> np.ndarray:
    """[vocab_size - 1] ids: the id of the most frequent token first. A
    permutation of the non-EOS ids made from the seed, so that no seed's
    frequent tokens are the table's first rows."""
    ids = np.delete(np.arange(vocab_size, dtype=np.int32), eos_id)
    return np.random.default_rng([seed, 1]).permutation(ids)


def make_tokens(seed: int, n: int, seq_len: int, vocab_size: int,
                eos_id: int, zipf_s: float, doc_len_median=None,
                doc_len_sigma=None) -> np.ndarray:
    """``n`` rows of ``seq_len + 1`` token ids, int32."""
    need = n * (seq_len + 1)
    rng = np.random.default_rng([seed, 0])
    weights = np.arange(1, vocab_size, dtype=np.float64) ** -float(zipf_s)
    cdf = np.cumsum(weights)
    ranks = np.searchsorted(cdf, rng.random(need) * cdf[-1], side="right")
    stream = token_ids_by_rank(seed, vocab_size, eos_id)[
        np.minimum(ranks, vocab_size - 2)]
    if doc_len_median is None:
        lengths = np.full(n, seq_len, np.int64)
    else:
        # a document and its EOS take two places at least, so this many
        # documents always fill the stream
        docs = need // 2 + 1
        lengths = np.clip(np.rint(rng.lognormal(
            np.log(doc_len_median), doc_len_sigma, docs)), 1, seq_len
        ).astype(np.int64)
    ends = np.cumsum(lengths + 1)             # one past each document's EOS
    stream[ends[ends <= need] - 1] = eos_id
    return stream.reshape(n, seq_len + 1)


class Feed:
    """An endless iterator of (inputs, labels) global device arrays."""

    def __init__(self, iterator: Iterator, close=lambda: None):
        self._it = iterator
        self._close = close

    def __iter__(self):
        return self

    def __next__(self):
        return next(self._it)

    def close(self):
        self._close()


def _split(seed, global_batch, pool_batches, dataset):
    from dgc_tpu.data import ArraySplit
    from dgc_tpu.data.datasets import IMAGENET_MEAN, IMAGENET_STD
    images, labels = make_pool(seed, pool_batches * global_batch,
                               dataset["image_size"], dataset["num_classes"])
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


def pipeline_feed(seed, global_batch, pool_batches, dataset, mesh) -> Feed:
    from dgc_tpu.data import Prefetcher, stage_ahead
    split = _split(seed, global_batch, pool_batches, dataset)
    batches = Prefetcher(split, _endless_batches(len(split), global_batch,
                                                 seed))
    staged = stage_ahead(batches, lambda b: _to_mesh(b, mesh))
    return Feed(staged, close=batches.close)


def host_batches(seed, global_batch, n, dataset, traffic, spans=None
                 ) -> Iterator[Tuple]:
    """``n`` global batches on the host, as the step receives them, one at
    a time; the making of each is a span of ``spans``."""
    span = (spans or Spans()).span
    if dataset["kind"] == "images":
        split = _split(seed, global_batch, n, dataset)
        index_iter = _endless_batches(len(split), global_batch, seed)
        for _ in range(n):
            indices = next(index_iter)
            with span("setup", "input.batch"):
                batch = split.get_batch(indices)
            yield batch
        return
    with span("setup", "input.pool"):
        rows = make_tokens(seed, n * global_batch, dataset["seq_len"],
                           dataset["vocab_size"], dataset["eos_id"],
                           traffic["zipf_s"], traffic["doc_len_median"],
                           traffic["doc_len_sigma"])
    for r in rows.reshape(n, global_batch, -1):
        with span("setup", "input.batch"):
            batch = (np.ascontiguousarray(r[:, :-1]),
                     np.ascontiguousarray(r[:, 1:]).reshape(-1))
        yield batch


def resident_batches(seed, global_batch, n, dataset, traffic, mesh,
                     spans=None) -> List[Tuple]:
    """``n`` global batches on the device, made once."""
    return [_to_mesh(b, mesh) for b in host_batches(
        seed, global_batch, n, dataset, traffic, spans)]


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

    inputs = stack([b[0] for b in batches], P(None, axes))
    labels = stack([b[1] for b in batches], P(None, axes))
    return Feed(itertools.repeat((inputs, labels)))

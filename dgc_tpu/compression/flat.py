"""Flat (bucketed) execution engine for the compression pipeline.

The reference runs the DGC pipeline tensor-by-tensor: per-parameter hooks,
per-tensor top-k, per-tensor collectives with named handles
(/root/reference/dgc/horovod/optimizer.py:105-139, dgc/compression.py:155-212)
— and its README lists the resulting per-tensor thresholding overhead and
allgather volume as the system's known costs (README.md:130-138).

On TPU the idiomatic answer (SURVEY.md §7 "hard parts" #3, and the north-star
"Pallas kernels operating on HBM-resident gradient buffers") is to keep the
whole gradient, the error-feedback memory, and the optimizer state as a few
flat HBM-resident buffers and run the pipeline over them **fused**:

* ``ParamLayout`` — a static flat [P] layout over every parameter, with the
  DGC-compressed tensors stored **row-aligned in size buckets** first
  ([0, T)) and the dense-fallback tensors (biases/BN, reference
  train.py:136-140) in the tail block [T, P). Each bucket is a
  [rows, cols] tile, one tensor per row, so the engine's batched row
  views are pure reshapes — no HBM gather on the hot path (the gather
  version measured ~3 ms/step on v5e for ResNet-20, ~10x the rest of the
  sparsify pipeline). Flatten/unflatten compile to data movement XLA fuses
  away; only a handful of buffers ever cross the jit boundary.
* ``FlatDGCEngine`` — the sampled-top-k sparsification of every tensor runs
  as a few *batched* ops over the bucket row views, followed by exactly two
  ``all_gather`` collectives for the whole model and one scatter-add
  decompress. Error-feedback compensate/update are single fused elementwise /
  scatter ops over the [P] memory buffers.

Numerics follow the same contract as the per-tensor path
(``dgc_tpu.compression.dgc``, ``dgc_tpu.ops.sparsify``): per-tensor sampled
thresholds, bounded adaptation, fixed ``num_selects`` payload per tensor (the
wire volume stays within 2% of the reference's — the padded-payload gate
``_PAD_PAYLOAD_MAX_FRAC`` may inflate near-tight buckets by up to 2% to buy
an identity index map, never shrink them), scatter-add-then-average
decompress, momentum correction and masking per SURVEY.md §2.3-2.5.
"""

import collections
import dataclasses
import functools
import math
import os
from typing import (Any, Callable, Dict, List, NamedTuple, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from dgc_tpu.compression import gossip as _gossip_sched
from dgc_tpu.compression.memory import DGCSGDMemory
from dgc_tpu.ops import kernels
from dgc_tpu.resilience import faults as _faults
from dgc_tpu.resilience import integrity
from dgc_tpu.telemetry import trace as _trace
from dgc_tpu.utils.pytree import named_flatten, named_unflatten

__all__ = ["ParamLayout", "LayoutMask", "InPlaceUpdate", "FlatDGCEngine",
           "FlatDenseExchange"]

#: block alignment (elements) of the compressed-block boundary and the buffer
#: tail — multiples of the Pallas tile for BOTH supported state dtypes
#: (f32: 8 x 128; the opt-in bf16 error-feedback state: 16 x 128) so the
#: kernels see aligned buffers and need no padding copies on the hot path
_ALIGN = 16 * 128

#: exchange regime -> (value kind, index lane). "d" buckets ride the
#: dense-fallback psum; sparse kinds pick the value lane ("f32" native,
#: "f16" half wire, "i8" int8 + per-row f32 scales, "i4" nibble-packed
#: int4 + per-bucket f32 scales riding the i8 q lane) and the index
#: flag the index lane: False = plain flat offsets, True = bit-packed
#: words (``wirecodec.IndexCodec``), "delta" = Elias-Fano words over
#: the canonical sorted order (``wirecodec.DeltaIndexCodec``; both word
#: streams share ONE gathered uint32 lane). One regime per bucket,
#: chosen by ``compression.planner`` (or derived uniformly from the
#: legacy compressor flags when no plan is given).
_REGIMES = {
    "dense": ("d", False),
    "fp32": ("f32", False), "fp32_packed": ("f32", True),
    "fp16": ("f16", False), "fp16_packed": ("f16", True),
    "int8": ("i8", False), "int8_packed": ("i8", True),
    "int4_packed": ("i4", True),
    "int8_delta_idx": ("i8", "delta"),
    # decentralized gossip exchange (compression/gossip.py): the WIRE is
    # exactly the fp32 one (native values + plain offsets — the lanes,
    # shapes and collective count never change with the round type); the
    # schedule decides per round whether the gathered payload feeds the
    # parameters (full-sync round) or only the rotating neighborhood's
    # inbox (gossip round)
    "gossip_ring": ("f32", False),
    "gossip_hcube": ("f32", False),
}


#: bytes from which :meth:`ParamLayout.flatten` places a tile-aligned
#: tensor into its slot (``kernels.place_rows``) instead of concatenating
#: it: the smallest tensor that read a win IN THE STEP. Read on a v5e
#: (PERF.md §6, PR 43). Bare programs, 25 packs in one program, ms a pack
#: less the fc matmuls' own 0.68: VGG-16-BN's DGC layout concatenated
#: 4.67, fc1 + fc2 (411 + 67 MB) placed 1.89, the five 9.4 MB
#: convolutions placed too 1.59; ResNet-50's concatenated 0.73, its four
#: tensors of 8.4-9.4 MB placed 0.54, everything from 4 MB 0.36, from
#: 1 MB 0.35. In the step the small ones' win is not there: a 9.4 MB
#: gradient leaves the backward pass in VMEM and the concatenation reads
#: it from there, while a Mosaic call takes its operand from HBM. VGG
#: with the convolutions placed too: ``fwd_bwd`` 47.794 against 47.790
#: ms. ResNet-50 at 8 MiB / 4 MiB / 1 MiB: ``fwd_bwd`` -0.24 / -0.34 /
#: -0.36 ms, but compensate +0.14 (its gradient operand no longer comes
#: from VMEM: 100.2% -> 82.7% of its roofline) and the un-scoped copies
#: +0.15 / +0.08 / +0.13: +0.05 / -0.11 / -0.09 ms of a 53 ms step. So
#: the bound is fc2's own size, and every ResNet lowers to the
#: concatenation
PLACE_MIN_BYTES = 64 << 20


def _round_up(n: int, align: int) -> int:
    return -(-n // align) * align


def _count_collective(kind: str, operand, axis, engine, **where) -> None:
    """One ``exchange.collective`` count where a collective is issued:
    the bytes THIS worker hands it, from the traced operand's shape and
    dtype — what the program puts on the wire, not what the planner
    modeled (trace time only; returns at once with tracing off).
    ``where`` names the part of the buffer it carries (``segment=i``)."""
    _trace.count("exchange.collective",
                 int(operand.size) * operand.dtype.itemsize, kind=kind,
                 axis=str(axis), engine=type(engine).__name__, **where)


class _BucketGeom(NamedTuple):
    """Ratio-independent geometry of one size bucket of compressed tensors:
    a [rows, cols] tile in the flat buffer starting at ``base``. Tensor
    ``names[r]`` occupies row r, i.e. [base + r*cols, base + r*cols + numel);
    the row tail is structural zeros. Rows are NOT padded to the sublane in
    storage — that would inflate every persistent [total] buffer (params,
    momentums, velocities, optimizer state) by up to ~2x at ImageNet scale;
    the Pallas kernels pad their row blocks in-trace instead."""
    names: Tuple[str, ...]
    base: int
    rows: int          # len(names)
    cols: int          # row width: ladder-kernel block aligned


class ParamLayout:
    """Static flat-buffer layout over a pytree of arrays.

    Compressed tensors are grouped into size buckets and stored
    **row-aligned**: bucket g is a contiguous [rows, cols] tile, one
    tensor per row, so the batched row view the engine sparsifies over is a
    pure ``reshape`` of the flat buffer — measured on v5e, materializing the
    same view with an HBM gather costs ~3 ms/step for ResNet-20, ~10x the
    rest of the sparsify pipeline combined. Row tails, the gap
    after the last bucket, and the buffer tail are all structural zeros; the
    first gap slot (``sentinel``) doubles as the scatter sentinel — it always
    holds 0 in every buffer, so padded payload slots read value 0 and
    scatters to it are no-ops (SURVEY.md §2.5's zero-contribution
    tolerance). The dense-fallback tensors pack contiguously after the gap.

    The layout depends only on shapes + the compressed-name set (bucketing
    is by size), never on the compress ratio — memory buffers stay valid
    across warm-up ratio changes (reference compression.py:91-107).
    """

    #: bucket-count/padding exchange rate for _group_by_size's partition
    #: DP. Padded slots are NOT just storage: they inflate the operand
    #: AREA of every per-bucket pass (importance, ladder, selection
    #: top-k), whose cost scales with rows x cols — measured at ResNet-20,
    #: one 22x36864 merged bucket (3x area) cost 0.25 ms/step MORE than
    #: two tight buckets. A bucket's fixed floor (extra op launches) is
    #: worth ~300k slots of padding on v5e at both measured scales
    #: (ResNet-20: 0.39 -> 0.14 ms overhead vs the 2M setting;
    #: ResNet-50: neutral within noise).
    FLOOR_SLOTS = 300_000

    def __init__(self, tree, compressed_names: Sequence[str] = ()):
        named, self.treedef = named_flatten(tree)
        compressed = [n for n in named if n in set(compressed_names)]
        dense = [n for n in named if n not in set(compressed_names)]
        self.shapes = {n: tuple(named[n].shape) for n in named}
        self.sizes = {n: int(np.prod(self.shapes[n], dtype=np.int64))
                      for n in named}
        dtypes = {np.dtype(named[n].dtype) for n in named}
        if len(dtypes) > 1:
            raise ValueError(
                f"flat layout requires a uniform dtype, got {dtypes}")
        self.dtype = dtypes.pop() if dtypes else np.dtype(np.float32)
        #: number of real (non-padding) parameters
        self.num_params = sum(self.sizes.values())

        # --- compressed block: size-bucketed row tiles ---
        self.buckets: List[_BucketGeom] = []
        self.offsets: Dict[str, int] = {}
        off = 0
        for group in self._group_by_size(compressed):
            cols = kernels.ladder_cols(max(self.sizes[n] for n in group))
            geom = _BucketGeom(tuple(group), off, len(group), cols)
            self.buckets.append(geom)
            for r, n in enumerate(group):
                self.offsets[n] = off + r * cols
            off += len(group) * cols
        # bucket order is the storage order of the compressed names
        self.compressed_names = [n for g in self.buckets for n in g.names]
        self.dense_names = dense
        self.names: List[str] = self.compressed_names + dense
        #: end of the compressed storage; [t_data, t_compressed) is the gap
        self.t_data = off
        #: scatter sentinel — always a structural-zero slot (the gap is
        #: at least one slot wide even when t_data is already aligned)
        self.t_compressed = _round_up(off + 1, _ALIGN) if compressed else 0
        self.sentinel = self.t_data
        off = self.t_compressed
        for n in dense:
            self.offsets[n] = off
            off += self.sizes[n]
        self.p_data_end = off
        self.total = _round_up(off, _ALIGN) if off else 0
        #: minimal index dtype the flat offsets fit in: int32 normally,
        #: int64 at/above 2**31 slots (~8 GiB fp32 of parameters — the
        #: BASELINE "int64 idx" config row). The engine forces the int64
        #: wire format there (FlatDGCEngine.index_dtype) instead of
        #: silently wrapping; int64 device arrays need jax x64 mode.
        self.index_dtype = np.int32 if self.total < 2 ** 31 else np.int64
        # insertion order of `named` (the treedef leaf order), for unflatten
        self._tree_order = list(named)

    def _group_by_size(self, compressed: Sequence[str]) -> List[List[str]]:
        """Partition the size-sorted tensors into contiguous buckets by an
        exact O(n^2) DP minimizing ``FLOOR_SLOTS * #buckets + padded
        slots`` — the measured per-step trade between per-bucket op floors
        and the bandwidth/storage cost of row padding. Big tensors stay in
        tight buckets (padding a 1M-row to 2.4M costs more than a bucket
        floor); the small-tensor tail collapses into few buckets (its
        padding is absolutely cheap, the floors are not)."""
        names = sorted(compressed, key=lambda n: -self.sizes[n])
        n = len(names)
        if n == 0:
            return []
        sizes = [self.sizes[x] for x in names]
        best = [float("inf")] * (n + 1)
        best[n] = 0.0
        cut = [n] * (n + 1)
        for i in range(n - 1, -1, -1):
            cols = kernels.ladder_cols(sizes[i])
            pad = 0
            for j in range(i, n):
                pad += cols - sizes[j]
                c = self.FLOOR_SLOTS + pad + best[j + 1]
                if c < best[i]:
                    best[i] = c
                    cut[i] = j + 1
        groups: List[List[str]] = []
        i = 0
        while i < n:
            groups.append(names[i:cut[i]])
            i = cut[i]
        return groups

    @classmethod
    def for_compressor(cls, tree, compressor) -> "ParamLayout":
        """The canonical layout for a compressor: its initialized attributes
        are the compressed names (the dim>1 selection the harness feeds to
        ``initialize``, reference train.py:136-140). Single source of truth
        for the compressed-first ordering — use this everywhere a layout and
        an engine must agree on offsets."""
        return cls(tree, list(getattr(compressor, "attributes", {}) or {}))

    # -------------------------------------------------------------- #

    def placed_names(self) -> Tuple[str, ...]:
        """The tensors :meth:`flatten` with ``place`` puts into their slot
        with ``kernels.place_rows`` instead of concatenating them, in
        storage order: geometry alone. A float32 tensor of
        ``PLACE_MIN_BYTES`` or more whose 2-D view ``[prod(shape[:-1]),
        shape[-1]]`` is whole (8, 128) tiles (so the view of the backward
        pass's output is a bitcast), whose slot starts and ends on a tile
        of the buffer's [total / 128, 128] view, and which has no row
        tail."""
        if self.dtype.itemsize != 4:
            return ()
        cols_of = {n: g.cols for g in self.buckets for n in g.names}
        out = []
        for n in self.names:
            shape, size = self.shapes[n], self.sizes[n]
            if (len(shape) >= 2 and 4 * size >= PLACE_MIN_BYTES
                    and cols_of.get(n, size) == size
                    and kernels.place_rows_eligible(
                        self.total, self.offsets[n],
                        size // shape[-1], shape[-1])):
                out.append(n)
        return tuple(out)

    def pack_bytes(self) -> Dict[str, int]:
        """Bytes of the flat buffer :meth:`flatten` with ``place`` writes
        by each path: ``place`` (:meth:`placed_names`) and ``concat`` (the
        rest, structural zeros included)."""
        item = self.dtype.itemsize
        placed = item * sum(self.sizes[n] for n in self.placed_names())
        return {"place": placed, "concat": self.total * item - placed}

    def flatten(self, tree, place: bool = False) -> jax.Array:
        """Pytree -> flat [P] (layout order, structural-zero row tails /
        gaps). Traced into the train step as the gradient packer
        (training/step.py) — keep it free of host-side work. XLA does NOT
        fuse the concatenation into the backward's writes: on the chip it
        is an op of its own that copies every gradient once more, 1-D to
        1-D, at 316-367 GB/s (half the chip's sustained rate), after a
        relayout copy of each large 2-D gradient from the backward pass's
        (8, 128) tiles to row-major (3.03 + 1.45 ms a step at VGG-16-BN;
        PERF.md §6, PR 43). So a tensor of :meth:`placed_names` is written
        into its slot by ONE pass from the tiles it was produced in, and
        only the runs between such slots are concatenated, each put in
        place by a ``dynamic_update_slice`` of the same buffer: every slot
        of [0, total) is written exactly once, bitwise as the
        concatenation of everything writes it. A layout with no such
        tensor (every dense layout, every ResNet) traces the plain
        concatenation, and so does every caller that does not say
        ``place``: the step's body does (per-device code under
        ``shard_map``); a state built under a ``jit`` over several
        devices cannot, because XLA cannot partition a Mosaic call."""
        if not self.names:
            return jnp.zeros((0,), self.dtype)
        named, _ = named_flatten(tree)
        placed = self.placed_names() if place else ()
        flat = None
        for n in placed:
            flat = kernels.place_rows(
                named[n].reshape(-1, self.shapes[n][-1]), self.offsets[n],
                self.total, into=flat)
        for start, parts in self.unplaced_runs(named, placed):
            run = jnp.concatenate(parts)
            flat = run if flat is None else jax.lax.dynamic_update_slice(
                flat, run, (start,))
        return flat

    def unplaced_runs(self, named, placed) -> List[Tuple[int, list]]:
        """``(start, parts)`` of each run of the buffer between the slots
        of ``placed``, in storage order: the 1-D forms of the tensors
        ``named`` and the structural zeros that, concatenated, fill
        [start, the next placed slot or total)."""
        # storage order: (tensor or None for structural zeros, elements)
        order: List[Tuple[Optional[str], int]] = []
        for g in self.buckets:
            for n in g.names:
                order += [(n, self.sizes[n]), (None, g.cols - self.sizes[n])]
        order.append((None, self.t_compressed - self.t_data))
        order += [(n, self.sizes[n]) for n in self.dense_names]
        order.append((None, self.total - self.p_data_end))
        runs: List[Tuple[int, list]] = [(0, [])]
        at = 0
        for n, count in order:
            at += count
            if n in placed:  # dgclint: ok[tracer-branch] — a tuple of names
                runs.append((at, []))
            elif n is not None:
                runs[-1][1].append(jnp.ravel(named[n]))
            elif count > 0:
                runs[-1][1].append(jnp.zeros((count,), self.dtype))
        return [run for run in runs if run[1]]

    def pieces(self) -> List[Tuple[int, int, Tuple[str, ...]]]:
        """The flat buffer cut where its content changes hands: half-open
        runs ``(start, stop, names)`` that tile ``[0, total)`` once, in
        storage order — each size bucket's tile, the gap up to
        ``t_compressed``, each dense-tail tensor, the buffer's padding.
        A run holds the gradients of ``names`` (none: structural zeros)
        and of no other tensor, so what is computed from it alone can
        start when THEY are final."""
        cuts = [(g.base, g.names) for g in self.buckets]
        cuts.append((self.t_data, ()))
        cuts += [(self.offsets[n], (n,)) for n in self.dense_names]
        cuts.append((self.p_data_end, ()))
        out = []
        for (lo, names), (hi, _) in zip(cuts, cuts[1:] + [(self.total, ())]):
            if hi > lo:
                out.append((lo, hi, tuple(names)))
        return out

    def unflatten(self, flat: jax.Array, transform=None):
        """Flat [P] -> pytree with the original structure. ``transform``
        (name, array) -> array wraps each view as it is built (the train
        step's per-tensor convert-hoisting guards, training/step.py)."""
        named = {n: flat[self.offsets[n]:self.offsets[n] + self.sizes[n]]
                 .reshape(self.shapes[n]) for n in self._tree_order}
        if transform is not None:
            named = {n: transform(n, a) for n, a in named.items()}
        return named_unflatten(named, self.treedef)

    def convert_hoist_risky(self) -> frozenset:
        """Compressed tensors whose flat-buffer view XLA can rewrite as
        ``slice(reshape(P))`` — base offset AND the buffer total both
        multiples of ``prod(shape[1:])``. Under auto-bf16 conv precision
        the simplifier then hoists the weight convert over the WHOLE
        buffer (see ``ops.kernels.opaque_view`` for the measured cost and
        the fix). Only tensors much smaller than the buffer qualify: at
        ``total < 4 * numel`` the whole-buffer convert costs about what
        XLA's direct slice+convert does (it picks that form for VGG's
        fc1, 74% of the buffer), while the guard's copy is pure
        addition."""
        out = set()
        for n in self.compressed_names:
            shape = self.shapes[n]
            if len(shape) < 2 or self.total < 4 * self.sizes[n]:
                continue
            trailing = int(np.prod(shape[1:], dtype=np.int64))
            if (trailing > 1 and self.offsets[n] % trailing == 0
                    and self.total % trailing == 0):
                out.add(n)
        return frozenset(out)

    def unflatten_named(self, flat: jax.Array, keep_1d: bool = False):
        """Flat [P] -> {name: array} (layout order)."""
        out = {}
        for n in self.names:
            piece = flat[self.offsets[n]:self.offsets[n] + self.sizes[n]]
            out[n] = piece if keep_1d else piece.reshape(self.shapes[n])
        return out

    def mask_vector(self, predicate) -> "LayoutMask":
        """Per-coordinate 0/1 mask over the flat buffer from a per-name
        predicate (e.g. the optimize_bn_separately weight-decay split,
        reference train.py:121-125), held as geometry: a
        :class:`LayoutMask`. Hand it to an optimizer as
        ``weight_decay_mask`` (it is callable on the flat params and
        builds the mask in-register); ``np.asarray`` of it is the [P]
        float32 vector. Do not pass that vector on as a device array: a
        [P] mask closed over by the step is a compile-time constant, and
        XLA folds each expression the optimizer uses it in (``wd * m``,
        ``m``, ``1 - m``) into a [P] constant of its own that the
        optimizer's HBM-bound fusion then streams beside p, buf and g."""
        spans = sorted((self.offsets[n], self.offsets[n] + self.sizes[n],
                        bool(predicate(n)))
                       for n in self.names if self.sizes[n])
        return LayoutMask(self.total, self.index_dtype, spans)


class LayoutMask:
    """A 0/1 mask over a :class:`ParamLayout`'s flat [total] buffer, kept
    on the host as intervals instead of as a [total] array.

    ``runs`` are the merged half-open intervals of ones. Only the real
    coordinates of a tensor bind the mask: row tails, the gap and the
    buffer tail are structural zeros in parameters, gradients and
    optimizer state, where ``wd * mask * 0`` is 0 whatever the mask says,
    so those slots join whichever neighbouring run merges (ResNet-50's
    BatchNorm split: 2 runs where the exact vector has 22), and the first
    and last run reach the buffer's ends where no masked-out tensor lies
    between.

    Called on the flat params (the optimizers' ``weight_decay_mask``
    callable contract, ``optim/sgd.py``) it builds a boolean [total] mask
    from ``lax.iota`` and one or two range compares per run: nothing
    [total]-sized enters the optimizer's fusion but p, buf and g. XLA does
    not fold an iota, as it would a small constant padded or concatenated
    up to [total]. ``np.asarray(mask)`` is the exact float32 vector (zero
    on every structural slot) for host users.
    """

    #: most runs the iota form takes. A run is two compares, an ``and``
    #: and an ``or`` per element on the VPU, which hide under the fusion's
    #: five HBM streams while they are few: the update over ResNet-50's
    #: [27.1M] buffer on a v5e takes 0.811 ms with no mask and with 1 to 8
    #: runs, 0.817 with 16, 0.845 with 24, 1.12 with 32 (VPU-bound from
    #: ~22 on), against 0.954 (``sgd``) and 1.239 (``dgc_sgd``) with the
    #: [total] vector's constants (PERF.md §6, PR 25). The tree's configs
    #: have 2 runs (DGC layout) and 16 (ResNet-50 with no compressed
    #: block: a run per bottleneck). A predicate that alternates over more
    #: tensors than this takes the [total] vector.
    MAX_RUNS = 16

    def __init__(self, total: int, index_dtype, spans):
        """``spans``: sorted ``(start, stop, one)`` of every non-empty
        tensor's real coordinates."""
        self.total = int(total)
        self.index_dtype = index_dtype
        self._ones = [(a, b) for a, b, one in spans if one]
        runs: List[List[int]] = []
        prev = True            # no masked-out tensor since the last run
        for start, stop, one in spans:
            if one and prev and runs:
                runs[-1][1] = stop
            elif one:
                runs.append([0 if prev else start, stop])
            prev = one
        if runs and prev:
            runs[-1][1] = self.total
        self.runs: Tuple[Tuple[int, int], ...] = tuple(map(tuple, runs))

    @property
    def form(self) -> str:
        """``"runs"``: built in-register from the geometry; ``"vector"``:
        too many runs, the [total] constant."""
        return "runs" if len(self.runs) <= self.MAX_RUNS else "vector"

    def __array__(self, dtype=None, copy=None):
        out = np.zeros((self.total,), np.float32)
        for a, b in self._ones:
            out[a:b] = 1.0
        return out if dtype is None else out.astype(dtype, copy=False)

    def __call__(self, flat) -> jax.Array:
        if getattr(flat, "shape", None) != (self.total,):
            raise ValueError(
                f"a layout mask covers the flat [{self.total}] buffer, "
                f"got params {getattr(flat, 'shape', type(flat))}")
        _trace.count("optimizer.wd_mask", 1, form=self.form,
                     runs=len(self.runs))
        if self.form == "vector":
            return jnp.asarray(np.asarray(self))
        return self.at(jax.lax.iota(self.index_dtype, self.total))

    def at(self, idx) -> jax.Array:
        """The ``runs`` form at the flat coordinates ``idx`` (any shape):
        what the call builds over the whole buffer, for a reader that
        holds a block of it (``optim/sgd.py::ElementwiseRule``)."""
        mask = jnp.zeros(idx.shape, bool)
        for start, stop in self.runs:
            inside = jnp.ones(idx.shape, bool)
            if start > 0:
                inside &= idx >= start
            if stop < self.total:
                inside &= idx < stop
            mask |= inside
        return mask


class _Bucket(NamedTuple):
    """Ratio-dependent sparsification attributes of one layout bucket
    (all static, host-side). The storage geometry lives in the layout's
    ``_BucketGeom``; the [rows, cols] view over the flat buffer is a pure
    reshape at ``base`` (kernels pad rows to the sublane in-trace)."""
    base: int                  # start of the tile in the flat buffer
    rows: int                  # real rows R
    cols: int                  # row width (ladder-kernel block aligned)
    row_offsets: np.ndarray    # [R] global offset of each tensor row
    numels: np.ndarray         # [R]
    strides: np.ndarray        # [R] sampling stride
    num_samples: np.ndarray    # [R]
    max_s: int
    topk_samples: np.ndarray   # [R]
    max_k: int
    num_selects: np.ndarray    # [R]
    max_sel: int
    adapt: np.ndarray          # [R] bool: run threshold adaptation
    exact: bool                # every row samples its whole tensor
    tight: np.ndarray          # [payload] positions into the [R*max_sel] grid
    payload: int
    #: runs of consecutive rows sharing a sample stride: (r0, r1, stride, n)
    #: with n = max num_samples in the run — the strided sample of such a
    #: run is ONE dynamic_slice of the [Rg, n, stride] reshape (see
    #: sparsify)
    stride_groups: Tuple[Tuple[int, int, int, int], ...]


#: single-tensor bucket rows wider than this are split into S segments
#: (stratified selection): approx top-k over ONE giant row has no row
#: parallelism and its k grows with the tensor — VGG-16's fc1
#: ([1, 102.8M], k=102761) measured 19.6 ms PartialReduce + 17.2 ms
#: aggregation sort per step on v5e (device profile). Split into
#: ~4M-wide segments with the per-tensor quota distributed EXACTLY
#: (payload/wire volume unchanged), each segment estimating its own
#: sampled threshold — selection becomes "threshold passers, capped per
#: segment", the stratified analogue of the reference's index-order
#: truncation (compression.py:151); misses stay in error feedback.
_SPLIT_COLS = 8 * 1024 * 1024
_SPLIT_TARGET = 4 * 1024 * 1024

#: maximum wire-payload growth a bucket may pay to make its payload the
#: full [R, max_sel] selection grid (identity ``tight`` map — both
#: payload-scale compaction gathers skipped; see _bucket_from_rows)
_PAD_PAYLOAD_MAX_FRAC = 0.02


def _segment_rows(name, attrs, base, cols, sample_ratio, compress_ratio):
    """Split one giant tensor row into S segment rows: returns
    (seg_cols, list of per-segment TensorAttrs-like tuples
    (row_off, numel, stride, num_samples, topk_samples, num_selects))."""
    from dgc_tpu.compression.dgc import sampling_geometry
    S = 1
    while (cols % (2 * S) == 0 and cols // (2 * S) >= _SPLIT_TARGET
           and attrs.num_selects >= 2 * S):
        S *= 2
    seg_cols = cols // S
    rows = []
    rem_sel = attrs.num_selects
    rem_numel = attrs.numel
    for s in range(S):
        numel_s = min(seg_cols, attrs.numel - s * seg_cols)
        assert numel_s > 0, (name, s, seg_cols, attrs.numel)
        # proportional quota with exact total (largest-remainder on the
        # running remainder keeps sum == num_selects)
        ns = (rem_sel if s == S - 1
              else int(round(rem_sel * numel_s / rem_numel)))
        ns = max(1, min(ns, rem_sel - (S - 1 - s)))
        rem_sel -= ns
        rem_numel -= numel_s
        num_samples, stride = sampling_geometry(numel_s, sample_ratio,
                                                compress_ratio)
        topk = max(1, int(math.ceil(num_samples * compress_ratio)))
        rows.append((base + s * seg_cols, numel_s, stride, num_samples,
                     topk, ns))
    return seg_cols, rows


def _build_buckets(attributes, layout: ParamLayout,
                   compressor=None) -> List[_Bucket]:
    """Per-ratio sparsification attributes for each of the layout's size
    buckets (the geometry itself is ratio-independent, layout.buckets)."""
    buckets: List[_Bucket] = []
    for g in layout.buckets:
        if (compressor is not None and len(g.names) == 1
                and g.cols > _SPLIT_COLS
                and attributes[g.names[0]].num_selects >= 2):
            name = g.names[0]
            seg_cols, rows = _segment_rows(
                name, attributes[name], g.base, g.cols,
                compressor.sample_ratio, compressor.compress_ratio)
            if len(rows) > 1:
                buckets.append(_bucket_from_rows(g.base, seg_cols, rows))
                continue
        rows = [(layout.offsets[n], a.numel, a.sample_stride,
                 a.num_samples, a.top_k_samples, a.num_selects)
                for n, a in ((n, attributes[n]) for n in g.names)]
        buckets.append(_bucket_from_rows(g.base, g.cols, rows))
    return buckets


def _bucket_from_rows(base: int, cols: int, rows) -> _Bucket:
    """Assemble a :class:`_Bucket` from per-row tuples
    ``(row_off, numel, stride, num_samples, topk_samples, num_selects)``.

    The bucket's wire payload is normally the TIGHT concatenation of each
    row's ``num_selects`` slots, extracted from the selection's
    [R, max_sel] grid by the static ``tight`` gather. When the rows'
    quotas are nearly uniform (the VGG fc segments: equal splits ±1) that
    gather moves payload-scale data to drop almost nothing — so when
    padding the payload to the full [R * max_sel] grid would grow the
    wire by at most ``_PAD_PAYLOAD_MAX_FRAC``, the payload IS the grid:
    ``tight`` becomes the identity, sparsify skips both payload-scale
    compaction gathers (values + indices), and the extra slots ride the
    wire as structural no-ops ((0.0, sentinel) — the scatter-add
    contract, SURVEY.md §2.5). Real transmitted elements per tensor stay
    <= num_selects either way (the reference's contract,
    compression.py:151); only the fixed wire shape grows, bounded by the
    gate (measured +0.1% at VGG's fc buckets vs ~1 ms of gathers; tight
    ResNet-20 buckets would inflate 35% and keep the gather)."""
    cols_in = list(zip(*rows))
    # offsets can exceed int32 at the int64-wire scale; the rest are
    # tensor-local and always fit
    offs = np.array(cols_in[0], np.int64)
    numels, strides, samples, topks, selects = (
        np.array(c, np.int32) for c in cols_in[1:])
    num_selects = selects
    max_sel = int(num_selects.max())
    n_rows_ = len(rows)
    padded = n_rows_ * max_sel
    if padded - int(num_selects.sum()) <= (
            _PAD_PAYLOAD_MAX_FRAC * int(num_selects.sum())):
        tight = np.arange(padded, dtype=np.int64)
    else:
        tight = np.concatenate([
            np.arange(r * max_sel, r * max_sel + k, dtype=np.int64)
            for r, k in enumerate(num_selects)])
    stride_groups = []
    n_rows = len(rows)
    r0 = 0
    for r in range(1, n_rows + 1):
        if r == n_rows or strides[r] != strides[r0]:
            stride_groups.append((r0, r, int(strides[r0]),
                                  int(samples[r0:r].max())))
            r0 = r
    return _Bucket(
        base=base,
        rows=n_rows,
        cols=cols,
        row_offsets=offs,
        numels=numels,
        strides=strides,
        num_samples=samples,
        max_s=int(samples.max()),
        topk_samples=topks,
        max_k=int(topks.max()),
        num_selects=num_selects,
        max_sel=max_sel,
        adapt=numels > samples,
        exact=bool((samples >= numels).all()),
        tight=tight,
        payload=int(tight.shape[0]),
        stride_groups=tuple(stride_groups),
    )


def _exact_topk(x: jax.Array, k: int):
    """Exact per-row top-k: the Pallas iterative-max kernel on TPU (bitwise
    lax.top_k-compatible, kernels.topk_rows) where its k sequential
    max-extractions cost less than XLA's sort-based lowering — measured
    crossover ~2M element-extractions per row block on v5e (ResNet-20's
    [22, 36864] k=37 bucket: kernel 0.14 vs sort 0.16 ms; ResNet-50's
    [19, 65536] k=66: kernel 0.52 vs sort 0.42 ms, device profile).
    topk_rows additionally self-gates on k <= lane width and VMEM budget;
    off-TPU always lax.top_k (the interpreter would be slower than the
    native sort)."""
    if kernels.use_pallas() and k * x.shape[1] <= 2_000_000:
        return kernels.topk_rows(x, k)
    return jax.lax.top_k(x, k)


def _ladder_adapt(imp_rows, thr, num_selects, adapt_mask, lower,
                  max_iters: int):
    """One-pass threshold adaptation for ``resample=True``.

    With resample, the reference's loop only LOWERS the threshold
    (x lower_bound while too few pass, compression.py:139-149; overflow is
    resolved by the exact top-k select). The trajectory therefore lives on
    the static ladder ``thr * lb^i``, and the sequential stopping rule
    "first i with count >= lo, else max_iters" is a closed-form pick once
    all ladder counts are known — computed in ONE pass over the rows
    (Pallas kernel on TPU; its jnp reference elsewhere) instead of one full
    re-scan per loop iteration.

    The engine's hot path no longer calls this (it derives the identical
    ladder choice from the selection top-k, :func:`_ladder_adapt_from_topk`
    — zero extra HBM passes); kept as the full-scan reference the
    equivalence test pins the derivation against."""
    levels = max_iters + 1
    if kernels.use_pallas():
        counts = kernels.ladder_counts(imp_rows, thr, lower, levels)
    else:
        counts = kernels.ladder_counts_reference(imp_rows, thr, lower,
                                                 levels)
    return _ladder_pick(counts, thr, num_selects, adapt_mask, lower,
                        max_iters)


def _ladder_pick(counts, thr, num_selects, adapt_mask, lower,
                 max_iters: int):
    """Closed-form ladder stopping rule from per-level pass counts:
    first i with count >= lower * num_selects, else max_iters."""
    lo = (lower * num_selects)[:, None]                   # [R, 1]
    passing = counts.astype(jnp.float32) >= lo            # [R, L]
    first = jnp.argmax(passing, axis=1).astype(jnp.int32)
    i_star = jnp.where(jnp.any(passing, axis=1), first, max_iters)
    adapted = thr * (lower ** i_star.astype(thr.dtype))
    return jnp.where(adapt_mask, adapted, thr)


def _ladder_adapt_from_topk(top_scores, thr, num_selects, adapt_mask,
                            lower, max_iters: int):
    """Ladder adaptation with ZERO extra HBM passes: the per-level counts
    are derived from the (sorted) selection top-k values instead of
    re-scanning the [R, cols] importance block.

    Why this is exact (equal to :func:`_ladder_adapt` on the same
    selection): for any level t, if the true count ``#{imp >= t}`` is at
    most k, every such element is inside the top-k, so the count computed
    over ``top_scores`` equals it; if the true count exceeds k, the top-k
    count saturates at k — but the stopping rule only asks ``count >=
    lower * num_selects`` and ``lower * num_selects <= num_selects <= k``,
    so a saturated count passes exactly when the true count does. Hence
    the chosen level i* is identical. (With approximate selection the
    top-k itself is approximate; the derived counts inherit exactly the
    selection's recall, nothing more — and on CPU, where approx_max_k
    lowers to an exact sort, the equality is bitwise.)"""
    levels = max_iters + 1
    t = thr[:, None] * (lower ** jnp.arange(levels, dtype=thr.dtype))[None]
    counts = jnp.sum(top_scores[:, :, None] >= t[:, None, :], axis=1)
    return _ladder_pick(counts, thr, num_selects, adapt_mask, lower,
                        max_iters)


def _batched_adapt(imp_rows, thr, num_selects, adapt_mask, lower, upper,
                   max_iters: int, resample: bool):
    """Batched threshold adaptation — same per-row semantics as
    ``ops.adapt_threshold`` (reference compression.py:128-149), run for all
    rows of a bucket simultaneously in one bounded while_loop."""
    lo = lower * num_selects
    hi = upper * num_selects

    def count(t):
        return jnp.sum(imp_rows >= t[:, None], axis=1)

    def need(c):
        n = (c < lo) if resample else ((c < lo) | (c > hi))
        return n & adapt_mask

    def cond(carry):
        t, c, it = carry
        return (it < max_iters) & jnp.any(need(c))

    def body(carry):
        t, c, it = carry
        nt = jnp.where(c < lo, t * lower, jnp.where(c > hi, t * upper, t))
        nt = jnp.where(need(c), nt, t)
        return nt, count(nt), it + 1

    thr, _, _ = jax.lax.while_loop(cond, body,
                                   (thr, count(thr), jnp.int32(0)))
    return thr


class _Lanes(NamedTuple):
    """The sparse wire: what one worker hands the all-gathers (gathered:
    the same with a leading [W]), in the order they are issued. A lane the
    plan does not use is None."""
    q: Optional[jax.Array] = None       # int8: int8 payload, int4 nibbles
    f32: Optional[jax.Array] = None     # native values, then both scales
    f16: Optional[jax.Array] = None
    words: Optional[jax.Array] = None   # uint32: bit-packed, Elias-Fano
    plain: Optional[jax.Array] = None   # flat offsets


#: one gossip round (compression/gossip.py): the dropped workers ([W] bool,
#: under fault injection only, else None), is it a full sync, was that forced
#: by staleness, the new ages, this worker's index, each sender's row weight
_GossipRound = collections.namedtuple(
    "_GossipRound", "dropped full forced new_age widx row_w")


@dataclasses.dataclass
class InPlaceUpdate:
    """A step's offer to :meth:`FlatDGCEngine.exchange`: run the
    elementwise update of my flat [P] ``state`` where the sparse tier's
    gradient is made, instead of handing me a [P] gradient to read.
    ``rule(g, idx, scalars, *blocks) -> new blocks`` and ``scalars()``
    are those of ``kernels.payload_update_bits`` (``scalars`` is traced
    only when the offer is taken: a step that keeps today's form
    compiles today's program). An engine that takes the offer replaces
    ``state`` by buffers whose compressed block [0, T) is updated and
    whose tail [T, P) is as it was, sets ``taken``, and returns the
    tail's gradient alone."""
    state: Tuple[jax.Array, ...]
    rule: Callable
    scalars: Callable[[], Tuple[jax.Array, ...]]
    taken: bool = False


@dataclasses.dataclass
class _Exchange:
    """What one ``FlatDGCEngine.exchange`` call carries from stage to stage
    (trace-time values; a stage fills what the later ones read)."""
    grad: Any                       # [P], node-reduced
    mem: Dict                       # the memory as handed in
    update: Optional[InPlaceUpdate] = None    # the step's offer
    taps: Any = None                # telemetry.taps with telemetry on
    grad_norm: Any = None
    clip_delta: Any = None
    gd: Any = None                  # grad[T:]
    mc: Any = None                  # live momentum, velocity of [0, T)
    vc: Any = None
    md: Any = None                  # live momentum of the tail
    mc_prev: Any = None             # mc, vc before this step's compensate
    vc_prev: Any = None
    gossip: Optional[_GossipRound] = None
    sel_stats: Optional[Dict] = None
    values: Any = None              # this worker's payload
    indices: Any = None
    acc: Any = None                 # [T] the sparse tier's contribution
                                    # (None: it went into the offer's state)
    new_bits: Any = None            # this step's transmit record
    inbox: Any = None               # gossip: the round's neighbor mass


class FlatDGCEngine:
    """Fused flat-buffer execution of the DGC pipeline for one compressor +
    layout pair. Rebuilt (cheaply, host-side) whenever the warm-up schedule
    changes the compress ratio (reference compression.py:91-107)."""

    #: ``exchange`` takes the step's :class:`InPlaceUpdate` offer
    takes_update = True

    def __init__(self, compressor, layout: ParamLayout, plan=None):
        self.c = compressor
        self.layout = layout
        self.T = layout.t_compressed
        # wire index dtype: int32 unless the flat offsets cannot fit
        # (layout.total >= 2**31, the BASELINE "int64 idx" row) or the
        # config explicitly asks for the int64 wire format
        # (int32_indices=False, reference compression.py:26 semantics)
        want64 = (not getattr(compressor, "int32_indices", True)
                  or np.dtype(layout.index_dtype) == np.int64)
        if want64 and not jax.config.jax_enable_x64:
            raise RuntimeError(
                "the int64 index wire format needs jax x64 mode: enable "
                "jax_enable_x64 (JAX_ENABLE_X64=1 or "
                "jax.experimental.enable_x64()) — required because "
                f"int32_indices={getattr(compressor, 'int32_indices', True)}"
                f" and the flat layout holds {layout.total} slots")
        self.index_dtype = jnp.int64 if want64 else jnp.int32
        # ratio >= 1.0 transmits everything dense (per-tensor path's
        # `compress_ratio < 1.0` guard) — no buckets, no sparse payload
        self.buckets = (_build_buckets(compressor.attributes, layout,
                                       compressor)
                        if compressor.compress_ratio < 1.0 else [])
        # --- per-bucket exchange regimes (compression/planner.py) ---
        # plan=None derives one uniform regime from the legacy compressor
        # flags, so every pre-planner configuration keeps its exact wire;
        # a Plan (or a plain regime sequence) may mix regimes per bucket.
        if plan is None:
            regimes = (self._legacy_regime(),) * len(self.buckets)
            self.plan = None
        else:
            regimes = tuple(getattr(plan, "regimes", plan))
            if len(regimes) != len(self.buckets):
                raise ValueError(
                    f"plan carries {len(regimes)} regimes for "
                    f"{len(self.buckets)} buckets — the plan was built for "
                    "a different geometry; call Plan.replan(engine) after "
                    "every warmup compress-ratio change")
            self.plan = plan if hasattr(plan, "regimes") else None
        unknown = [r for r in regimes if r not in _REGIMES]
        if unknown:
            raise ValueError(f"unknown exchange regime(s) {unknown}; "
                             f"expected one of {sorted(_REGIMES)}")
        self.regimes: Tuple[str, ...] = regimes
        rk = [_REGIMES[r] for r in regimes]
        #: bucket ids by role: dense-planned buckets ride the fallback
        #: psum slab-wise; the sparse pipeline runs over the rest
        self._sparse_ids = [i for i, (k, _) in enumerate(rk) if k != "d"]
        self._dense_ids = [i for i, (k, _) in enumerate(rk) if k == "d"]
        sparse = [self.buckets[i] for i in self._sparse_ids]
        self._sparse_buckets = sparse
        #: per SPARSE bucket (payload order): value kind / packed flag
        self._kinds = tuple(rk[i][0] for i in self._sparse_ids)
        self._packed = tuple(rk[i][1] for i in self._sparse_ids)
        #: per-worker wire payload in elements — the reference's sum of
        #: per-tensor num_selects (compression.py:151) over the SPARSE
        #: buckets, plus at most _PAD_PAYLOAD_MAX_FRAC of structural
        #: no-op slots per bucket whose payload is the padded
        #: [R, max_sel] grid (_bucket_from_rows; real transmitted
        #: elements per tensor stay <= num_selects either way)
        sl, off = [], 0
        for b in sparse:
            sl.append((off, off + b.payload))
            off += b.payload
        self._payload_slices = tuple(sl)
        self.payload_size = off
        self.payload_rows = sum(b.rows for b in sparse)
        #: adaptive-exchange statics (resilience/adaptive.py): per payload
        #: slot, its importance rank within its row and the row's full
        #: quota — from the bucket's tight map, so both tight and padded
        #: layouts are covered (see the _row_map note below). The top-k
        #: writes each row's selections in descending-|value| order, so
        #: masking slots with rank >= ceil(quota * send_frac) keeps
        #: exactly the LARGEST selected elements; at send_frac == 1 every
        #: structurally valid slot survives and the wire is bitwise
        #: unchanged.
        if sparse and self.payload_size:
            self._adaptive_rank = np.concatenate(
                [(b.tight % b.max_sel).astype(np.int32) for b in sparse])
            self._adaptive_quota = np.concatenate(
                [np.asarray(b.num_selects, np.float32)[b.tight // b.max_sel]
                 for b in sparse])
        else:
            self._adaptive_rank = None
            self._adaptive_quota = None
        #: kind-local chunk map: sparse bucket j's values ride value lane
        #: self._kinds[j] at [lo, hi) of that lane's concatenated
        #: payload; its indices ride the packed-words or plain-offsets
        #: lane likewise. Uniform plans have exactly one chunk per lane,
        #: and every chunk helper is the identity there — the lane
        #: machinery compiles away to the pre-planner wire.
        kof: Dict[str, int] = {}
        vloc = []
        for b, kk in zip(sparse, self._kinds):
            lo = kof.get(kk, 0)
            vloc.append((kk, lo, lo + b.payload))
            kof[kk] = lo + b.payload
        self._val_chunks = tuple(vloc)
        self._kind_payload = kof
        iof = {True: 0, False: 0, "delta": 0}
        iloc = []
        for b, p in zip(sparse, self._packed):
            iloc.append((p, iof[p], iof[p] + b.payload))
            iof[p] += b.payload
        self._idx_chunks = tuple(iloc)
        self._plain_payload = iof[False]
        #: int8 wire buckets: payload position -> tensor row (static,
        #: payload order = rows in int8-bucket order, num_selects entries
        #: each) for the per-TENSOR quantization scales; the scale wire
        #: is one f32 per row — negligible next to the payload
        i8 = [b for b, kk in zip(sparse, self._kinds) if kk == "i8"]
        self._i8_rows = sum(b.rows for b in i8)
        if i8 and self.payload_size:
            # per payload slot: owning tensor row — derived from the
            # bucket's tight map (slot s of the [R, max_sel] grid belongs
            # to row s // max_sel), so it is correct for both the tight
            # and the padded-payload layouts (_bucket_from_rows)
            rm, base = [], 0
            for b in i8:
                rm.append((b.tight // b.max_sel).astype(np.int32) + base)
                base += b.rows
            self._row_map = jnp.asarray(np.concatenate(rm))
        else:
            self._row_map = None
        #: int4 wire buckets (nibble-packed values on the i8 q lane):
        #: per-slot bucket map for the per-BUCKET quantization scale
        #: (one f32 each, appended to the f32 lane after the i8 row
        #: scales) and a per-bucket byte layout — each bucket's nibble
        #: stream pads to a whole byte on its own, so the per-bucket
        #: wire accounting is exact
        i4 = [b for b, kk in zip(sparse, self._kinds) if kk == "i4"]
        self._i4_buckets = len(i4)
        if i4 and self.payload_size:
            self._i4_map = jnp.asarray(np.concatenate(
                [np.full(b.payload, j, np.int32)
                 for j, b in enumerate(i4)]))
            ck, plo, blo = [], 0, 0
            for b in i4:
                nb = (b.payload + 1) // 2
                ck.append((plo, plo + b.payload, blo, blo + nb))
                plo, blo = plo + b.payload, blo + nb
            self._i4_chunks = tuple(ck)
        else:
            self._i4_map = None
            self._i4_chunks = ()
        #: static mask of int8 payload slots — only needed when int8
        #: error feedback must coexist with deferred-masking (non-i8)
        #: buckets in one mixed plan; None for every uniform plan
        if i8 and len(i8) != len(sparse):
            i8m = np.zeros((self.payload_size,), bool)
            for (s0, s1), kk in zip(self._payload_slices, self._kinds):
                if kk == "i8":
                    i8m[s0:s1] = True
            self._i8_slot_mask = i8m
        else:
            self._i8_slot_mask = None
        # bit-packed index wire (compression/wirecodec.py): per-slot
        # static tensor-local widths over the PACKED buckets; their
        # all_gather ships the uint32 bitstream instead of [payload]
        # int32 offsets (plain-index buckets keep their own lane)
        pk = [b for b, p in zip(sparse, self._packed) if p is True]
        if pk and self.payload_size:
            from dgc_tpu.compression.wirecodec import IndexCodec
            self._codec = IndexCodec(pk)
        else:
            self._codec = None
        # Elias-Fano index wire (int8_delta_idx): its word stream rides
        # the SAME gathered uint32 lane as the IndexCodec bitstream
        # (codec words first, delta words after). Encode needs each
        # delta bucket's payload sorted by canonical position, so the
        # engine records the per-bucket payload slices + per-slot row
        # bounds the sort key is built from (_sort_delta_payload).
        dl = [b for b, p in zip(sparse, self._packed) if p == "delta"]
        if dl and self.payload_size:
            from dgc_tpu.compression.wirecodec import DeltaIndexCodec
            self._dcodec = DeltaIndexCodec(dl)
            ds, dj = [], 0
            for (s0, s1), p in zip(self._payload_slices, self._packed):
                if p == "delta":
                    n = s1 - s0
                    ds.append((s0, s1,
                               self._dcodec.slot_off[dj:dj + n],
                               self._dcodec.slot_numel[dj:dj + n]))
                    dj += n
            self._delta_sort = tuple(ds)
        else:
            self._dcodec = None
            self._delta_sort = ()
        # receiver-side index clamp bounds: packed/delta slots enforce
        # their static row bounds (exactly what an honest encode can
        # produce); plain slots the generic [0, T) range. Mixed plans
        # stitch one full-payload bounds pair; uniform plans keep the
        # pre-planner arguments (codec arrays, or None/None for the
        # generic clamp).
        word_codecs = [c for c in (self._codec, self._dcodec)
                       if c is not None]
        if len(word_codecs) == 1 and not self._plain_payload:
            self._clamp_bounds = (word_codecs[0].slot_off,
                                  word_codecs[0].slot_numel)
        elif word_codecs:
            so = np.zeros((self.payload_size,), np.int64)
            sn = np.full((self.payload_size,), max(int(self.T), 1),
                         np.int64)
            pj = dj = 0
            for (s0, s1), p in zip(self._payload_slices, self._packed):
                if p is True:
                    so[s0:s1] = self._codec.slot_off[pj:pj + s1 - s0]
                    sn[s0:s1] = self._codec.slot_numel[pj:pj + s1 - s0]
                    pj += s1 - s0
                elif p == "delta":
                    so[s0:s1] = self._dcodec.slot_off[dj:dj + s1 - s0]
                    sn[s0:s1] = self._dcodec.slot_numel[dj:dj + s1 - s0]
                    dj += s1 - s0
            self._clamp_bounds = (so, sn)
        else:
            self._clamp_bounds = (None, None)
        #: opt-in payload checksum (resilience.integrity): one int32 word
        #: per sparse bucket over the exact wire bits, shipped on the
        #: index gather. Verified only when the caller passes
        #: ``health_out`` to ``exchange`` (the guarded step does); the
        #: counter surfaces as the ``checksum_failures`` guard metric.
        self.checksum = (bool(getattr(compressor, "checksum", False))
                         and self.payload_size > 0)
        if self.checksum and self._row_map is not None:
            raise ValueError(
                "checksum=True is not supported with int8_values — the "
                "per-row f32 scale wire would ride uncovered; use the "
                "fp16/f32 value wire")
        if self.checksum and self._i4_buckets:
            raise ValueError(
                "checksum=True is not supported with the int4_packed "
                "wire — the per-bucket f32 scale wire would ride "
                "uncovered; use the fp16/f32 value wire")
        sparse_set = set(r for r in regimes if r != "dense")
        if self.checksum and len(sparse_set) > 1:
            raise ValueError(
                "checksum=True needs one wire format across the sparse "
                f"buckets; the plan mixes {sorted(sparse_set)} — plan "
                "with candidates=('dense', <one regime>) or disable the "
                "checksum")
        self._num_seg = len(sparse)
        if self.checksum:
            from dgc_tpu.resilience.integrity import bucket_segments
            self._seg_ids = bucket_segments(sparse)
        else:
            self._seg_ids = None
        #: any sparse bucket selects through the segment-top-2 kernel:
        #: the TPU compensate pass then emits the candidates itself
        #: (kernels.fused_compensate_bits_cands) instead of a standalone
        #: kernel re-reading the velocity it just wrote
        self._seg_fused = any(self._use_seg_kernel(b) for b in sparse)
        #: two-megakernel hot path: opt-in via
        #: ``DGCCompressor(megakernel=True)`` / configs/dgc/megakernel.py
        #: / ``DGC_MEGAKERNEL=1``. Plan-static — when off, nothing below
        #: is traced and the program is byte-identical to the unfused
        #: engine (contract: megakernel-off-compiles-away).
        self._megakernel = bool(
            getattr(compressor, "megakernel", False)
            or os.environ.get("DGC_MEGAKERNEL", "") == "1")
        #: bucket ids the forward megakernel owns (one fused
        #: compensate->threshold->select->pack pass each); the
        #: complement spans keep the plain compensate and their usual
        #: selection paths
        self._mk_fwd_ids = tuple(
            bi for bi in self._sparse_ids if self._use_megakernel_fwd(bi))
        # --- gossip exchange (compression/gossip.py) ----------------- #
        # plan-static: self._gossip is the GossipConfig when the plan
        # carries a gossip family, else None — and None lowers ZERO
        # extra ops (contract: gossip-off-compiles-away). The Plan
        # already rejects mixed gossip families / gossip next to other
        # sparse regimes; what's validated here is what only the ENGINE
        # knows.
        self._gossip = getattr(self.plan, "gossip", None)
        if self._gossip is not None:
            if self._mem is None:
                raise ValueError(
                    "gossip regimes need momentum-correction memory "
                    "(DGCSGDMemory): a worker's untransmitted mass must "
                    "live in the error-feedback residual between "
                    "neighborhood rounds")
            if not self._sparse_ids:
                raise ValueError(
                    "gossip plan has no sparse buckets — with an all-"
                    "dense plan (or compress_ratio >= 1) there is no "
                    "neighborhood payload to exchange; plan without the "
                    "gossip candidates instead")
            if self._megakernel:
                raise ValueError(
                    "megakernel=True is not supported with gossip "
                    "regimes: the fused forward emits its candidates "
                    "before the neighborhood inbox is folded into the "
                    "velocities, so they would be one round stale")
            if getattr(self.c, "fused_apply", False):
                raise ValueError(
                    "fused_apply=True is not supported with gossip "
                    "regimes: the fused scatter cannot split the "
                    "gathered payload between parameters (full-sync "
                    "round) and the neighborhood inbox (gossip round)")
            # the seg-top2 fused compensate also emits selection
            # candidates before the inbox fold — run the plain
            # compensate + standalone selection under gossip instead
            self._seg_fused = False

    def _legacy_regime(self) -> str:
        """The uniform wire regime the compressor flags describe — what
        every ``plan=None`` engine runs, bit-for-bit the pre-planner
        behavior."""
        c = self.c
        if getattr(c, "int8_values", False):
            base = "int8"
        elif getattr(c, "fp16_values", False):
            base = "fp16"
        else:
            base = "fp32"
        return base + ("_packed"
                       if getattr(c, "packed_indices", False) else "")

    def _chunks(self, arr: jax.Array, flags, want) -> jax.Array:
        """Concatenated payload chunks of the sparse buckets whose flag in
        ``flags`` (``self._kinds``: the value lane; ``self._packed``: the
        three-valued index lane) is ``want`` — the identity when all share
        it (uniform plans keep their exact pre-planner wire arrays)."""
        if all(f == want for f in flags):  # dgclint: ok[tracer-branch] — flags are plan-static regime flags, not a tracer
            return arr
        parts = [arr[s0:s1] for (s0, s1), f
                 in zip(self._payload_slices, flags) if f == want]
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts)

    def _sort_delta_payload(self, values: jax.Array, indices: jax.Array
                            ) -> Tuple[jax.Array, jax.Array]:
        """Sort each ``int8_delta_idx`` bucket's payload slice by
        canonical position — the Elias-Fano encode precondition
        (wirecodec.DeltaIndexCodec). Values and ORIGINAL indices are
        permuted together: downstream consumers (quantization, the
        transmit record, int8 error feedback) keep seeing matched
        (value, index) pairs with sentinels intact — a permutation
        changes no transmitted coordinate set. The sort key is the
        CANONICAL (in-row clipped) position so padded sentinel slots
        sort inside their owning row; rows occupy disjoint ascending
        ranges, so the sort never crosses rows and every static per-row
        structure (_row_map, clamp bounds, slot ownership) stays
        valid."""
        for s0, s1, off, num in self._delta_sort:
            seg = indices[s0:s1]
            o = jnp.asarray(off, seg.dtype)
            hi = jnp.asarray(num - 1, seg.dtype)
            canon = o + jnp.clip(seg - o, 0, hi)
            order = jnp.argsort(canon)
            values = values.at[s0:s1].set(values[s0:s1][order])
            indices = indices.at[s0:s1].set(seg[order])
        return values, indices

    def _decode_i4(self, g_q4: jax.Array, g_scale4: jax.Array,
                   dt) -> jax.Array:
        """Decode the gathered int4 nibble bytes back to values: unpack
        each bucket's byte span (odd payloads drop the zero pad nibble),
        then rescale by that bucket's f32 scale. ``g_q4`` is
        [W, nibble bytes] int8, ``g_scale4`` starts with the
        [W, _i4_buckets] per-bucket scales; returns [W, i4 payload] in
        ``dt``."""
        from dgc_tpu.compression.wirecodec import unpack_int4
        parts = [unpack_int4(g_q4[:, blo:bhi], phi - plo)
                 for plo, phi, blo, bhi in self._i4_chunks]
        q = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)
        scale = g_scale4[:, :self._i4_buckets].astype(dt)
        return q.astype(dt) * jnp.take(scale, self._i4_map, axis=1)

    # -------------------------------------------------------------- #
    # telemetry geometry (dgc_tpu.telemetry)                         #
    # -------------------------------------------------------------- #

    def wire_bytes_per_worker(self) -> int:
        """Static per-worker sparse wire bytes per step under the active
        plan: the sizes of the lanes ``_encode_values`` and
        ``_encode_indices`` lay out (checksum words apart). Dense-planned
        buckets ride the fallback psum, the same on both arms: 0 here."""
        if not self.payload_size:
            return 0
        lanes = jax.eval_shape(
            lambda v, i: (self._encode_values(v)[0], self._encode_indices(i)),
            jax.ShapeDtypeStruct((self.payload_size,), self.layout.dtype),
            jax.ShapeDtypeStruct((self.payload_size,), self.index_dtype))
        return sum(lane.size * lane.dtype.itemsize
                   for lane in jax.tree_util.tree_leaves(lanes))

    def bucket_wire_bytes(self) -> List[int]:
        """Per-bucket sparse wire bytes under the active plan (the
        per-regime breakdown the planner's prediction is checked
        against). Dense-planned buckets report 0; packed-index buckets
        attribute their exact slot bit widths rounded up to whole bytes,
        while :meth:`wire_bytes_per_worker` pads the shared bit stream
        once to whole 4-byte words — so the sum may differ from the
        engine total by sub-word rounding in either direction:
        ``-(num packed buckets) < total - sum < 4`` bytes."""
        out = []
        pj = dj = 0
        for b, r in zip(self.buckets, self.regimes):
            kind, packed = _REGIMES[r]
            if kind == "d":
                out.append(0)
                continue
            if kind == "i8":
                vb = b.payload + 4 * b.rows
            elif kind == "i4":
                # nibble bytes (per-bucket padded, exact) + ONE f32 scale
                vb = (b.payload + 1) // 2 + 4
            elif kind == "f16":
                vb = 2 * b.payload
            else:
                vb = b.payload * np.dtype(self.layout.dtype).itemsize
            if packed is True:
                w = self._codec.widths[pj:pj + b.payload]
                pj += b.payload
                ib = -(-int(w.sum()) // 8)
            elif packed == "delta":
                # the Elias-Fano stream word-aligns per bucket — exact
                ib = 4 * self._dcodec.bucket_words[dj]
                dj += 1
            else:
                ib = b.payload * jnp.dtype(self.index_dtype).itemsize
            out.append(int(vb + ib))
        return out

    def bucket_descriptors(self):
        """Static per-bucket geometry for telemetry headers/readers: the
        per-bucket stat columns (selected_frac, threshold) are emitted in
        this order. Carries each bucket's planned exchange regime and its
        per-regime wire bytes (buckets may disagree under a mixed
        plan)."""
        wb = self.bucket_wire_bytes()
        return [{"base": int(b.base), "rows": int(b.rows),
                 "cols": int(b.cols), "numel": int(np.sum(b.numels)),
                 "num_selects": int(np.sum(b.num_selects)),
                 "payload": int(b.payload), "regime": r,
                 "wire_bytes": int(w)}
                for b, r, w in zip(self.buckets, self.regimes, wb)]

    def telemetry_static(self) -> Dict:
        """Header block for the telemetry sink (see registry.make_header)."""
        return {
            "engine": type(self).__name__,
            "num_params": int(self.layout.total),
            "t_compressed": int(self.T),
            "compress_ratio": float(self.c.compress_ratio),
            "payload_elems": int(self.payload_size),
            "wire_bytes": self.wire_bytes_per_worker(),
            "index_bits": (round(self._codec.bits_per_index, 2)
                           if self._codec is not None else
                           8 * jnp.dtype(self.index_dtype).itemsize),
            "regimes": list(self.regimes),
            "buckets": self.bucket_descriptors(),
        }

    # -------------------------------------------------------------- #
    # memory (fused over the flat buffers)                           #
    # -------------------------------------------------------------- #

    @property
    def _mem(self) -> Optional[DGCSGDMemory]:
        m = self.c.memory
        return m if isinstance(m, DGCSGDMemory) else None

    def init_memory(self) -> Dict:
        """Error-feedback buffers, stored SPLIT at the compressed/dense
        boundary T. The two halves live different lives every step (the
        compressed half goes through compensate/mask, the tail through the
        non-accumulating correction); storing them pre-split lets the
        masking multiply write the final state buffers directly instead of
        materializing masked intermediates that a concat fusion then
        re-reads — measured ~1.8 ms/step of full-[P] traffic on ResNet-50
        (v5e). External consumers use :meth:`memory_state_dict` (the
        reference's per-name checkpoint format, memory.py:79-88), which is
        layout-agnostic."""
        if self._mem is None:
            return {}
        T, P = self.T, self.layout.total
        # state dtype: the memory's optional narrow override (bf16 error
        # feedback — halves the compensate pass's dominant HBM streams and
        # every downstream read of the compensated gradient), else the
        # layout dtype. sent_c stays f32 regardless: sub-word scatters
        # lower to a serial while-loop on v5e (see below).
        sdt = self._mem.dtype or self.layout.dtype
        zc = jnp.zeros((T,), sdt)
        zd = jnp.zeros((P - T,), sdt)
        # masking is DEFERRED: the step that transmits records its
        # transmitted coordinates, and the NEXT step's compensate applies
        # the zeroing on read, fused into the Pallas kernel — bitwise
        # identical to eager masking but it rides the compensate pass
        # instead of costing its own full-[T] write+read (measured
        # 0.83 ms/step at ResNet-50 scale on v5e). The record is
        # BIT-PACKED (sent_bits, kernels.pack_sent_bits — one int32 word
        # per 32 coordinates): per-worker payload indices are unique, so
        # one word-wide scatter of single bits builds it (a sub-word byte
        # mask would lower to a serial while-loop on v5e). Its shape is
        # ratio-independent, so checkpoints survive warm-up ratio changes.
        out = {"momentums_c": zc, "velocities_c": zc,
               "momentums_d": zd, "velocities_d": zd,
               "sent_bits": jnp.zeros((kernels.num_sent_words(T) if T else 0,),
                                      jnp.int32)}
        if self._gossip is not None:
            # gossip state rides the ordinary memory dict, so checkpoint
            # save/resume of the round clock is bitwise for free and the
            # step guard's atomic memory revert covers it too:
            #   gossip_clock  — rounds completed (the schedule's time)
            #   gossip_age    — [W] rounds since each worker's mass last
            #                   reached the params (replicated-by-
            #                   construction: computed from replicated
            #                   inputs on every worker)
            #   gossip_inbox  — neighbor payloads received this round,
            #                   folded into the velocities NEXT round
            #                   (after the deferred transmit mask — a
            #                   freshly received value must not be wiped
            #                   by the receiver's own transmit record)
            #   gossip_forced — cumulative staleness-forced full syncs
            out["gossip_clock"] = jnp.zeros((), jnp.int32)
            out["gossip_age"] = jnp.zeros((self._gossip.world,), jnp.int32)
            out["gossip_inbox"] = jnp.zeros((T,), sdt)
            out["gossip_forced"] = jnp.zeros((), jnp.int32)
        return out

    def _compensate_acc(self, mmt, vec, grad, sent_bits=None,
                        want_cands=False):
        """Momentum correction + local accumulation (memory.py:50-63) —
        the fused single-pass Pallas kernel on TPU, its jnp reference
        elsewhere (bit-compatible, tests/test_kernels.py). With
        ``sent_bits`` (the previous step's bit-packed transmit record,
        kernels.pack_sent_bits), the transmit mask (memory.py:72-77) is
        applied on read inside the same pass (deferred masking), expanded
        from the packed words in VMEM. ``grad`` may be the WHOLE flat [P]
        buffer (longer than the state): on the ``want_cands`` fused-kernel
        path it is read through the kernel's index map with no ``[:T]``
        operand-slice copy; every other path still slices to exactly [T]
        (those kernels take exact-length operands).

        ``want_cands`` (TPU bits path only): emit the segment-top-2
        selection candidates from the same pass — the compensate kernel
        is bandwidth-bound with an idle VPU, so candidate extraction
        rides the stream instead of re-reading the velocity it just
        wrote (kernels.fused_compensate_bits_cands). Returns
        ``(comp, mmt', vec', cands_or_None)``; candidates are bitwise
        the standalone kernel's, so the CPU/test path (cands=None,
        seg_top2_reference downstream) stays equivalent.

        With a narrow (bf16) state dtype the compensated gradient is the
        bf16 velocity and the selection pipeline runs on it directly (a
        split-output f32 variant lost: docs/RESULTS.md)."""
        m = self._mem
        n = mmt.shape[0] if hasattr(mmt, "shape") else 0
        if m is None:
            return grad, mmt, vec, None
        if (want_cands and sent_bits is not None and kernels.use_pallas()
                and n > 0):
            # the one no-slice path: the fused kernel reads [0, T) of a
            # possibly-longer grad through its index map
            mmt, vec, cv, ci = kernels.fused_compensate_bits_cands(
                grad, mmt, vec, sent_bits, m.momentum, m.nesterov,
                m.momentum_masking)
            return vec, mmt, vec, (cv, ci)
        # every other kernel/reference takes an exactly-[T] operand
        g = grad if grad.shape[0] == n else grad[:n]
        if sent_bits is not None:
            if kernels.use_pallas() and n > 0:
                mmt, vec = kernels.fused_compensate_bits(
                    g, mmt, vec, sent_bits, m.momentum, m.nesterov,
                    m.momentum_masking)
            else:
                mmt, vec = kernels.fused_compensate_bits_reference(
                    g, mmt, vec, sent_bits, m.momentum, m.nesterov,
                    m.momentum_masking)
        elif kernels.use_pallas() and n > 0:
            mmt, vec = kernels.fused_compensate(g, mmt, vec, m.momentum,
                                                m.nesterov)
        else:
            mmt, vec = kernels.fused_compensate_reference(
                g, mmt, vec, m.momentum, m.nesterov)
        return vec, mmt, vec, None

    def _clip_block(self, block: jax.Array, names: Sequence[str],
                    base: int) -> jax.Array:
        """Per-tensor gradient clipping over a flat block: the memory's
        ``gradient_clipping`` callable applied per named tensor
        (reference memory.py:52-53), batched.

        Whole buckets clip as one ``vmap`` over the [R, cols] row view (a
        pure reshape) — row tails are structural zeros, and every C7 clip
        function is *padding-invariant* (appended zeros change no norm and
        clip back to zero), so per-row == per-tensor. This collapses the
        global variants' per-tensor ``pmean`` into one [R]-vector collective
        per bucket and avoids a per-tensor dynamic-update-slice chain at
        ImageNet scale (50+ tensors). Non-bucket names (the dense tail)
        batch the same way through a padded [R, C] gather — the dense block
        is small (biases/BN), so the gather is off the sizing path.

        Custom ``gradient_clipping`` callables must preserve that
        padding-invariance contract (all reference clip_grad.py:10-42
        functions do).
        """
        clip = self._mem.gradient_clipping
        lay = self.layout
        names = list(names)
        name_set = set(names)
        done = set()
        for g in lay.buckets:
            if not all(n in name_set for n in g.names):
                continue
            s = g.base - base
            view = block[s:s + g.rows * g.cols].reshape(g.rows, g.cols)
            clipped = jax.vmap(clip)(view)
            block = block.at[s:s + g.rows * g.cols].set(clipped.reshape(-1))
            done.update(g.names)
        rest = [n for n in names if n not in done]
        if rest:
            C = max(lay.sizes[n] for n in rest)
            offs = jnp.asarray([lay.offsets[n] - base for n in rest],
                               jnp.int32)[:, None]
            sizes = jnp.asarray([lay.sizes[n] for n in rest],
                                jnp.int32)[:, None]
            col = jnp.arange(C, dtype=jnp.int32)[None, :]
            valid = col < sizes
            pos = jnp.where(valid, offs + col, 0)
            rows = jnp.where(valid, block[pos.reshape(-1)].reshape(pos.shape),
                             jnp.zeros((), block.dtype))
            rows = jax.vmap(clip)(rows)
            # invalid slots scatter out of bounds and drop
            flat_pos = jnp.where(valid, offs + col,
                                 jnp.int32(block.shape[0])).reshape(-1)
            block = block.at[flat_pos].set(rows.reshape(-1), mode="drop")
        return block

    def _compensate_dense(self, mmt, grad):
        """Non-accumulating correction for the dense-fallback block, applied
        after averaging (reference compression.py:198, memory.py:64-70).
        Math in the gradient dtype; the stored momentum rounds once to the
        state dtype (no-op unless the bf16 state option is on) — matching
        ``DGCSGDMemory.compensate(accumulate=False)`` exactly."""
        m = self._mem
        if m is None:
            return grad, mmt
        sdt = mmt.dtype
        mmt = mmt.astype(grad.dtype)
        if m.nesterov:
            mmt = (mmt + grad) * m.momentum
            return mmt + grad, mmt.astype(sdt)
        mmt = m.momentum * mmt + grad
        return mmt, mmt.astype(sdt)

    # -------------------------------------------------------------- #
    # sparsify (batched per bucket)                                  #
    # -------------------------------------------------------------- #

    def _select_topk(self, scores: jax.Array, max_sel: int):
        """Selection top-k over a bucket's [R, cols] scores.

        Exact ``lax.top_k`` at lane-scale k; beyond it (ImageNet-scale
        tensors, num_selects in the thousands) the reduction-based
        ``lax.approx_max_k`` — the sort-based exact TopK is 10-50x slower
        there (measured 39 ms/step total for ResNet-50) and aborts the v5e
        compiler at the largest shapes. Measured recall at the default 0.95
        target is >= 0.98; a missed coordinate simply stays in the
        error-feedback velocity — the same guarantee that already covers
        the reference's index-order truncation (compression.py:151). On
        CPU approx_max_k lowers to an exact sort, so the flat-vs-per-tensor
        equivalence tests see identical selections."""
        r = self.c.approx_recall
        # approx whenever allowed AND the exact path would pay the
        # sort-based TopK: k beyond the lane width, or above the Pallas
        # iterative-max kernel's work crossover (~2M element-extractions,
        # see _exact_topk). Below both, exact selection is cheaper than the
        # reduction anyway. Measured at the ResNet-50 [11, 65536] k=66
        # bucket (previously routed to the sort by the old max_sel > 128
        # gate): approx 0.048 vs sort 0.235 ms isolated on v5e.
        if r is not None and (max_sel > 128
                              or max_sel * scores.shape[1] > 2_000_000):
            # the AGGREGATED single-stage form, deliberately — both
            # restructurings lost their paired full-step A/B at ResNet-50
            # on v5e (isolated micro-benches said otherwise both times;
            # only paired interleaved full steps are trusted on this
            # backend): round 2's "no-aggregate + manual lax.top_k" was
            # ~0.55 ms/step slower, and round 3's two-stage
            # (approx-of-candidates instead of the aggregation sort) was
            # ~0.2 ms/step slower despite an isolated 1.5 ms win. The
            # recall TARGET is the actual lever: 0.90 halves the candidate
            # count the aggregation sorts vs 0.95 (-0.62 ms/step paired at
            # ResNet-50) while measured recall stays 0.966-0.975 at every
            # ResNet-50 bucket (scripts/measure_recall.py) — above the
            # 0.95 regression threshold. On CPU approx_max_k lowers to an
            # exact sort, which the flat-vs-per-tensor equivalence suite
            # relies on.
            return jax.lax.approx_max_k(scores, max_sel,
                                        recall_target=float(r))
        return _exact_topk(scores, max_sel)

    def _sample_rows(self, b: "_Bucket", imp_rows: jax.Array,
                     k: jax.Array) -> jax.Array:
        """Per-row threshold samples for one bucket (reference
        compression.py:113-121); pad slots carry importance -1.

        TPU-native strided sampling: sample 128-LANE BLOCKS at the
        tensor's sampling rate instead of single elements at the
        reference's element stride. Element-strided extraction fights the
        [8, 128] tiling no matter how it is phrased — positional gather
        1.5 ms, strided dynamic_slice 1.8 ms, one-hot einsum ~3 ms per
        big ResNet-50 bucket on v5e (the [n, stride] reshape is a
        physical relayout) — while whole-lane blocks at a block stride
        read contiguous 512 B bursts: measured ~0.1 ms. Per tensor this
        is still a systematic sample of the same fraction of |grad| with
        a fresh uniform random phase per step; within-block correlation
        slightly widens the threshold estimator's variance, which the
        bounded ladder adaptation (compression.py:128-149) exists to
        correct — bounded empirically by
        tests/test_flat.py::test_lane_block_sampling_quantile. The
        contract requires sampling to match in distribution, not
        positions (SURVEY.md §4); rows run one shared phase per stride
        run so the extraction is ONE slice. Stride-1 runs
        (sample-everything rows) stay exact."""
        R = b.rows
        numels = jnp.asarray(b.numels)[:, None]
        neg1 = jnp.full((), -1.0, imp_rows.dtype)
        if self.c.strided_sample:
            L = 128
            # widths per stride group: nb is rounded UP (truncation would
            # draw as little as half the budget, n=255 -> 128); the
            # overshoot (< L extra samples) biases the quantile estimate
            # slightly HIGH, which the ladder adaptation lowers — the
            # safe direction. Safe to read: nb*L <= round_up(n, L) <=
            # round_up(max numel, lane) <= cols, and over-reads past a
            # shorter row's numel land on the -1 importance pad.
            widths = []
            for (_, _, stride, n) in b.stride_groups:
                widths.append(n if (stride == 1 or n < L)
                              else -(-n // L) * L)
            width = max(widths)
            parts = []
            for gi, (r0, r1, stride, n) in enumerate(b.stride_groups):
                kg = jax.random.fold_in(k, gi)
                u = jax.random.uniform(kg, ())
                Rg = r1 - r0
                nb = -(-n // L)
                if stride == 1:
                    # the reference's exact sample-everything path
                    smp = imp_rows[r0:r1, :n]
                elif n < L:
                    # sample sets smaller than a lane block (tiny tensors
                    # only): keep the reference's element stride with a
                    # fresh random phase — the gather is n < 128
                    # elements, off the sizing path
                    phase = jnp.floor(u * stride).astype(jnp.int32)
                    pos = phase + jnp.arange(n, dtype=jnp.int32) * stride
                    pos = jnp.minimum(pos, b.cols - 1)
                    smp = jnp.take_along_axis(
                        imp_rows[r0:r1],
                        jnp.broadcast_to(pos[None, :], (Rg, n)), axis=1)
                else:
                    # nb blocks at block-stride sb spread over the data
                    # span n*stride (~ the largest row's numel). Reading
                    # the 4-D view from a layout-free [Rg, cols/128, 128]
                    # slice of the flat buffer (to skip imp_rows' 2-D
                    # relayout) was tried and LOST its paired A/B by
                    # ~0.5 ms/step at ResNet-50 — the slice-of-bitcast
                    # chain materializes the near-full span instead of
                    # fusing into the dynamic_slice; the imp_rows read
                    # below reuses the block selection already paid for.
                    sb = max(1, (n * stride) // (nb * L))
                    phase = jnp.floor(u * sb).astype(jnp.int32)
                    v = imp_rows[r0:r1, :nb * sb * L].reshape(
                        Rg, nb, sb, L)
                    smp = jax.lax.dynamic_slice(
                        v, (jnp.int32(0), jnp.int32(0), phase,
                            jnp.int32(0)),
                        (Rg, nb, 1, L)).reshape(Rg, nb * L)
                if smp.shape[1] < width:
                    smp = jnp.concatenate(
                        [smp, jnp.full((Rg, width - smp.shape[1]), neg1)],
                        axis=1)
                parts.append(smp)
            # no per-slot validity mask: lane-block slots do not map to
            # the reference's slot order; out-of-row positions already
            # read the -1 importance pad and sort below every threshold
            return (jnp.concatenate(parts) if len(parts) > 1
                    else parts[0])
        s_idx = jnp.arange(b.max_s, dtype=jnp.int32)[None, :]
        s_valid = s_idx < jnp.asarray(b.num_samples)[:, None]
        u = jax.random.uniform(k, (R, b.max_s))
        pos = jnp.floor(u * numels).astype(jnp.int32)
        # rows sampling everything must sample exactly, not with
        # replacement (per-tensor path's numel==num_samples branch,
        # dgc.py sparsify)
        exact = jnp.asarray(b.num_samples)[:, None] >= numels
        pos = jnp.where(exact, jnp.minimum(s_idx, numels - 1), pos)
        # positions are < numel <= cols by the sampling geometry
        # (reference compression.py:66-85), so the row-local gather
        # stays in bounds; invalid sample slots read -1
        return jnp.where(
            s_valid,
            jnp.take_along_axis(imp_rows, jnp.minimum(pos, b.cols - 1),
                                axis=1),
            neg1)                                     # [R, maxS]

    #: minimum row width for the 3-D layout-free selection path. Measured
    #: on v5e: at ResNet-50's bucket widths (<= 2.36M) the 2-D path WINS
    #: the paired full-step A/B (4.74 vs 5.12 ms overhead — the axis-1
    #: PartialReduce + candidate remap costs more than the relayout it
    #: avoids there); at VGG's fc widths (3.2-4.2M segments) the 3-D path
    #: wins. Smaller buckets also keep the exact CPU lowering the
    #: equivalence suite pins.
    SEL3D_MIN_COLS = 3 * 1024 * 1024
    #: per-(row, lane) candidate quota as a multiple of the mean
    #: (num_selects / 128) — Poisson tails at 2x the mean are negligible
    #: for the gated sizes (mean >= ~25/lane: P(lane > 2x mean) < 1e-5)
    SEL3D_MARGIN = 2

    def _use_3d(self, b: "_Bucket") -> bool:
        """Whether a bucket takes the 3-D lane-stratified selection path:
        approx allowed, genuinely sampled+strided (every row), and wide
        enough that the 2-D view's physical relayout is worth avoiding."""
        return (self._sampled_strided_ok(b)
                and b.cols % 128 == 0 and b.cols >= self.SEL3D_MIN_COLS)

    def _sampled_strided_ok(self, b: "_Bucket") -> bool:
        """Shared preconditions of both layout-free selection paths:
        approx allowed, genuinely sampled+strided on every row, resample
        adaptation (the ladder-from-topk derivation)."""
        return (self.c.approx_recall is not None and not b.exact
                and self.c.strided_sample
                and self.c.resample
                and bool((b.strides > 1).all())
                and bool((b.num_samples >= 128).all()))

    def _use_seg_kernel(self, b: "_Bucket") -> bool:
        """Whether a bucket selects through the segment-top-2 candidates
        kernel (kernels.seg_top2_candidates): the same sampled+strided
        preconditions as :meth:`_use_3d`, plus the kernel's geometric
        alignment and enough (lane, segment) cells that per-cell top-2
        captures the top set (cells >= 3*k keeps the cell occupancy
        ~Poisson(<=1/3), losing ~1%). Unlike the approx 3-D path the
        kernel reads the flat buffer in place and emits signed values,
        so it wins WITHOUT the SEL3D_MIN_COLS width gate — the round-3
        negative result for 3-D-below-3M-cols was the PartialReduce
        form's relayout-vs-remap trade, which the kernel does not pay."""
        nb = b.cols // 128
        cells = (nb // kernels._SEG_BLOCKS) * 128
        return (self._sampled_strided_ok(b)
                and cells >= 3 * b.max_sel
                and kernels.seg_top2_eligible(
                    self.T // 128, b.base, b.cols, b.rows))

    def _use_fused_apply(self, m, int8_ef: bool, dt) -> bool:
        """Whether the post-gather epilogue takes the fused Pallas
        apply (kernels.payload_apply_bits) instead of the two XLA
        scatters: opt-in (``DGCCompressor(fused_apply=True)``), needs a
        transmit record to build (``m``), a plain f32 value wire (the
        kernel accumulates in f32; int8 error feedback keeps its empty
        record + eager masking), and a lane-aligned T (always true for
        the layout's _ALIGN). Runs interpreted off-TPU — the CPU oracle
        the parity tests pin — but only up to a small payload: the
        interpreter executes the per-entry RMW loop serially (~0.3 ms
        per wire entry on CPU — minutes per step at warmup-ratio
        payloads), so at real scale off-TPU the engine silently keeps
        the XLA scatter path."""
        return (getattr(self.c, "fused_apply", False)
                and self._apply_kernel_ok("fused_apply", m, int8_ef, dt))

    def _decline(self, flag: str, why: str) -> bool:
        """A kernel flag the user set that the engine cannot honour for a
        reason OTHER than bucket geometry: on the TPU backend that is an
        error, not a silent fall-through to the XLA path (off-TPU the
        fall-through stays: the CPU parity tests stack the flags on
        every wire format). Returns False so gates can ``return`` it."""
        if kernels.use_pallas():
            raise ValueError(
                f"DGCCompressor({flag}=True) cannot be honoured: {why}")
        return False

    def _apply_kernel_serves(self, m, int8_ef: bool, dt) -> bool:
        """The static plan properties the apply kernel serves: a transmit
        record to build (error-feedback memory), a plain f32 value wire
        with no int8 error feedback, int32 offsets over a lane-aligned
        T the kernel's chunk arithmetic cannot overflow, and no gossip
        round (its inbox takes the [T] accumulator itself)."""
        return (m is not None and not int8_ef and dt == jnp.float32  # dgclint: ok[tracer-branch] — memory/wire dtype/T are plan-static Python values, not tracers
                and self.T % kernels._LANE == 0
                and self.T + kernels._APPLY_CHUNK < 2 ** 31
                and not self._gossip)

    def _apply_kernel_ok(self, flag: str, m, int8_ef: bool, dt) -> bool:
        """Preconditions of a FLAGGED apply kernel: the interpreter's
        payload bound (see :meth:`_use_fused_apply`), then those that
        are not bucket geometry (see :meth:`_decline`)."""
        if kernels._interpret() and self.payload_size > 4096:
            return False
        if self._apply_kernel_serves(m, int8_ef, dt):  # dgclint: ok[tracer-branch] — plan-static Python values, not tracers
            return True
        return self._decline(
            flag, "the fused apply kernel needs error-feedback memory "
            "(DGCSGDMemory), an f32 value wire and no int8 error feedback "
            f"— got memory={type(m).__name__}, int8_error_feedback="
            f"{int8_ef}, wire dtype={jnp.dtype(dt).name}")

    #: the geometry rule of :meth:`_apply`, on static numbers only.
    #: WITHOUT the step's offer (the pass writes a [T] gradient for the
    #: optimizer to read) the pairs stream through the apply kernel
    #: where that f32 accumulator (4 T bytes) is larger than the chip's
    #: 128 MiB of VMEM, in which XLA otherwise keeps it between the
    #: scatter and the optimizer's fusion (ResNet-50: 108 MB, kept;
    #: VGG-16-BN: 556 MB, streamed). PR 31 read it: ResNet-50 forced to
    #: ``stream`` lost 0.03-0.06 ms of ``dgc_overhead_ms`` to the
    #: scatter; see :meth:`_apply`
    APPLY_STREAM_MIN_BYTES = 128 * 1024 * 1024
    #: WITH the offer there is no accumulator to keep anywhere (form
    #: ``update``: the [T] gradient is never written), so that bound
    #: does not apply, and this one is where the pass's fixed cost (the
    #: sort, the staging's small ops, the chunk walk) meets what it
    #: saves: 8 MB, just under the smallest T that read a win. Step 0
    #: of PR 41 (v5e, bare programs, 25 calls inside one program, ms a
    #: call, the pass + the tail's rule | scatter + bit scatter +
    #: ``dgc_sgd``'s fusion; one worker's pairs, then four workers'):
    #: T 27,068,416 (ResNet-50) 0.717 | 1.083, 0.902 | 1.765;
    #: 13,105,152 (ResNet-18) 0.356 | 0.371, 0.437 | 0.677; 6,553,600
    #: 0.194 | 0.206, 0.231 | 0.373; 2,029,568 (ResNet-110) 0.080 |
    #: 0.096, 0.091 | 0.132; 370,688 (ResNet-20, 1.5 MB) 0.040 | 0.035,
    #: 0.042 | 0.037: a LOSS, so ResNet-20 keeps the scatter. Nothing
    #: was read between 370,688 and 2,029,568 elements
    APPLY_UPDATE_MIN_BYTES = 8_000_000
    #: and, either way, while the windows' first / last indices (two
    #: int32 a 128 pairs) stay a small part of the scalar memory they
    #: are prefetched into: 2**21 pairs are 2 x 64 KiB
    APPLY_STREAM_MAX_PAIRS = 1 << 21

    @classmethod
    def _apply_streams(cls, T: int, pairs: int,
                       offered: bool = False) -> bool:
        """The geometry rule on what is static at trace time: the
        compressed block's length, the gathered pairs ``W * payload``
        and whether the step offers its optimizer's rule for the block
        (:class:`InPlaceUpdate`, and nothing else writes the block)."""
        floor = (cls.APPLY_UPDATE_MIN_BYTES if offered
                 else cls.APPLY_STREAM_MIN_BYTES)
        return 4 * T > floor and pairs <= cls.APPLY_STREAM_MAX_PAIRS

    def _use_fused_select(self, b: "_Bucket") -> bool:
        """Whether a bucket's selection runs the fused
        threshold->select->pack kernel (kernels.select_pack_rows): ONE
        pass over the bucket rows emits scores, signed payload values,
        and columns together — replacing the masked-importance
        materialization, the top-k, and the payload value gather.
        Opt-in (``DGCCompressor(fused_select=True)``) and exact-selection
        region only: the same lane-width / work-crossover bounds
        :meth:`_select_topk` uses to route to ``_exact_topk``, so the
        fused and unfused paths select bitwise-identical payloads
        (pinned in tests/test_kernels.py)."""
        return (getattr(self.c, "fused_select", False)
                and b.max_sel <= kernels._MR_MAX_K
                and b.max_sel * b.cols <= (2_000_000
                                           if kernels._interpret()
                                           else 16_000_000))

    def _use_megakernel_fwd(self, bi: int) -> bool:
        """Whether bucket ``bi``'s compensate + selection runs the
        forward megakernel (kernels.dgc_forward_rows): masked
        error-feedback compensate -> momentum correction -> threshold
        mask -> multi-round in-VMEM select -> pack, ONE Pallas pass —
        the compensated gradient and the candidate (value, column)
        pairs never round-trip through HBM between the compensate and
        select launches. Plan-static gates: the megakernel opt-in, an
        error-feedback memory with f32 state and gradient (the kernel
        refuses narrow state; bf16 error feedback keeps the unfused
        path), a plain 2-D selection bucket (seg-kernel / 3-D buckets
        keep their own fused candidate stream), kernel geometry (k
        within the multi-round bound, one whole row VMEM-resident),
        and a serial-interpreter work bound off-TPU (oversize buckets
        silently keep the unfused path there — the `_use_fused_apply`
        convention, so the CPU parity oracles stay fast)."""
        if not self._megakernel:
            return False
        sdt = (self._mem.dtype or self.layout.dtype) if self._mem else None
        if (sdt is None or np.dtype(sdt) != np.dtype(np.float32)
                or np.dtype(self.layout.dtype) != np.dtype(np.float32)):
            return self._decline(
                "megakernel", "the forward megakernel needs error-feedback "
                "memory (DGCSGDMemory) with f32 state and f32 gradients — "
                f"got memory={type(self._mem).__name__}, state dtype={sdt}, "
                f"layout dtype={self.layout.dtype}")
        b = self.buckets[bi]
        if self._use_seg_kernel(b) or self._use_3d(b):
            return False
        if not (0 < b.max_sel <= min(b.cols, kernels._MR_MAX_K)):
            return False
        if b.base % kernels._LANE or b.cols % kernels._LANE:
            return False
        # one row (grad+mmt+vec streams + selection carry) must fit the
        # kernel's VMEM budget; wider buckets are layout-free-path
        # territory anyway
        if b.cols > 128 * 1024:
            return False
        if kernels._interpret() and b.rows * b.cols * b.max_sel > 50_000_000:
            return False
        return True

    def _use_megakernel_apply(self, m, int8_ef: bool, dt) -> bool:
        """Whether the post-gather epilogue runs the apply megakernel
        (kernels.dgc_apply_rows): the fused-apply pass with the
        worker-average decompress divide folded into the kernel body,
        so the divided [W * payload] wire never materializes in HBM.
        Same preconditions as :meth:`_use_fused_apply`, keyed on the
        megakernel opt-in instead of ``fused_apply``."""
        return (self._megakernel
                and self._apply_kernel_ok("megakernel", m, int8_ef, dt))

    def _compensate_megakernel(self, mmt, vec, grad, sent_bits):
        """Forward-megakernel compensate over [0, T): eligible buckets
        (``_mk_fwd_ids``) run kernels.dgc_forward_rows — ONE pass per
        bucket emitting the compensated state AND the packed selection
        (scores, signed values, columns), which :meth:`sparsify`
        consumes via ``fwd_sel`` instead of relaunching a selection
        kernel over state it would re-read from HBM. Complement spans
        (dense-planned slabs, ineligible buckets, alignment gaps) keep
        the plain fused compensate, windowed onto the span by
        kernels.realign_bits (bitwise the full-record expansion).
        Reassembly is base-order concatenation — every element takes
        exactly the unfused pass's op sequence, so engine-level parity
        is bitwise (pinned in tests/test_megakernel.py).

        Returns ``(comp, mmt', vec', fwd_sel)`` with ``comp is vec'``
        (deferred masking applies on read; the compensated gradient IS
        the velocity, as on :meth:`_compensate_acc`'s bits path)."""
        m = self._mem
        T = self.T
        g = grad if grad.shape[0] == T else grad[:T]
        segs = []
        pos = 0
        for bi in sorted(self._mk_fwd_ids,
                         key=lambda i: self.buckets[i].base):
            b = self.buckets[bi]
            if b.base > pos:
                segs.append((pos, b.base, None))
            segs.append((b.base, b.base + b.rows * b.cols, bi))
            pos = b.base + b.rows * b.cols
        if pos < T:
            segs.append((pos, T, None))
        mparts, vparts = [], []
        fwd_sel = {}
        for lo, hi, bi in segs:
            gs, ms, vs = g[lo:hi], mmt[lo:hi], vec[lo:hi]
            if bi is None:
                span_bits = kernels.realign_bits(sent_bits, lo, hi - lo)
                if kernels.use_pallas():
                    ms, vs = kernels.fused_compensate_bits(
                        gs, ms, vs, span_bits, m.momentum, m.nesterov,
                        m.momentum_masking)
                else:
                    ms, vs = kernels.fused_compensate_bits_reference(
                        gs, ms, vs, span_bits, m.momentum, m.nesterov,
                        m.momentum_masking)
            else:
                b = self.buckets[bi]
                with _trace.phase("forward", bi):
                    ms, vs, s, v, c = kernels.dgc_forward_rows(
                        gs, ms, vs, sent_bits, lo,
                        jnp.asarray(b.numels, jnp.int32), b.max_sel,
                        m.momentum, m.nesterov, m.momentum_masking)
                fwd_sel[bi] = (s, v, c)
            mparts.append(ms)
            vparts.append(vs)
        mmt = mparts[0] if len(mparts) == 1 else jnp.concatenate(mparts)
        vec = vparts[0] if len(vparts) == 1 else jnp.concatenate(vparts)
        return vec, mmt, vec, fwd_sel

    def _sample_rows_3d(self, b: "_Bucket", v2d: jax.Array,
                        k: jax.Array) -> jax.Array:
        """Lane-block strided samples from the layout-free [R, nb, 128]
        RAW view — the SAME positions and values as :meth:`_sample_rows`
        on the 2-D view (block j = lanes [128j, 128j+128)), but sliced
        from a view whose reshape from the flat buffer is a bitcast, not
        a relayout. Only the strided n >= 128 branch exists here (the
        :meth:`_use_3d` gate).

        Samples are drawn from the raw values and |.| is applied to the
        small extracted blocks (|slice(x)| == slice(|x|), so the result
        is identical) — deliberately, so the full-size importance
        ``|v3|`` has exactly ONE consumer (the selection's PartialReduce)
        and XLA fuses the abs into it instead of materializing a
        tensor-sized importance array (measured ~3 ms/step of abs/copy
        passes at VGG's fc buckets, device profile r5).

        Extraction is ONE whole-row gather of the sampled 128-lane blocks
        from the FULL-buffer [T/128, 128] bitcast view (no slice of the
        bucket is ever taken; the block ids are static per row up to the
        random phase). The earlier form — a [Rg, nb, sb, L] reshape of a
        row slice + dynamic_slice at the phase — materialized nearly the
        whole bucket span per stride group (~4 ms/step of slice copies at
        VGG's fc buckets, device profile r5); the row gather touches only
        the sampled 512 B blocks."""
        L = 128
        nb_row = b.cols // L
        base_blk = b.base // L
        widths = [-(-n // L) * L for (_, _, _, n) in b.stride_groups]
        width = max(widths)
        neg1 = jnp.full((), -1.0, v2d.dtype)
        parts = []
        for gi, (r0, r1, stride, n) in enumerate(b.stride_groups):
            kg = jax.random.fold_in(k, gi)
            u = jax.random.uniform(kg, ())
            Rg = r1 - r0
            nb_s = -(-n // L)
            sb = max(1, (n * stride) // (nb_s * L))
            phase = jnp.floor(u * sb).astype(jnp.int32)
            # block j of row r = lane-block j*sb + phase of the [R, nb,
            # 128] view = row base_blk + (r0+r)*nb_row + j*sb + phase
            rows = (base_blk
                    + (r0 + jnp.arange(Rg, dtype=jnp.int32))[:, None]
                    * nb_row
                    + jnp.arange(nb_s, dtype=jnp.int32)[None, :] * sb
                    + phase)                               # [Rg, nb_s]
            smp = jnp.abs(jnp.take(v2d, rows.reshape(-1), axis=0,
                                   indices_are_sorted=True)
                          ).reshape(Rg, nb_s * L)
            if smp.shape[1] < width:
                smp = jnp.concatenate(
                    [smp, jnp.full((Rg, width - smp.shape[1]), neg1)],
                    axis=1)
            parts.append(smp)
        return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

    def _sparsify_bucket_3d(self, vec_c: jax.Array, v2d: jax.Array,
                            b: "_Bucket", k: jax.Array, cands=None):
        """Layout-free selection over one wide bucket.

        The [R, cols] 2-D view is a PHYSICAL relayout of the flat buffer
        (T(8,128) interleaves 8 rows; ~10 ms/step of copies at VGG scale,
        device profile), while any row-major [R, cols/128, 128] 3-D view
        is a bitcast (the (8,128) tiling binds the last two dims, which
        are contiguous). Selection therefore runs as
        ``approx_max_k(reduction_dimension=1)`` over the 3-D importance —
        per-(row, lane) candidates with a ``SEL3D_MARGIN``x quota — then
        one small exact/approx top-k over the flattened candidates
        (measured 5.5 vs 15.9 ms isolated at VGG-fc1 scale vs the
        2-D reshape + row approx). Sampling and the payload value gather
        read the same layout-free views, so the bucket's data is never
        relayouted at all. Lane stratification only binds when one lane
        holds more than margin x mean of the top set — negligible for the
        gated sizes; recall is checked on-chip by scripts/tpu_check.py.
        """
        lay = self.layout
        S = lay.sentinel
        R, cols = b.rows, b.cols
        nb = cols // 128
        row_off = jnp.asarray(b.row_offsets,
                              dtype=self.index_dtype)[:, None]
        numels = jnp.asarray(b.numels)[:, None]

        samples = self._sample_rows_3d(b, v2d, k)
        r = self.c.approx_recall
        if b.max_k > 128 or b.max_k * samples.shape[1] > 2_000_000:
            sorted_s = jax.lax.approx_max_k(samples, b.max_k,
                                            recall_target=float(r))[0]
        else:
            sorted_s = _exact_topk(samples, b.max_k)[0]
        thr = jnp.take_along_axis(
            sorted_s, jnp.asarray(b.topk_samples)[:, None] - 1,
            axis=1)[:, 0]

        if self._use_seg_kernel(b):
            # candidates kernel: per-(lane, 256-block segment) top-2 by
            # |.|, streamed straight out of the flat buffer — no bucket
            # slice, no tensor-sized importance array, and the SIGNED
            # values + columns come out of the stream, so no payload-
            # scale random gather afterwards (the r5 device profile
            # attributed ~6 ms/step at VGG to that chain)
            span = kernels._SEG_BLOCKS * 128
            if cands is not None:
                # candidates already emitted by the fused compensate
                # pass (bitwise the standalone kernel's): slice this
                # bucket's contiguous segment range — candidate-scale
                # data (~1/64 of the bucket), no [T]-scale re-read
                cv_all, ci_all = cands
                sb = b.base // span
                nsr = cols // span
                # fail fast if the candidate stream doesn't cover this
                # bucket's segment range (e.g. a [T]-sized stream zipped
                # with a longer layout, or a misaligned b.base)
                assert cv_all.shape[0] * span >= b.base + R * cols, (
                    cv_all.shape, b.base, R, cols)
                cvals = cv_all[sb:sb + R * nsr].reshape(R, -1)
                ccols = kernels.seg_cols_local(
                    ci_all[sb:sb + R * nsr].reshape(R, nsr, 2, 128))
            else:
                fn = (kernels.seg_top2_candidates if kernels.use_pallas()
                      else kernels.seg_top2_reference)
                cvals, ccols = fn(v2d, b.base, R, cols)
            # the candidate top-k runs DIRECTLY on the [R, ~2*cells]
            # array. A mid-stage per-lane approx reduction (shrinking the
            # aggregation to the classic 2x-margin size before the sort)
            # was built and measured: +0.6 ms/step at VGG — the extra
            # PartialReduce + index remap cost more than the halved sort
            # saves. Negative result, do not re-litigate without a new
            # mechanism.
            top_scores, c2 = self._select_topk(jnp.abs(cvals), b.max_sel)
            # ONE packed gather for (value, column): interleave the
            # values with the columns so the payload-scale random access
            # is paid once, not twice (two take_along_axis remaps
            # measured 0.99 ms EACH at VGG, device profile r5). The pack
            # rides the INT32 domain — the kernel's (always-f32) values
            # bitcast to int32, columns native — because the reverse
            # (columns bitcast to f32) puts small ints into subnormal
            # f32 bit patterns, which the TPU flushes to zero in the
            # gather (verified on-chip: every gathered column < 2^23
            # came back 0). Integer paths preserve bits; bitcast is
            # bijective.
            packed = jnp.stack(
                [jax.lax.bitcast_convert_type(cvals, jnp.int32), ccols],
                axis=-1)                                   # [R, C, 2]
            sel = jnp.take_along_axis(packed, c2[:, :, None], axis=1)
            # back to the pipeline dtype (exact round-trip: the kernel's
            # f32 values are exact up-casts of a narrow state)
            sel_vals = jax.lax.bitcast_convert_type(
                sel[:, :, 0], jnp.float32).astype(vec_c.dtype)
            cols_sel = sel[:, :, 1].astype(self.index_dtype)
        else:
            # fallback (non-segment-aligned geometry): per-(row, lane)
            # approx candidates over the 3-D view
            v3 = vec_c[b.base:b.base + R * cols].reshape(R, nb, 128)
            imp3 = jnp.abs(v3)
            kp = min(nb, -(-self.SEL3D_MARGIN * b.max_sel // 128))
            cv, ci = jax.lax.approx_max_k(imp3, kp, reduction_dimension=1,
                                          recall_target=float(r))
            cand = cv.reshape(R, kp * 128)             # [R, kp*128]
            top_scores, c2 = self._select_topk(cand, b.max_sel)
            lane = c2 % 128
            blk = jnp.take_along_axis(ci.reshape(R, kp * 128), c2, axis=1)
            cols_sel = blk.astype(self.index_dtype) * 128 + lane.astype(
                self.index_dtype)
            sel_vals = None

        if self.c.max_adaptation_iters > 0 and b.adapt.any():
            thr = _ladder_adapt_from_topk(
                top_scores, thr, jnp.asarray(b.num_selects, jnp.float32),
                jnp.asarray(b.adapt), self.c.compress_lower_bound,
                self.c.max_adaptation_iters)

        slot = jnp.arange(b.max_sel, dtype=jnp.int32)[None, :]
        # structural-zero row tails carry importance 0, not the 2-D view's
        # -1 pad — exclude them explicitly so an all-zero gradient (thr=0)
        # cannot select pad slots
        valid = ((top_scores >= thr[:, None])
                 & (slot < jnp.asarray(b.num_selects)[:, None])
                 & (cols_sel < numels))
        gidx = jnp.where(valid, row_off + cols_sel,
                         jnp.asarray(S, self.index_dtype))
        if sel_vals is None:
            # payload values via one small global gather from the flat
            # buffer (the sentinel slot reads the structural 0.0)
            vals = jnp.where(valid, vec_c[gidx],
                             jnp.zeros((), vec_c.dtype))
        else:
            vals = jnp.where(valid, sel_vals, jnp.zeros((), vec_c.dtype))
        return vals, gidx

    def _select(self, bi: int, block, scores, fwd_sel):
        """A bucket's fixed-size selection over its [R, cols] importance:
        ``(top_scores, signed payload values or None, columns)``, by
        any route bitwise the same set."""
        b = self.buckets[bi]
        fused = (fwd_sel or {}).get(bi)  # plan-static dict, not a tracer
        if fused is not None:
            # already emitted by the forward megakernel's compensate pass
            # (bitwise select_pack_rows on the same block)
            return fused
        if self._use_fused_select(b):
            # fused threshold->select->pack: the kernel masks by numel and
            # emits the top set's SIGNED payload values in the same pass —
            # the [R, cols] importance array and the value gather disappear
            return kernels.select_pack_rows(
                block, jnp.asarray(b.numels, jnp.int32), b.max_sel)
        top_scores, cols = self._select_topk(scores, b.max_sel)
        return top_scores, None, cols

    def _selection_payload(self, b: "_Bucket", block, row_off, thr,
                           top_scores, fvals, cols):
        """A selection as [R, max_sel] ``(values, flat indices)``: slots
        under the row's ``thr`` (None: importance >= 0, the exact geometry)
        or past its num_selects are invalid and carry (0.0, sentinel).
        Values: the kernel's, else a row-local gather (no global one)."""
        slot = jnp.arange(b.max_sel, dtype=jnp.int32)[None, :]
        valid = (top_scores >= (0 if thr is None else thr[:, None])) & (
            slot < jnp.asarray(b.num_selects)[:, None])
        gidx = jnp.where(valid, row_off + cols.astype(self.index_dtype),
                         jnp.asarray(self.layout.sentinel, self.index_dtype))
        vals = jnp.where(valid,
                         (fvals if fvals is not None else
                          jnp.take_along_axis(block, cols, axis=1)),
                         jnp.zeros((), block.dtype))
        return vals, gidx

    def sparsify(self, vec_c: jax.Array, key: jax.Array, seg_cands=None,
                 fwd_sel=None, stats_out: Optional[Dict] = None):
        """Sampled-top-k selection over the compressed block [T].

        ``seg_cands`` — optional ``(cand_vals, cand_blks)`` from the
        fused compensate pass (kernels.fused_compensate_bits_cands);
        seg-kernel buckets then slice their segments instead of
        re-reading the flat buffer.

        ``fwd_sel`` — optional dict ``{bucket id: (scores, values,
        columns)}`` from the forward megakernel
        (:meth:`_compensate_megakernel`): those buckets' selections
        were already extracted inside the compensate pass (bitwise
        kernels.select_pack_rows on the compensated block), so their
        select stage here is a dict lookup — no kernel launch, no
        re-read of the velocity. Thresholding, adaptation, and
        validity masking run unchanged on the fused scores.

        ``stats_out`` — optional dict the telemetry taps fill with
        per-bucket selection stats (selected_frac, threshold,
        payload_elems; see dgc_tpu.telemetry.registry) computed from the
        emitted payload. Only traced when telemetry is on.

        Returns tight ``(values, indices)`` of length ``payload_size``;
        padded/invalid slots carry (0.0, sentinel) — the sentinel is the
        always-zero gap slot after the compressed storage, so scatters to
        it are no-ops (SURVEY.md §2.5 tolerates zero/duplicate
        contributions under scatter-add) and no +1-extension copies are
        needed anywhere.

        The row-aligned layout makes every [R, cols] bucket view a pure
        reshape of ``vec_c``; importance padding (-1 on row tails) is a
        fused iota-compare, never an HBM gather.
        """
        lay = self.layout
        S = lay.sentinel
        if not self.buckets:
            if stats_out is not None:
                from dgc_tpu.telemetry import taps
                stats_out.update(taps.empty_bucket_stats(0))
            return (jnp.zeros((0,), vec_c.dtype),
                    jnp.zeros((0,), self.index_dtype))
        out_v, out_i = [], []
        # ONE shared [T/128, 128] block view for every wide bucket's
        # sampling gather and candidates kernel (XLA cannot CSE the
        # reshape across nested-jit kernel calls; per-call copies cost
        # ~2.5 ms/step at VGG, device profile r5)
        v2d = (vec_c.reshape(-1, 128)
               if any(self._use_seg_kernel(b) or self._use_3d(b)
                      for b in self._sparse_buckets) else None)
        def emit(vals, gidx, b):
            # identity tight map (padded payload, _bucket_from_rows):
            # the [R, max_sel] grid IS the payload — no compaction gather
            if b.payload == b.rows * b.max_sel:
                out_v.append(vals.reshape(-1))
                out_i.append(gidx.reshape(-1))
            else:
                tight = jnp.asarray(b.tight)
                out_v.append(vals.reshape(-1)[tight])
                out_i.append(gidx.reshape(-1)[tight])

        for bi, b in enumerate(self.buckets):
            if self.regimes[bi] == "dense":
                # dense-planned bucket: its slab rides the fallback psum
                # in exchange() — no selection, no payload contribution
                continue
            k = jax.random.fold_in(key, bi)
            if self._use_seg_kernel(b) or self._use_3d(b):
                # layout-free selection — no 2-D relayout of the bucket
                with _trace.phase("select", bi):
                    vals, gidx = self._sparsify_bucket_3d(vec_c, v2d, b, k,
                                                          cands=seg_cands)
                with _trace.phase("pack", bi):
                    emit(vals, gidx, b)
                continue
            R = b.rows
            row_off = jnp.asarray(b.row_offsets,
                                  dtype=self.index_dtype)[:, None]
            numels = jnp.asarray(b.numels)[:, None]

            # --- batched row view: a reshape, not a gather; row tails
            #     read importance -1 ---
            block = vec_c[b.base:b.base + R * b.cols].reshape(R, b.cols)
            col = jnp.arange(b.cols, dtype=jnp.int32)[None, :]
            in_row = col < numels
            imp_rows = jnp.where(in_row, jnp.abs(block),
                                 jnp.full((), -1.0, vec_c.dtype))

            if b.exact:
                # every row samples its whole tensor (num_samples == numel,
                # the small-tensor geometry at tight ratios): then
                # top_k_samples == num_selects identically (both are
                # ceil(numel*ratio)), the "sampled" threshold is the exact
                # k-th largest, and threshold-mask + truncate-to-num_selects
                # is exactly top-num_selects by importance — the selection
                # pass below. Skip the redundant sampling/threshold pass
                # (adaptation is statically off: numel == num_samples).
                with _trace.phase("select", bi):
                    vals, gidx = self._selection_payload(
                        b, block, row_off, None,
                        *self._select(bi, block, imp_rows, fwd_sel))
                with _trace.phase("pack", bi):
                    emit(vals, gidx, b)
                continue

            # --- sampling positions (reference compression.py:113-121) ---
            with _trace.phase("threshold", bi):
                samples = self._sample_rows(b, imp_rows, k)

            # --- per-row sampled threshold (compression.py:123) ---
            # the threshold is a QUANTILE ESTIMATE over an already-random
            # sample; at VGG-scale rows (fc1: max_k=1060 over a [1, 1.06M]
            # sample set) the exact sort-based top_k here cost ~60 ms/step
            # on v5e (118% overhead, paired) — approx_max_k estimates the
            # same quantile, its small low-bias is exactly what the
            # bounded ladder adaptation corrects, and on CPU it lowers to
            # the exact sort (equivalence tests unchanged)
            r = self.c.approx_recall
            with _trace.phase("threshold", bi):
                if r is not None and (b.max_k > 128
                                      or b.max_k * b.max_s > 2_000_000):
                    sorted_s = jax.lax.approx_max_k(
                        samples, b.max_k, recall_target=float(r))[0]
                else:
                    sorted_s = _exact_topk(samples, b.max_k)[0]
                thr = jnp.take_along_axis(
                    sorted_s, jnp.asarray(b.topk_samples)[:, None] - 1,
                    axis=1)[:, 0]

            # --- fixed-size selection (ops.select_by_threshold semantics) ---
            # top-k over RAW importance, below-threshold slots invalidated
            # after the fact: the selected set above thr is identical to
            # top-k over threshold-masked scores (top-k orders by value, so
            # the >= thr prefix matches), and skipping the mask saves a
            # full [R, cols] materialization per bucket; row-tail pads
            # carry importance -1 < 0 <= thr and can never turn valid.
            # Selection runs BEFORE threshold adaptation (it does not
            # depend on thr), so the resample ladder can be derived from
            # the top-k values with no extra pass over the block.
            with _trace.phase("select", bi):
                top_scores, fvals, cols = self._select(bi, block, imp_rows,
                                                       fwd_sel)

            # --- bounded threshold adaptation (compression.py:128-149) ---
            if self.c.max_adaptation_iters > 0 and b.adapt.any():
                with _trace.phase("threshold", bi):
                    if self.c.resample:
                        # exact ladder choice from the selection's own
                        # top-k — replaces the full [R, cols]
                        # ladder-counts scan (see _ladder_adapt_from_topk
                        # for the equality argument)
                        thr = _ladder_adapt_from_topk(
                            top_scores, thr,
                            jnp.asarray(b.num_selects, jnp.float32),
                            jnp.asarray(b.adapt),
                            self.c.compress_lower_bound,
                            self.c.max_adaptation_iters)
                    else:
                        thr = _batched_adapt(
                            imp_rows, thr,
                            jnp.asarray(b.num_selects, jnp.float32),
                            jnp.asarray(b.adapt),
                            self.c.compress_lower_bound,
                            self.c.compress_upper_bound,
                            self.c.max_adaptation_iters, self.c.resample)
            with _trace.phase("select", bi):
                vals, gidx = self._selection_payload(
                    b, block, row_off, thr, top_scores, fvals, cols)

            with _trace.phase("pack", bi):
                emit(vals, gidx, b)
        if stats_out is not None:
            # telemetry tap over the emitted payload (no extra HBM pass —
            # the payload-sized arrays are already live): per-bucket real
            # selection count / effective threshold, whole-model payload
            from dgc_tpu.telemetry import taps
            counts, thrs, fracs = [], [], []
            sj = 0
            for b, r in zip(self.buckets, self.regimes):
                if r == "dense":
                    # dense-planned bucket: everything rides the psum —
                    # selected fraction 1.0, no threshold, no sparse
                    # payload contribution
                    fracs.append(jnp.ones((), jnp.float32))
                    thrs.append(jnp.zeros((), jnp.float32))
                    continue
                v, i = out_v[sj], out_i[sj]
                sj += 1
                c, t = taps.bucket_payload_stats(v, i, S)
                counts.append(c)
                thrs.append(t)
                fracs.append(c / float(np.sum(b.numels)))
            stats_out["selected_frac"] = jnp.stack(fracs)
            stats_out["threshold"] = jnp.stack(thrs)
            stats_out["payload_elems"] = sum(counts)
        return jnp.concatenate(out_v), jnp.concatenate(out_i)

    # -------------------------------------------------------------- #
    # the full exchange, stage by stage over one _Exchange record    #
    # -------------------------------------------------------------- #

    def _dense_combine(self, block: jax.Array, axis_name: str,
                       world_size: int, op: str) -> jax.Array:
        """The dense collective: psum-average (hvd.Average), psum (Sum), or
        pairwise-recursive Adasum (reference allreduce op semantics)."""
        if op == "adasum":
            # Adasum's dot/norm accumulations must run in full precision —
            # an fp16 wire would overflow them to NaN on any real block
            from dgc_tpu.optim.adasum import adasum_allreduce
            return adasum_allreduce(block, axis_name, world_size)
        wire = (block.astype(jnp.float16) if self.c.fp16_values else block)
        _count_collective("psum", wire, axis_name, self)
        total = jax.lax.psum(wire, axis_name).astype(block.dtype)
        return total / world_size if op == "average" else total

    def exchange(self, flat_grad: jax.Array, mem: Dict, key: jax.Array,
                 axis_name: str, world_size: int, op: str = "average",
                 local_axis: Optional[str] = None, local_size: int = 1,
                 telemetry: bool = False,
                 health_out: Optional[Dict] = None,
                 send_frac=None,
                 update: Optional[InPlaceUpdate] = None):
        """compress -> communicate -> decompress over the whole model:
        two ``all_gather`` + one ``psum`` per step, total.

        ``update`` — the step's :class:`InPlaceUpdate` offer. Where the
        apply pass streams the buffer (:meth:`_apply`) it is taken: the
        pass writes the updated compressed block of the offer's state
        and the transmit record, no [T] gradient exists, and the first
        element returned is the dense tail's [P - T] gradient. Anywhere
        else the offer is left untouched and the program is the one
        ``update=None`` compiles.

        ``send_frac`` — straggler-adaptive exchange (docs/RESILIENCE.md
        §Adaptive exchange): a traced f32 scalar in [0, 1], THIS worker's
        effective send fraction. After sparsification, each row keeps
        only its ``ceil(num_selects * send_frac)`` largest selections;
        the rest are masked to the structural ``(0.0, sentinel)`` pad and
        dropped from the transmit record, so the withheld mass stays in
        the local error-feedback residual (mass-conserving, oracle-pinned
        in tests/test_adaptive.py). Payload shapes are static — zero
        extra collectives, zero recompiles. ``None`` (the default) is
        Python-static off: byte-identical program. The dense early path
        ignores it (a dense psum has no per-worker quota to shrink).

        ``health_out`` — mutable out-param dict (the ``stats_out``
        precedent from :meth:`sparsify`): with the engine's payload
        checksum on, the receiver-side mismatch count lands under
        ``"checksum_failures"`` (f32 scalar, identical on every worker —
        a pure function of gathered data). None (the default) skips the
        verification entirely; the guarded step passes a dict.

        ``telemetry=True`` additionally returns a third element: the
        per-step stat pytree of ``dgc_tpu.telemetry.registry.STEP_METRICS``
        (device scalars computed from intermediates the exchange already
        materializes — no host syncs, no extra dispatches). The default
        ``False`` traces none of it, so the compiled program is byte-for-
        byte the pre-telemetry HLO.

        ``op`` selects the combine semantics: "average" (hvd.Average — the
        harness default), "sum", or "adasum" (delta-optimizer variant, C5).
        Compressed payloads divide by world size ONLY for "average"
        (reference compression.py:192-193).

        **Two-tier hierarchical mode** (``local_axis`` set): the real form
        of the reference's "#Sparsified Nodes < #GPUs" regime — which it can
        only *simulate* through ``num_batches_per_step`` micro-batching
        (/root/reference/README.md:126-128,133-134,
        dgc/horovod/optimizer.py:70-72) — dense aggregation over the
        near-free ICI axis first (one full-precision ``psum`` over
        ``local_axis``, averaged over ``local_size``), then the whole DGC
        pipeline (compensate -> sparsify -> gather -> scatter-add) runs on
        the *node-aggregated* gradient with only ``axis_name`` (the
        DCN/host axis) as the sparse exchange group. ``world_size`` is then
        the number of sparsified nodes. Error-feedback memory is per-node
        (identical across a node's workers by construction: same node
        gradient, same selection key — the step builder shares the sparsify
        key within a local group).

        With no initialized compressed tensors (T == 0, e.g. an uninitialized
        compressor) every parameter falls through to the dense block —
        the same graceful degradation as the per-tensor path's
        ``name in attributes`` guard."""
        flat_grad = self._node_reduce(flat_grad, op, local_axis, local_size)
        # dgcver anchors (analysis/verify.py): identity `name` tags that
        # seed/sink the verifier's static taint passes. Zero HLO ops.
        st = _Exchange(grad=kernels.vtag(flat_grad, "dgcver.src.grad"),
                       mem=mem, update=update)
        if telemetry:
            from dgc_tpu.telemetry import taps
            st.taps = taps
            st.grad_norm = taps.l2(st.grad)
            st.clip_delta = jnp.zeros((), jnp.float32)
        # ratio >= 1.0, nothing initialized or an all-dense PLAN (the
        # fast-fabric regime): everything dense, ZERO gathers lowered
        if (self.T == 0 or self.c.compress_ratio >= 1.0
                or not self._sparse_ids):
            return self._exchange_dense(st, axis_name, world_size, op)
        self._compress(st, key, axis_name, world_size, op, send_frac)
        g_values, g_indices = self._wire(st, axis_name, health_out)
        self._apply(st, g_values, g_indices, axis_name, world_size, op)
        out = self._dense_tail(st, axis_name, world_size, op)
        return self._finish(st, out)

    def _node_reduce(self, flat_grad, op: str, local_axis: Optional[str],
                     local_size: int):
        """The two-tier mode's dense-over-ICI tier: full-precision node
        aggregation (the fp16 wire option applies to the slow DCN link
        only). Under "adasum" the NODE MEAN is the logical Adasum
        participant — the reference's Adasum (optimizer.py:197-367) with
        each "sparsified node" acting as one worker (as Horovod's own
        hierarchical Adasum: in-node sum + normalize, Adasum across)."""
        if local_axis is not None and local_size > 1:
            _count_collective("psum", flat_grad, local_axis, self)
            flat_grad = jax.lax.psum(flat_grad, local_axis)
            if op in ("average", "adasum"):
                flat_grad = flat_grad / local_size
        return flat_grad

    def _clip_tapped(self, st: _Exchange, block, names):
        """``_clip_block`` from offset 0 and, with telemetry, the share of
        the block's norm the clip took (``clip_delta``)."""
        if st.taps is None:
            return self._clip_block(block, names, 0)
        pre = st.taps.l2(block)
        block = self._clip_block(block, names, 0)
        st.clip_delta = (pre - st.taps.l2(block)) / jnp.maximum(pre, 1e-12)
        return block

    def _gossip_round(self, mem, axis_name, world_size: int, op: str):
        """This round's gossip state (compression/gossip.py): pure
        functions of replicated memory state, so every worker computes
        identical values — zero extra collectives."""
        g_cfg = self._gossip
        if int(world_size) != g_cfg.world:
            raise ValueError(
                f"gossip plan was built for world={g_cfg.world} but "
                f"exchange runs with world_size={world_size} — "
                "replan for the current cohort")
        if op != "average":
            raise ValueError(
                "gossip regimes require op='average': the neighbor "
                f"mixing weights fold into the averaging divide "
                f"(got op={op!r})")
        clock = mem["gossip_clock"]
        dropped = (_faults.gossip_dropped(g_cfg.world, clock)
                   if _faults.armed() else None)
        full, forced, new_age = _gossip_sched.round_state(
            g_cfg, clock, mem["gossip_age"], dropped)
        widx = jax.lax.axis_index(axis_name)
        row_w = _gossip_sched.row_weights(g_cfg, clock, widx, full, dropped)
        return _GossipRound(dropped, full, forced, new_age, widx, row_w)

    def _compress(self, st: _Exchange, key, axis_name, world_size: int,
                  op: str, send_frac) -> None:
        """Gradient + memory -> this worker's payload (``st.values``,
        ``st.indices``): clip, masked compensate (or the forward
        megakernel), the gossip round and its inbox fold, ``sparsify``, the
        ``send_frac`` mask, the delta codec's sort. Also leaves the live
        ``mc``/``vc``/``md``, the PREVIOUS ``mc``/``vc`` for _dense_tail,
        the gossip round and the selection stats."""
        T, m = self.T, self._mem
        mem = st.mem
        gc, st.gd = st.grad[:T], st.grad[T:]
        if m is not None:
            st.mc = kernels.vtag(mem["momentums_c"], "dgcver.src.momentum")
            st.vc = kernels.vtag(mem["velocities_c"], "dgcver.src.residual")
            st.md = mem["momentums_d"]
        # the state BEFORE the accumulating compensate below, which runs
        # over the whole [T] buffer: _dense_tail corrects dense-PLANNED
        # slabs from it
        st.mc_prev, st.vc_prev = st.mc, st.vc

        # --- compressed block: masked compensate -> sparsify ---
        cands = fwd_sel = None
        if m is not None:
            if m.gradient_clipping is not None:
                # clipping runs on the LOCAL gradient inside the accumulating
                # compensate (reference memory.py:52-53)
                gsrc = self._clip_tapped(st, gc, self.layout.compressed_names)
            else:
                # the WHOLE flat buffer: no [:T] operand-slice copy on the
                # fused-candidates TPU path (see _compensate_acc)
                gsrc = st.grad
            # deferred masking (memory.py:72-77): the PREVIOUS step's
            # transmit record is applied on read inside the compensate
            # pass. x*0 == set-to-0 for finite values, and the sentinel
            # slot is a structural zero, so padded payload slots are no-ops.
            if self._mk_fwd_ids:
                # forward megakernel (plan-static opt-in; see
                # _compensate_megakernel): sparsify consumes its selections
                # via fwd_sel. Seg-kernel buckets (if any coexist) fall
                # back to the standalone candidates kernel — the
                # megakernel path does not thread want_cands.
                with _trace.phase("forward"):
                    comp, st.mc, st.vc, fwd_sel = self._compensate_megakernel(
                        st.mc, st.vc, gsrc, mem["sent_bits"])
            else:
                with _trace.phase("compensate"):
                    comp, st.mc, st.vc, cands = self._compensate_acc(
                        st.mc, st.vc, gsrc, mem["sent_bits"],
                        want_cands=self._seg_fused)
        else:
            comp = gc

        if self._gossip is not None:
            # plan-static: None lowers nothing
            st.gossip = self._gossip_round(mem, axis_name, world_size, op)
            # fold LAST round's received neighbor mass into the velocity
            # accumulator — AFTER the deferred transmit mask above, so a
            # freshly received value can never be wiped by this worker's
            # own transmit record; and into the VELOCITY only (the
            # sender already ran its momentum), matching the oracle in
            # tests/test_gossip.py. The inbox is consumed exactly once:
            # it is rewritten from this round's gather in _apply.
            st.vc = st.vc + mem["gossip_inbox"].astype(st.vc.dtype)
            comp = st.vc
        if os.environ.get("DGC_VERIFY_MUTATE", "") == "cast_bf16":
            # seeded mutation (tests/test_analysis_verify.py): a silent
            # precision drop on the compensated gradient — the dgcver
            # dtype-flow pass must turn the gate red on this
            comp = comp.astype(jnp.bfloat16).astype(st.grad.dtype)
        st.sel_stats = {} if st.taps is not None else None
        values, indices = self.sparsify(comp, key, seg_cands=cands,
                                        fwd_sel=fwd_sel,
                                        stats_out=st.sel_stats)
        # tag the selection BEFORE the adaptive mask: masked derivations
        # must stay tainted so conservation covers the withheld tail too
        values = kernels.vtag(values, "dgcver.sel_values")
        indices = kernels.vtag(indices, "dgcver.sel_indices")
        if send_frac is not None and self._adaptive_rank is not None:
            # straggler-adaptive masking (resilience/adaptive.py; see
            # ``exchange``): the withheld slots become structural
            # (0.0, sentinel) pads — wire no-ops everywhere downstream
            # (quantize/checksum/scatter) and absent from the record
            fr = jnp.clip(jnp.asarray(send_frac, jnp.float32), 0.0, 1.0)
            keep = (jnp.asarray(self._adaptive_rank)
                    < jnp.ceil(jnp.asarray(self._adaptive_quota) * fr))
            values = jnp.where(keep, values, jnp.zeros((), values.dtype))
            indices = jnp.where(keep, indices,
                                jnp.asarray(self.layout.sentinel,
                                            indices.dtype))
            if st.sel_stats is not None:
                # transmitted elements, post-mask (selection stats like
                # selected_frac/threshold stay pre-mask by design: they
                # describe the selection, this describes the wire)
                st.sel_stats["payload_elems"] = jnp.sum(
                    (indices != self.layout.sentinel).astype(jnp.float32))
        if self._dcodec is not None:
            # Elias-Fano precondition: each delta bucket's payload slice
            # sorted by canonical position BEFORE any lane packing, so
            # the quantized q lane and the index stream stay aligned
            with _trace.phase("pack"):
                values, indices = self._sort_delta_payload(values, indices)
        st.values, st.indices = values, indices

    @property
    def _int8_ef(self) -> bool:
        """int8 error feedback runs: an int8 lane, a memory for the
        rounding residual, the compressor's switch."""
        return bool(self._kind_payload.get("i8") and self._mem is not None
                    and getattr(self.c, "int8_error_feedback", False))

    def _encode_values(self, values: jax.Array):
        """Payload values -> the value lanes (q, f32, f16) as they ship,
        and, under int8 error feedback, the dequantized int8 payload in
        f32 (what the wire carried of those slots, for the caller to take
        out of the velocity), else None."""
        kp = self._kind_payload
        q_wire = q4_wire = scale = scale4 = dequant = None
        if kp.get("i8"):
            # int8 wire lane: symmetric per-TENSOR quantization (one f32
            # scale per row, segment-max over the tight payload) — the
            # reference's stated "no quantization/encoding of payloads"
            # caveat (README.md:130-138) addressed; dequantized after the
            # gather, before the scatter-add
            vals_i8 = self._chunks(values, self._kinds, "i8")
            with _trace.phase("pack"):
                smax = jax.ops.segment_max(jnp.abs(vals_i8), self._row_map,
                                           num_segments=self._i8_rows)
                scale = (smax / 127.0).astype(jnp.float32)
                safe = jnp.where(scale > 0, scale, 1.0)
                q_wire = jnp.clip(jnp.round(vals_i8 / safe[self._row_map]),
                                  -127, 127).astype(jnp.int8)
            if self._int8_ef:
                dequant = q_wire.astype(jnp.float32) * scale[self._row_map]
        if kp.get("i4"):
            # int4 wire lane: symmetric per-BUCKET quantization (one f32
            # scale per bucket — the payload is small enough that a
            # coarser scale granularity buys half the value bytes), two
            # nibbles per byte, each bucket padded to whole bytes
            from dgc_tpu.compression.wirecodec import pack_int4
            vals_i4 = self._chunks(values, self._kinds, "i4")
            with _trace.phase("pack"):
                smax4 = jax.ops.segment_max(jnp.abs(vals_i4),
                                            self._i4_map,
                                            num_segments=self._i4_buckets)
                scale4 = (smax4 / 7.0).astype(jnp.float32)
                safe4 = jnp.where(scale4 > 0, scale4, 1.0)
                q4 = jnp.clip(
                    jnp.round(vals_i4 / jnp.take(safe4, self._i4_map)),
                    -7, 7).astype(jnp.int32)
                nb = [pack_int4(q4[plo:phi])
                      for plo, phi, _, _ in self._i4_chunks]
                q4_wire = nb[0] if len(nb) == 1 else jnp.concatenate(nb)
        # f32 lane: a single part ships identity (uniform plans keep
        # their exact pre-planner wire arrays); several promote to f32
        # for the concat
        f32_parts = [p for p in (
            self._chunks(values, self._kinds, "f32") if kp.get("f32")
            else None, scale, scale4) if p is not None]
        f32_wire = f16_wire = None
        if len(f32_parts) == 1:
            f32_wire = f32_parts[0]
        elif f32_parts:  # dgclint: ok[tracer-branch] — list emptiness is plan-static (kp/scale), not a tracer test
            f32_wire = jnp.concatenate(
                [p.astype(jnp.float32) for p in f32_parts])
        if kp.get("f16"):
            f16_wire = self._chunks(values, self._kinds,
                                    "f16").astype(jnp.float16)
        if q_wire is not None and q4_wire is not None:
            q_lane = jnp.concatenate([q_wire, q4_wire])
        else:
            q_lane = q_wire if q_wire is not None else q4_wire
        return _Lanes(q=q_lane, f32=f32_wire, f16=f16_wire), dequant

    def _encode_indices(self, indices: jax.Array, chk=None) -> _Lanes:
        """Payload indices -> the index lanes: the shared uint32 word lane
        (IndexCodec words first, Elias-Fano delta words after) and the
        plain-offset lane. ``chk`` (the sender's checksum words, or None)
        rides behind the IndexCodec words where there are any, else behind
        the plain offsets (never beside delta words: no checksum + int8)."""
        words = plain = None
        if self._codec is not None or self._dcodec is not None:
            with _trace.phase("pack"):
                wparts = []
                if self._codec is not None:
                    wparts.append(self._codec.encode(
                        self._chunks(indices, self._packed, True)))
                    if chk is not None:
                        # int32 -> uint32 astype: a bit-preserving mod-2^32
                        # wrap, undone symmetrically on the receiver
                        wparts.append(chk.astype(jnp.uint32))
                if self._dcodec is not None:
                    wparts.append(self._dcodec.encode(
                        self._chunks(indices, self._packed, "delta")))
                words = (wparts[0] if len(wparts) == 1
                         else jnp.concatenate(wparts))
        if self._plain_payload:
            with _trace.phase("pack"):
                plain = self._chunks(indices, self._packed, False)
                if chk is not None and self._codec is None:
                    plain = jnp.concatenate(
                        [plain, chk.astype(self.index_dtype)])
        return _Lanes(words=words, plain=plain)

    def _gather(self, lanes: _Lanes, axis_name) -> _Lanes:
        """The engine's ONE all-gather site: every lane that exists, in
        the order of ``_Lanes``' fields, each counted as it is issued."""
        out = []
        with _trace.phase("allgather"):
            for lane in lanes:
                if lane is not None:
                    _count_collective("all_gather", lane, axis_name, self)
                    lane = jax.lax.all_gather(lane, axis_name)
                out.append(lane)
        return _Lanes(*out)

    def _decode_values(self, g: _Lanes, dt) -> jax.Array:
        """Gathered value lanes -> [W, payload] values in payload order.
        Uniform fp16/f32 plans hand their lane through at wire precision
        (the shared ``.astype(dt)`` happens at the scatter)."""
        kinds = set(self._kinds)
        if kinds == {"f16"}:
            return g.f16
        if kinds == {"f32"}:
            return g.f32
        kp = self._kind_payload
        with _trace.phase("decode"):
            if kinds == {"i8"}:
                return g.q.astype(dt) * jnp.take(
                    g.f32.astype(dt), self._row_map, axis=1)
            if kinds == {"i4"}:
                # the f32 lane is exactly the per-bucket scale vector
                return self._decode_i4(g.q, g.f32, dt)
            # mixed plan: stitch the lanes back into payload order
            n8, f32_off = kp.get("i8", 0), kp.get("f32", 0)
            if n8:
                g_i8 = g.q[:, :n8].astype(dt) * jnp.take(
                    g.f32[:, f32_off:].astype(dt), self._row_map, axis=1)
            if kp.get("i4"):
                g_i4 = self._decode_i4(
                    g.q[:, n8:], g.f32[:, f32_off + self._i8_rows:], dt)
            parts = []
            for kk, lo, hi in self._val_chunks:
                if kk == "i8":
                    parts.append(g_i8[:, lo:hi])
                elif kk == "i4":
                    parts.append(g_i4[:, lo:hi])
                elif kk == "f16":
                    parts.append(g.f16[:, lo:hi].astype(dt))
                else:
                    parts.append(g.f32[:, lo:hi].astype(dt))
            return jnp.concatenate(parts, axis=1)

    def _decode_indices(self, g: _Lanes, checksum: bool = False):
        """Gathered index lanes -> ([W, payload] indices in payload order,
        the gathered checksum words or None): static gathers + shifts,
        exact for every real slot; padded slots land in-row, value 0.0."""
        g_chk = None
        srcs = {True: None, False: None, "delta": None}
        if g.words is not None:
            with _trace.phase("decode"):
                nc = self._codec.nwords if self._codec is not None else 0
                if checksum:
                    g_chk = g.words[:, nc:].astype(jnp.int32)
                if self._dcodec is not None:
                    srcs["delta"] = self._dcodec.decode(
                        g.words[:, nc:nc + self._dcodec.nwords],
                        self.index_dtype)
                if self._codec is not None:
                    srcs[True] = self._codec.decode(
                        g.words[:, :nc], self.index_dtype)
        if g.plain is not None:
            with _trace.phase("decode"):
                if checksum and self._codec is None:
                    g_chk = g.plain[:, self._plain_payload:].astype(
                        jnp.int32)
                    srcs[False] = g.plain[:, :self._plain_payload]
                else:
                    srcs[False] = g.plain
        live = [s for s in srcs.values() if s is not None]
        if len(live) == 1:
            return live[0], g_chk
        with _trace.phase("decode"):
            return jnp.concatenate(
                [srcs[p][:, lo:hi] for p, lo, hi in self._idx_chunks],
                axis=1), g_chk

    def _wire(self, st: _Exchange, axis_name, health_out):
        """Payload -> every worker's, [W, payload] values and indices:
        encode, gather, decode — the values, then the indices: the value
        decode and the sender's checksum stay between the two groups of
        gathers, where the int8, packed and guarded programs have them."""
        lanes, dequant = self._encode_values(st.values)
        if dequant is not None:
            # quantization ERROR FEEDBACK: the wire carried q*scale, so
            # the velocity keeps the rounding residual ``values - q*scale``
            # instead of being zeroed. vc already holds ``values`` there
            # (comp IS the velocity), so one scatter-subtract of the
            # dequantized payload leaves exactly the residual — and the
            # int8 slots' transmit record stays EMPTY this step (the
            # residual must survive the next compensate). Momentum masking
            # (memory.py:72-77) happens eagerly instead, bitwise the
            # deferred form since nothing reads mmt in between. Padded
            # slots carry (sentinel, q=0): a no-op at the structural zero.
            dequant = dequant.astype(st.vc.dtype)
            idx_i8 = self._chunks(st.indices, self._kinds, "i8")
            st.vc = st.vc.at[idx_i8].add(-dequant)
            if self._mem.momentum_masking:
                st.mc = st.mc.at[idx_i8].set(jnp.zeros((), st.mc.dtype))
        g_values = self._decode_values(self._gather(lanes, axis_name),
                                       st.grad.dtype)
        if _faults.armed():
            # deterministic post-gather corruption (tests only)
            g_values = _faults.corrupt_wire(g_values)
        checksum = self.checksum and health_out is not None
        chk = None
        if checksum:
            # sender-side per-bucket checksum over the exact wire forms:
            # the value words as shipped (checksum plans are uniform
            # non-int8: exactly one value lane), and the indices in the
            # form the receiver reconstructs (codec slots clip in-row —
            # see IndexCodec.canonical). Rides the index gather below.
            with _trace.phase("pack"):
                wire_values = lanes.f16 if lanes.f16 is not None else lanes.f32
                idx_canon = (self._codec.canonical(st.indices)
                             if self._codec is not None else st.indices)
                chk = integrity.payload_checksum(
                    wire_values, idx_canon, self._seg_ids, self._num_seg)
        g_indices, g_chk = self._decode_indices(
            self._gather(self._encode_indices(st.indices, chk), axis_name),
            checksum)
        if _faults.armed():
            g_indices = _faults.corrupt_indices(g_indices)
        if checksum:
            health_out["checksum_failures"] = integrity.count_mismatches(
                g_values, g_indices, g_chk, self._seg_ids, self._num_seg)
        # always-on bounds clamp BEFORE the scatter-add: XLA drops >= T
        # indices under jit but wraps NEGATIVE ones python-style, so a
        # corrupted payload word decoding to -5 would silently add
        # garbage at T-5 (integrity.clamp_indices routes them to the
        # sentinel). Honest traffic passes through bitwise unchanged.
        with _trace.phase("decode"):
            g_indices = integrity.clamp_indices(
                g_indices, self.T, self.layout.sentinel, *self._clamp_bounds)
        return g_values, g_indices

    def _sent_flags(self, g_indices, axis_name):
        """Per gathered entry: THIS worker's and a real slot — the fused
        apply kernels' transmit record, bitwise ``pack_sent_bits``. Part
        of the pass's staging (``_apply`` calls it under ``apply``)."""
        with _trace.phase("apply", part="stage"):
            me = jax.lax.axis_index(axis_name)
            rows = jnp.arange(g_indices.shape[0], dtype=jnp.int32)[:, None]
            return ((rows == me)
                    & (g_indices != self.layout.sentinel)).reshape(-1)

    def _transmit_record(self, st: _Exchange):
        """THIS step's transmit record for the next compensate:
        bit-packed, one word-wide scatter over a 32x smaller buffer
        (padded slots carry the sentinel and are dropped — their repeated
        single-bit adds would carry across bits). Under int8 error
        feedback (see _wire) the int8 slots keep an EMPTY record; in a
        mixed plan the non-i8 buckets still record theirs."""
        indices = st.indices
        with _trace.phase("pack"):
            if self._int8_ef and self._i8_slot_mask is None:
                return jnp.zeros_like(st.mem["sent_bits"])
            if self._int8_ef:
                rec = jnp.where(
                    jnp.asarray(self._i8_slot_mask),
                    jnp.asarray(self.layout.sentinel, indices.dtype),
                    indices)
                return kernels.pack_sent_bits(
                    rec, self.T, sentinel=self.layout.sentinel)
            if os.environ.get("DGC_VERIFY_MUTATE", "") == "drop_foldback":
                # seeded mutation: lose the transmit record, so the next
                # compensate re-sends what the wire already carried — the
                # dgcver ef-conservation pass must turn the gate red
                return jnp.zeros_like(st.mem["sent_bits"])
            return kernels.pack_sent_bits(
                indices, self.T, sentinel=self.layout.sentinel)

    def _apply(self, st: _Exchange, g_values, g_indices, axis_name,
               world_size: int, op: str) -> None:
        """Every worker's payload -> the sparse tier's [T] contribution
        (``st.acc``), this worker's transmit record (``st.new_bits``)
        and, on a gossip plan, the round's inbox.

        Averaging divides the [W, payload] WIRE values BEFORE the
        scatter (algebraically identical to the reference's
        scatter-then-divide, compression.py:192-193; differs by
        float-rounding order only): the full-[T] divide pass disappears
        — its read/write cost scales with the model, ~0.8 ms/step at
        VGG.

        **Three forms of one step, chosen from T, W * payload and
        whether the step offers its optimizer's rule**
        (:meth:`_apply_streams`; ``exchange.apply`` counts the pairs
        and names the form under ``step.trace``). What the chip says
        (v5e; PR 30's probes, PR 31's, PR 35's and PR 41's, `PERF.md`
        §6):

        * ``scatter``: ``zeros[T].at[idx].add(wire)``, then
          ``pack_sent_bits`` over the local indices. XLA:TPU's scatter
          ALIASES its operand and is a PASS over it (an earlier
          installation read "always copies": disproved, PR 30): into
          zeros the pass is a fused fill, 0.85 ms at VGG's T, plus 9.1
          ns a pair wherever the pairs fall (2.07 ms at 138,360 pairs,
          5.86 at 553,440); into a live buffer (the optimizer's output,
          the [P] result with its tail) it is a [T] read + write, which
          is why "update without the dense gradient" gave back what it
          saved. The bit scatter is a second per-pair pass: 1.39 ms.
          The fused [2T] acc+sent scatter, scatter-set into the live
          mmt/vec buffers and sub-word masks all lose as well.
        * ``update``: ``stream``, taken one step further where the
          step offers its optimizer's elementwise rule
          (:class:`InPlaceUpdate`): the same pass reads the chunk's p
          and buf blocks, runs the rule on the chunk's gradient while
          it is in VMEM and writes p', buf' and the record
          (``kernels.payload_update_bits``). The [T] gradient is never
          written and the optimizer's five-stream fusion (4.13 ms at
          VGG) shrinks to the dense tail: alone the pass takes 3.39 ms
          at 138,360 pairs against 1.49 + 4.12 before, 3.74 at 553,440
          against 2.87 + 4.15 (step 0 of PR 35, `PERF.md` §6).
        * ``stream``: one stable sort of the pairs and ONE pass over
          the buffer (``kernels.payload_apply_bits``), which expands
          128 pairs at a time into one-hot matrix products and writes
          every output tile and the transmit record once, into a [P]
          buffer whose tail :meth:`_dense_tail` fills in place. 1.78 ms
          against 3.46 at VGG's T with 138,360 pairs, 3.90 against 7.25
          with 553,440 (sort 0.45 / 1.09 of it). The staging the two
          opt-in kernels had before (argsort + take + three payload-
          sized scatter-sets, ``_stage_payload``) cost 7.05 / 27.70 ms
          alone: every payload-sized XLA scatter, gather or argsort is
          6-10 ns an element here.

        The rule has a case for each answer to "is a [T] gradient
        written?" (and, either way, the pairs' window maps fit scalar
        memory). WITHOUT the step's offer (guards, no donation,
        per-worker optimizer state, a chained transformation, a
        vector-form mask, a dense-planned bucket) one is: stream where
        that accumulator cannot stay on the chip, ``4 T > 128 MiB``.
        At ResNet-50 (T 27,068,416: 108 MB) XLA keeps the scatter's
        [T] in VMEM until the optimizer has read it, so its fill and
        re-read are free and a kernel that writes HBM gives that up:
        forced to ``stream``, the step's ``dgc_overhead_ms`` read
        2.215, 2.239 against 2.185, 2.180 with the scatter (PR 31),
        although alone the pass is the faster of the two there (0.43
        against 0.60 ms); 556 MB wins by 1.8, and W * payload did not
        move it (1x and 4x at VGG). WITH the offer (form ``update``)
        none is, there is nothing to keep, and that bound does not
        apply: the pass replaces the scatter, the bit scatter AND the
        optimizer's five-stream fusion, and wins down to the
        staging's fixed cost, ``4 T > 8 MB``. Step 0 of PR 41 (bare
        programs at five T, :attr:`APPLY_UPDATE_MIN_BYTES`' comment):
        at ResNet-50's T 0.717 ms against 1.083, a win down to
        ResNet-110's 2,029,568 and a loss at ResNet-20's 370,688.
        In the whole step (``resnet50.steady``, parent | change on one
        machine, P C C P): ``dgc_overhead_ms`` 2.188, 2.188 | 1.889,
        1.879; traced, phase ``apply`` 0.234 -> 0.722 (the pass 0.665,
        sort + staging 0.057), ``pack`` 0.300 -> 0.125 (the bit
        scatter was 0.175 of it), the optimizer's 0.659 -> 0.002.
        Static plan properties the kernel does not serve keep the
        scatter: no error-feedback memory, a non-f32 value wire, int8
        error feedback, an int64 wire, a gossip plan; off the TPU
        backend the scatter stays unless ``fused_apply`` / ``megakernel``
        force the kernel (interpreted, small payloads: the parity
        tests)."""
        T, m = self.T, self._mem
        dt = st.grad.dtype
        gr = st.gossip
        if gr is not None:
            # per-sender row weights (gossip.row_weights) realize the
            # round semantics on the ONE gathered wire, before the /W
            # below: shapes and collectives are identical every round,
            # and mixing columns sum to 1, so global signed mass is
            # conserved (oracle-pinned)
            g_values = g_values * gr.row_w[:, None].astype(g_values.dtype)
        wire = g_values.reshape(-1).astype(dt)
        mk_apply = self._use_megakernel_apply(m, self._int8_ef, dt)
        flagged = mk_apply or self._use_fused_apply(m, self._int8_ef, dt)
        # the step's offer counts where nothing else writes the
        # compressed block (a dense-planned bucket does), and is taken
        # where the geometry rule then streams (the opt-in kernels keep
        # their own form)
        offered = st.update is not None and not self._dense_ids
        stream = flagged or (
            kernels.use_pallas()
            and self._apply_kernel_serves(m, self._int8_ef, dt)
            and self._apply_streams(T, wire.shape[0], offered))
        take = stream and not flagged and offered
        _trace.count("exchange.apply", wire.shape[0],
                     path=("update" if take else "stream" if stream
                           else "scatter"))
        if op == "average" and not mk_apply:
            wire = wire / world_size
        if take:
            # the streamed pass below with the offer's rule inside it
            # (kernels.payload_update_bits): each chunk's gradient stays
            # in VMEM, where the rule reads it beside the chunk's state
            # blocks and writes them back in place. Four [T] streams
            # where the pass and the optimizer's fusion moved six
            with _trace.phase("apply"):
                offer = st.update
                offer.state, st.new_bits = kernels.payload_update_bits(
                    wire, g_indices.reshape(-1),
                    self._sent_flags(g_indices, axis_name), T,
                    offer.state, offer.rule, offer.scalars(),
                    bits_donor=st.mem["sent_bits"],
                    max_dup=g_indices.shape[0])
                offer.taken = True
        elif stream:
            # one pass over the buffer (kernels.payload_apply_bits): a
            # stable sort of the pairs, then each VMEM-resident chunk
            # takes its pairs as one-hot matrix products and its
            # transmit bits (from _sent_flags) in the same visit, and is
            # written once, into a [P] buffer whose tail _dense_tail
            # fills in place; the dead previous-step record is donated
            # for the rebuild. Duplicates sum in payload order, so
            # values and record are bitwise the XLA path's on the CPU
            # (tests/test_flat.py, tests/test_megakernel.py). The
            # megakernel flag folds the worker average into the staging
            # (kernels.dgc_apply_rows): the same per-entry IEEE divide.
            with _trace.phase("apply"):
                flags = self._sent_flags(g_indices, axis_name)
                apply = (functools.partial(
                    kernels.dgc_apply_rows,
                    divisor=(float(world_size) if op == "average"
                             else None))
                    if mk_apply else kernels.payload_apply_bits)
                st.acc, st.new_bits = apply(
                    wire, g_indices.reshape(-1), flags, T,
                    bits_donor=st.mem["sent_bits"],
                    out_total=self.layout.total,
                    # a worker sends a coordinate once (disjoint rows,
                    # distinct top-k): W bounds a run of equal indices
                    max_dup=g_indices.shape[0])
        else:
            with _trace.phase("apply"):
                st.acc = jnp.zeros((T,),
                                   dt).at[g_indices.reshape(-1)].add(wire)
            if m is not None:
                st.new_bits = self._transmit_record(st)
        if gr is not None:
            with _trace.phase("apply"):
                if gr.dropped is not None:
                    # a dropped worker's transmit record is voided: the
                    # round carried none of its mass (receivers folded a
                    # zero-weighted row), so the mass must stay in its
                    # error-feedback residual for a later round — the
                    # droplink leg of the conservation oracle
                    st.new_bits = jnp.where(gr.dropped[gr.widx],
                                            jnp.zeros_like(st.new_bits),
                                            st.new_bits)
                # by round type: a gossip round feeds ONLY the
                # neighborhood inbox (folded into the velocities next
                # round) and the parameters see zeros from the sparse
                # tier; a full-sync round feeds them and resets the inbox
                st.inbox = jnp.where(gr.full, jnp.zeros_like(st.acc), st.acc)
                st.acc = jnp.where(gr.full, st.acc, jnp.zeros_like(st.acc))

    def _dense_correct(self, avg, mmt, vec, keep, lo: int, hi: int):
        """The dense (non-accumulating) correction of [lo, hi) of the
        compressed block from the state BEFORE this step: materialize the
        pending transmit mask of a previous compressed step (``keep``,
        [T], or None; the reference zeroed those coordinates at the
        compressed step, memory.py:72-77), then ``_compensate_dense`` on
        ``avg``, read from its start. Returns ``(out, mmt', vec')`` for
        the range; over the whole block every slice is the identity."""
        mmt, vec = mmt[lo:hi], vec[lo:hi]
        if keep is not None:
            k = keep[lo:hi].astype(vec.dtype)
            vec = vec * k
            if self._mem.momentum_masking:
                mmt = mmt * k
        out, mmt = self._compensate_dense(mmt, avg[:hi - lo])
        return out, mmt, vec

    def _exchange_dense(self, st: _Exchange, axis_name, world_size: int,
                        op: str):
        """The all-dense step: one collective over the whole buffer, then
        the dense correction of the compressed block and of the tail."""
        T, P, m = self.T, self.layout.total, self._mem
        mem = st.mem
        avg = self._dense_combine(st.grad, axis_name, world_size, op)
        if m is None:
            return self._finish(st, avg)
        if m.gradient_clipping is not None:
            avg = self._clip_tapped(st, avg, self.layout.names)
        mc = kernels.vtag(mem["momentums_c"], "dgcver.src.momentum")
        vc = kernels.vtag(mem["velocities_c"], "dgcver.src.residual")
        bits = mem.get("sent_bits")
        keep = (kernels.keep_from_bits(bits, T)
                if T and bits is not None else None)
        out_c, st.mc, st.vc = self._dense_correct(avg, mc, vc, keep, 0, T)
        out_d, st.md = self._compensate_dense(mem["momentums_d"], avg[T:])
        out = (jnp.concatenate([out_c, out_d]) if T and P > T
               else (out_c if T else out_d))
        # the record is reset: carrying it forward would wrongly zero the
        # dense momentum written above
        st.new_bits = jnp.zeros((kernels.num_sent_words(T) if T else 0,),
                                jnp.int32)
        return self._finish(st, out)

    def _dense_tail(self, st: _Exchange, axis_name, world_size: int,
                    op: str):
        """The dense fallback block: one collective + correction.
        Dense-PLANNED buckets ride the SAME psum as the dense tail (one
        concatenated wire), then split back into per-bucket slabs that get
        the dense-path semantics: clip on the averaged gradient, pending
        transmit mask from the PREVIOUS state materialized,
        non-accumulating compensate — overriding what the accumulating
        compensate wrote in [0, T). Returns the step's [P] result, or
        its [P - T] tail where the apply pass took the step's update."""
        T, P, m = self.T, self.layout.total, self._mem
        clip = m.gradient_clipping if m is not None else None
        acc = st.acc
        dslabs = [(i, self.buckets[i]) for i in self._dense_ids]
        if not (P > T or dslabs):
            return jnp.zeros((0,), st.grad.dtype) if acc is None else acc
        with _trace.phase("dense"):
            dparts = [st.grad[b.base:b.base + b.rows * b.cols]
                      for _, b in dslabs]
            # dparts emptiness is plan-static (dense regime ids)
            dwire = (jnp.concatenate(dparts + [st.gd])  # dgclint: ok[tracer-branch]
                     if dparts else st.gd)
            davg = self._dense_combine(dwire, axis_name, world_size, op)
            keep, off = None, 0
            for i, b in dslabs:
                lo, n = b.base, b.rows * b.cols
                slab = davg[off:off + n]
                off += n
                if clip is not None:
                    slab = self._clip_block(
                        slab, self.layout.buckets[i].names, lo)
                if m is None:
                    acc = acc.at[lo:lo + n].set(slab.astype(acc.dtype))
                    continue
                if keep is None:
                    keep = kernels.keep_from_bits(st.mem.get("sent_bits"), T)
                out_slab, mslab, vslab = self._dense_correct(
                    slab, st.mc_prev, st.vc_prev, keep, lo, lo + n)
                acc = acc.at[lo:lo + n].set(out_slab.astype(acc.dtype))
                st.mc = st.mc.at[lo:lo + n].set(mslab)
                st.vc = st.vc.at[lo:lo + n].set(vslab)
            if P > T:
                gd_avg = davg[off:]
                if clip is not None:
                    # the fallback's compensate sees the AVERAGED
                    # gradient (reference compression.py:198 ->
                    # memory.py:52-53)
                    gd_avg = self._clip_block(gd_avg,
                                              self.layout.dense_names, T)
                out_d, st.md = self._compensate_dense(st.md, gd_avg)
        if acc is None:     # taken by the step's update: the tail alone
            return out_d.astype(st.grad.dtype)
        if P > T and acc.shape[0] == P:  # the apply kernel's [P]: in place
            return jax.lax.dynamic_update_slice(
                acc, out_d.astype(acc.dtype), (T,))
        return jnp.concatenate([acc, out_d]) if P > T else acc

    def _finish(self, st: _Exchange, out):
        """The step's result, ``(out, memory)`` and with telemetry the
        stats: the one place the memory dict is assembled."""
        mem = st.mem
        if self._mem is not None:
            # the verifier's sinks pair with the selection's tags: an
            # all-dense step plants neither
            tag = kernels.vtag if st.values is not None else (lambda x, _: x)
            mem = {"momentums_c": tag(st.mc, "dgcver.sink.momentum"),
                   "velocities_c": tag(st.vc, "dgcver.sink.residual"),
                   "momentums_d": st.md, "velocities_d": mem["velocities_d"],
                   "sent_bits": tag(st.new_bits, "dgcver.sink.sent_bits")}
            gr = st.gossip
            if gr is not None:
                mem["gossip_clock"] = st.mem["gossip_clock"] + 1
                mem["gossip_age"] = gr.new_age
                mem["gossip_inbox"] = st.inbox.astype(st.vc.dtype)
                mem["gossip_forced"] = (st.mem["gossip_forced"]
                                        + gr.forced.astype(jnp.int32))
        if st.taps is None:
            return out, mem
        return out, mem, self._telemetry_stats(st)

    def _telemetry_stats(self, st: _Exchange):
        """The STEP_METRICS pytree (see telemetry.taps) of the step's
        record. Without a selection (the all-dense paths) payload and wire
        are zero and vc IS the residual. Under deferred masking ``vc``
        still holds exactly the transmitted values, which masking will
        zero: the untransmitted residual is ||vc||² minus the energy of
        the live payload (invalid slots carry 0.0), and likewise the mass.
        Under int8 error feedback vc was already rewritten to the residual
        and is the norm directly; mixed plans with int8 EF count only the
        deferred (non-i8) slots."""
        taps, mc, md, vc = st.taps, st.mc, st.md, st.vc
        tx = None
        if st.values is not None and self._mem is not None:
            if not self._int8_ef:
                tx = st.values.astype(jnp.float32)
            elif self._i8_slot_mask is not None:
                tx = jnp.where(jnp.asarray(self._i8_slot_mask), 0.0,
                               st.values.astype(jnp.float32))
        if st.sel_stats is None:
            sel = taps.empty_bucket_stats(len(self.buckets))
            wire = 0.0
        else:
            sel = st.sel_stats
            wire = float(self.wire_bytes_per_worker())
        if tx is not None:
            tx_energy = jnp.sum(tx ** 2)
            tx_abs = jnp.sum(jnp.abs(tx))
        if mc is None and md is None and vc is None:
            mom = res = mass = jnp.zeros((), jnp.float32)
        else:
            mom = jnp.sqrt(taps.l2(mc) ** 2 + taps.l2(md) ** 2)
            if tx is None:
                res = taps.l2(vc)
                mass = taps.l1(vc)
            else:
                res = jnp.sqrt(jnp.maximum(
                    jnp.sum(vc.astype(jnp.float32) ** 2) - tx_energy, 0.0))
                mass = jnp.maximum(taps.l1(vc) - tx_abs, 0.0)
        return taps.assemble_step_stats(
            grad_norm=st.grad_norm, momentum_norm=mom, residual_norm=res,
            residual_mass=mass, clip_delta=st.clip_delta,
            payload_elems=sel["payload_elems"],
            wire_bytes=jnp.asarray(wire, jnp.float32),
            selected_frac=sel["selected_frac"], threshold=sel["threshold"])

    # -------------------------------------------------------------- #
    # checkpoint-format parity (reference memory.py:79-88)           #
    # -------------------------------------------------------------- #

    def memory_full(self, mem: Dict) -> Dict:
        """Split memory -> canonical {momentums: [P], velocities: [P]}
        view, with any pending (deferred) transmit mask materialized —
        checkpoint/inspection time only, the hot path never builds it.
        The packed transmit record is ratio-independent (its word count
        never changes), so a pending mask survives warm-up engine rebuilds
        untouched — the next compensate applies it identically."""
        mc, vc = mem["momentums_c"], mem["velocities_c"]
        m = self._mem
        if m is not None and mc.shape[0] > 0:
            keep = kernels.keep_from_bits(mem["sent_bits"],
                                          mc.shape[0]).astype(vc.dtype)
            vc = vc * keep
            if m.momentum_masking:
                mc = mc * keep
        if "gossip_inbox" in mem:
            # pending neighbor mass is velocity-in-flight (the next
            # exchange folds it in after the mask — same order as here);
            # materializing it keeps the canonical view mass-conserving
            vc = vc + mem["gossip_inbox"].astype(vc.dtype)
        return {
            "momentums": jnp.concatenate([mc, mem["momentums_d"]]),
            "velocities": jnp.concatenate([vc, mem["velocities_d"]]),
        }

    def memory_state_dict(self, mem: Dict) -> Optional[Dict]:
        """Flat memory -> per-name {momentums, velocities} (the reference's
        checkpoint format, memory.py:79-80)."""
        if not mem:
            return None
        full = self.memory_full(mem)
        return {
            "momentums": self.layout.unflatten_named(full["momentums"],
                                                     keep_1d=True),
            "velocities": self.layout.unflatten_named(full["velocities"],
                                                      keep_1d=True),
        }

    def load_memory_state_dict(self, mem: Dict, saved: Optional[Dict]) -> Dict:
        """Per-name saved buffers -> flat memory, merging by name
        (reference memory.py:82-88). Gap slots stay zero."""
        if not mem or saved is None:
            return mem
        lay = self.layout
        T = self.T
        full = self.memory_full(mem)
        out = {}
        for key in ("momentums", "velocities"):
            flat = full[key]
            for n in lay.names:
                if n in saved[key]:
                    piece = jnp.asarray(saved[key][n]).reshape(-1)
                    flat = jax.lax.dynamic_update_slice(
                        flat, piece.astype(flat.dtype), (lay.offsets[n],))
            out[key + "_c"] = flat[:T]
            out[key + "_d"] = flat[T:]
        # loaded buffers are canonical (already masked): nothing pending
        out["sent_bits"] = jnp.zeros((kernels.num_sent_words(T) if T
                                      else 0,), jnp.int32)
        # gossip clock/ages ride through from the caller's memory; the
        # inbox stays empty — memory_full materialized any pending
        # neighbor mass into the canonical velocities at save time
        for k in ("gossip_clock", "gossip_age", "gossip_forced"):
            if k in mem:
                out[k] = mem[k]
        if "gossip_inbox" in mem:
            out["gossip_inbox"] = jnp.zeros_like(mem["gossip_inbox"])
        return out


class FlatDenseExchange:
    """Flat-path counterpart for the dense baseline compressors
    (``NoneCompressor``/``FP16Compressor``): the flat gradient buffer
    all-reduced, one psum per segment of the layout (``segments``)."""

    payload_size = 0
    #: the step may hand ``exchange`` the order in which the tensors'
    #: gradients become final (``grad_ready``, training/step.py)
    takes_grad_ready = True
    #: slots below which a piece of the buffer does not get a collective
    #: of its own (``segments``). At 2**20, 4 MiB of float32, VGG-16-BN's
    #: nine largest tensors go alone and the other 49 ride two collectives:
    #: eleven on four v5e chips, all but the last (0.04 ms) over before the
    #: backward pass is (PERF.md section 6, PR 28). No other size was read.
    MIN_SEGMENT = 1 << 20

    def __init__(self, compressor, layout: ParamLayout):
        self.c = compressor
        self.layout = layout

    def segments(self, grad_ready: Optional[Dict[str, int]] = None
                 ) -> List[List[Tuple[int, int]]]:
        """What each collective of the exchange carries, in the order the
        collectives are issued: lists of ``(start, stop)`` runs of the flat
        buffer that together tile it once. The layout's pieces are taken
        in the order their gradients become final (``grad_ready``: tensor
        name -> position in the backward pass; storage order without it).
        A piece of ``MIN_SEGMENT`` slots goes alone, so that no large
        tensor is copied to sit beside a small one; smaller pieces ride
        together and leave once they hold that much."""
        pieces = self.layout.pieces()
        if grad_ready is not None:
            # structural zeros wait for nothing and go last
            last = max(grad_ready.values())
            pieces.sort(key=lambda p: max((grad_ready[n] for n in p[2]),
                                          default=last))
        out, small, held = [], [], 0
        for lo, hi, _ in pieces:
            if hi - lo >= self.MIN_SEGMENT:
                out.append([(lo, hi)])
                continue
            small.append((lo, hi))
            held += hi - lo
            if held >= self.MIN_SEGMENT:
                out.append(small)
                small, held = [], 0
        if small:
            out.append(small)
        return out

    def _psum_segments(self, wire, axis_name, world_size, grad_ready):
        """``psum(wire)`` as one collective per segment, joined back in
        storage order. A segment's collective reads the gradients of its
        own tensors only, so it can run beside what is left of the backward
        pass. Each operand is tied to the result of the collective before
        it by an optimization barrier: until XLA expands the barriers, late
        in its pipeline, that keeps its combiner from re-joining the
        collectives, and its scheduler then keeps them in the order they
        were issued. Over one worker there is nothing to cut: the psum is
        elided."""
        segments = self.segments(grad_ready)
        if world_size == 1 or len(segments) < 2:  # dgclint: ok[tracer-branch] — the world size and the layout's cut are static
            _count_collective("psum", wire, axis_name, self, segment=0)
            return jax.lax.psum(wire, axis_name)
        reduced, before = {}, None
        for i, runs in enumerate(segments):
            operand = jnp.concatenate([wire[lo:hi] for lo, hi in runs])
            if before is not None:
                operand, _ = jax.lax.optimization_barrier((operand, before))
            _count_collective("psum", operand, axis_name, self, segment=i)
            before = jax.lax.psum(operand, axis_name)
            at = 0
            for lo, hi in runs:
                reduced[lo] = before[at:at + hi - lo]
                at += hi - lo
        return jnp.concatenate([reduced[lo] for lo in sorted(reduced)])

    def init_memory(self) -> Dict:
        return {}

    def exchange(self, flat_grad, mem, key, axis_name, world_size,
                 op: str = "average", local_axis: Optional[str] = None,
                 local_size: int = 1, telemetry: bool = False,
                 health_out: Optional[Dict] = None, send_frac=None,
                 grad_ready: Optional[Dict[str, int]] = None):
        # health_out/send_frac accepted for signature parity with
        # FlatDGCEngine; the dense psum has no sparse payload to checksum
        # and no per-worker quota for the adaptive policy to shrink
        if telemetry:
            # dense-baseline taps: grad norm only; no sparse payload, no
            # error-feedback state (wire_bytes is the SPARSE wire metric
            # and stays 0 here — the dense psum is the baseline itself)
            from dgc_tpu.telemetry import taps
            stats = taps.assemble_step_stats(
                grad_norm=taps.l2(flat_grad),
                momentum_norm=jnp.zeros((), jnp.float32),
                residual_norm=jnp.zeros((), jnp.float32),
                residual_mass=jnp.zeros((), jnp.float32),
                clip_delta=jnp.zeros((), jnp.float32),
                wire_bytes=jnp.zeros((), jnp.float32),
                **taps.empty_bucket_stats(0))
        if op == "adasum":
            if local_axis is not None and local_size > 1:
                # node-aggregated Adasum: the node mean is the participant
                with _trace.phase("dense"):
                    _count_collective("psum", flat_grad, local_axis, self)
                    flat_grad = (jax.lax.psum(flat_grad, local_axis)
                                 / local_size)
            # full precision: fp16 dot/norm accumulations would overflow
            from dgc_tpu.optim.adasum import adasum_allreduce
            out = adasum_allreduce(flat_grad, axis_name, world_size)
            return (out, mem, stats) if telemetry else (out, mem)
        hier = local_axis is not None and local_size > 1
        if hier:
            # full-precision ICI tier first; the (optional fp16) wire cast
            # applies to the cross-host link only, like the DGC engine.
            # Average divides BEFORE the wire cast — an undivided node sum
            # on an fp16 wire would overflow local_size x earlier.
            with _trace.phase("dense"):
                _count_collective("psum", flat_grad, local_axis, self)
                flat_grad = jax.lax.psum(flat_grad, local_axis)
            if op == "average":
                flat_grad = flat_grad / local_size
        wire = self.c._wire(flat_grad)
        # the gradient all-reduce DGC exists to replace: its own phase, as
        # the engine's dense tail has, not filed under the step's update
        with _trace.phase("dense"):
            total = self._psum_segments(wire, axis_name, world_size,
                                        grad_ready)
        total = self.c._unwire(total, flat_grad.dtype)
        out = (total / world_size if op == "average" else total).astype(
            flat_grad.dtype)
        return (out, mem, stats) if telemetry else (out, mem)

    def memory_state_dict(self, mem):
        return None

    def load_memory_state_dict(self, mem, saved):
        return mem

"""Pallas TPU kernels for the compression hot path.

SURVEY.md §7 item 6: the reference leans on fused CUDA kernels for its hot
ops (`topk`, `index_put_`, elementwise momentum updates — dgc/memory.py:50-77,
dgc/compression.py:109-153); the TPU-native equivalents are Pallas kernels
over the flat HBM-resident buffers of ``dgc_tpu.compression.flat``.

Shipped kernels (each with a jnp reference implementation it must match
bitwise — tested in tests/test_kernels.py):

* :func:`fused_compensate` — momentum correction + local accumulation
  (``mmt = m*mmt + g; vec += mmt``, nesterov variant) in ONE pass over HBM:
  reads (grad, mmt, vec), writes (mmt', vec') tile by tile through VMEM.
  The jnp version relies on XLA fusing 2-3 elementwise ops; the kernel makes
  the single-pass guarantee explicit and holds for any [P] size via grid
  chunking.

* :func:`ladder_counts` — the threshold-adaptation counts: for a threshold
  ladder ``thr * lb^i`` (i = 0..L), count per row how many elements pass each
  level, in ONE pass over the row view. The reference's adaptation loop
  (compression.py:128-149) re-scans the tensor once per iteration (≤ 10
  scans); counts for the whole ladder make the final threshold a closed-form
  pick (see ``flat.FlatDGCEngine``).

* :func:`dgc_forward_rows` / :func:`dgc_apply_rows` — the two-megakernel
  step (opt-in via ``DGCCompressor(megakernel=True)``): the whole
  compress side and the whole apply side each collapse into ONE Pallas
  pass::

      forward (one pass per eligible bucket, grid = bucket rows)
          HBM grad/mmt/vec row ──DMA──▶ VMEM
            └▶ bit-expand keep mask (packed transmit record)
               └▶ masked error-feedback compensate + momentum correction
                  └▶ k-round in-VMEM partial selection
                     (threshold → select → pack, values never respill)
          ──DMA──▶ HBM mmt' / vec' + (scores, values, cols) payload

      apply (one pass over the flat [T] buffer, grid = chunk pages)
          sorted pairs, one block a page ──BlockSpec by prefetch──▶ VMEM
            └▶ 128 pairs a window → one-hot row / lane factors
               └▶ MXU products: values (three bf16 parts) + sent bits
                  into the VMEM-resident output chunk, written once
          ──DMA──▶ HBM dense grad + packed transmit record

  Double-buffered streaming: both kernels run their HBM operands through
  the Pallas grid pipeline (the next block's DMA issues while the current
  block computes; the apply pass additionally scalar-prefetches its
  page→chunk and page→block maps so the output-block revisit pattern and
  the pairs' blocks are known ahead of the DMAs), so per-bucket cost is
  bandwidth-bound rather than launch-bound. Between them the unfused path's intermediate HBM
  round-trips (compensated velocity re-read, candidate buffers, staged
  importance) disappear.

Kernels run compiled on TPU and in interpreter mode elsewhere (CPU tests);
``use_pallas()`` picks automatically.
"""

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# phase markers (telemetry.trace): applied only to the INLINE-traced
# entrypoints below. The module-level jitted kernels
# (fused_compensate_bits[_cands]) must NOT carry a marker inside their
# jit — the nested-jit jaxpr cache doesn't key on the trace flag, so a
# marker baked there would leak across trace-on/off builds and break the
# trace-off-compiles-away byte-identity contract. Their call sites in
# compression/flat.py wrap them in phase("compensate") instead; the
# caller's name stack prefixes nested-jit op names, so attribution sees
# them either way. Every ``pl.pallas_call`` passes ``name=`` (its jitted
# kernel's own name, unique per site): the kernel's device events then
# carry that name whatever scope calls it, and being unconditional it is
# the same in trace-on and trace-off builds.
from dgc_tpu.telemetry import trace as _trace

__all__ = ["fused_compensate", "fused_compensate_reference",
           "fused_compensate_masked", "fused_compensate_masked_reference",
           "fused_compensate_bits", "fused_compensate_bits_reference",
           "fused_compensate_bits_cands",
           "fused_compensate_bits_cands_reference",
           "keep_from_sent", "pack_sent_bits", "keep_from_bits",
           "num_sent_words", "realign_bits",
           "ladder_counts", "ladder_counts_reference",
           "topk_rows", "topk_rows_reference",
           "select_pack_rows", "select_pack_rows_reference",
           "seg_top2_candidates", "seg_top2_reference",
           "seg_top2_eligible", "opaque_view", "use_pallas",
           "payload_apply_bits", "payload_apply_bits_reference",
           "payload_update_bits",
           "dgc_forward_rows", "dgc_forward_rows_reference",
           "dgc_apply_rows", "dgc_apply_rows_reference", "vtag"]

_LANE = 128          # TPU lane width
_SUBLANE = 8         # f32 sublane
#: rows of 128 lanes per compensate grid step (1 MB/buffer, 6 MB VMEM
#: across the 6 streams). Fewer, larger DMAs: ~1 ms/step faster than
#: 512-row chunks in isolation but only ~0.1 ms in the paired full-step
#: A/B at ResNet-50 (the scheduler already overlaps the smaller DMAs);
#: kept at 2048 for the consistent small win
_CHUNK_ROWS = 2048


def use_pallas() -> bool:
    """Compiled Pallas only on TPU backends; interpret elsewhere."""
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return not use_pallas()


def vtag(x, name: str):
    """Dataflow anchor for the dgcver verifier (analysis/verify.py).

    Wraps ``jax.ad_checkpoint.checkpoint_name`` — an identity ``name``
    primitive that survives into the jaxpr (where the verifier's taint
    passes seed/sink on it) but lowers to ZERO HLO ops, so every
    byte-identity and op-count contract is unaffected. Applied leafwise
    so pytrees tag transparently; non-array leaves pass through."""
    import jax.ad_checkpoint as _adc

    def leaf(v):
        try:
            return _adc.checkpoint_name(v, name)
        except Exception:
            return v
    return jax.tree_util.tree_map(leaf, x)


# ------------------------------------------------------------------ #
# fused momentum-correction compensate                               #
# ------------------------------------------------------------------ #

def fused_compensate_reference(grad, mmt, vec, momentum: float,
                               nesterov: bool):
    """jnp reference (the algorithm contract, reference memory.py:50-63).

    The state buffers (mmt, vec) may be a NARROWER dtype than the gradient
    (the opt-in bfloat16 error-feedback state, ``DGCSGDMemory(dtype=...)``):
    math always runs in the gradient dtype, with exactly one
    round-to-nearest down-cast per output — when dtypes match the casts
    are no-ops and the function is bitwise the original."""
    sdt = mmt.dtype
    mmt = mmt.astype(grad.dtype)
    vec = vec.astype(grad.dtype)
    if nesterov:
        mmt = (mmt + grad) * momentum
        vec = vec + mmt + grad
    else:
        mmt = momentum * mmt + grad
        vec = vec + mmt
    return mmt.astype(sdt), vec.astype(sdt)


def _compensate_kernel(g_ref, m_ref, v_ref, om_ref, ov_ref, *,
                       momentum: float, nesterov: bool):
    g = g_ref[:]
    m0 = m_ref[:].astype(g.dtype)
    v0 = v_ref[:].astype(g.dtype)
    if nesterov:
        m = (m0 + g) * momentum
        ov_ref[:] = (v0 + m + g).astype(ov_ref.dtype)
    else:
        m = momentum * m0 + g
        ov_ref[:] = (v0 + m).astype(ov_ref.dtype)
    om_ref[:] = m.astype(om_ref.dtype)


@functools.partial(jax.jit, static_argnames=("momentum", "nesterov"))
def fused_compensate(grad: jax.Array, mmt: jax.Array, vec: jax.Array,
                     momentum: float, nesterov: bool = False
                     ) -> Tuple[jax.Array, jax.Array]:
    """Single-pass ``(mmt', vec')`` over flat [P] buffers.

    Buffers whose length is a multiple of 16*128 (the ``ParamLayout``
    alignment — 16 sublanes so the optional 2-byte state dtype tiles
    cleanly too) run copy-free: reshape to [rows, 128] is a view, the
    grid's ragged last block is masked by Mosaic. Other lengths (direct
    callers, tests) pay one pad copy. ``mmt``/``vec`` may be a narrower
    dtype than ``grad`` (bf16 error-feedback state): math runs in the
    gradient dtype with one rounding per output."""
    n = grad.shape[0]
    # any sub-4-byte ref needs the 16-sublane bf16 tile granularity
    sub = _SUBLANE * (2 if min(grad.dtype.itemsize, mmt.dtype.itemsize,
                               vec.dtype.itemsize) < 4 else 1)
    pad = (-n) % (sub * _LANE)
    if pad:
        grad = jnp.concatenate([grad, jnp.zeros((pad,), grad.dtype)])
        mmt = jnp.concatenate([mmt, jnp.zeros((pad,), mmt.dtype)])
        vec = jnp.concatenate([vec, jnp.zeros((pad,), vec.dtype)])
    rows = (n + pad) // _LANE
    shape2d = (rows, _LANE)
    g2, m2, v2 = (x.reshape(shape2d) for x in (grad, mmt, vec))

    block_rows = min(_CHUNK_ROWS, rows)
    grid = pl.cdiv(rows, block_rows)
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    om, ov = pl.pallas_call(
        functools.partial(_compensate_kernel, momentum=momentum,
                          nesterov=nesterov),
        grid=(grid,),
        out_shape=(jax.ShapeDtypeStruct(shape2d, mmt.dtype),
                   jax.ShapeDtypeStruct(shape2d, vec.dtype)),
        in_specs=[spec, spec, spec],
        out_specs=(spec, spec),
        # in-place state update (see fused_compensate_bits): spares two
        # [T] output allocations + the surrounding carry copies —
        # measured -3.6 ms/step at VGG, -0.5 at ResNet-50 (paired A/B)
        input_output_aliases={1: 0, 2: 1},
        interpret=_interpret(),
        name="fused_compensate",
    )(g2, m2, v2)
    om, ov = om.reshape(-1), ov.reshape(-1)
    return (om[:n], ov[:n]) if pad else (om, ov)


def keep_from_sent(sent):
    """Transmit-count -> multiplicative keep mask: 1.0 where the coordinate
    was NOT transmitted last step (count 0), else 0.0. Used by the v0.3
    full-[T] count-vector record (:func:`fused_compensate_masked`, kept
    as the tested building block); the engine now ships the bit-packed
    record (:func:`pack_sent_bits` / :func:`fused_compensate_bits`)."""
    return (sent == 0).astype(sent.dtype)


def fused_compensate_masked_reference(grad, mmt, vec, sent, momentum: float,
                                      nesterov: bool, momentum_masking: bool):
    """jnp reference: apply the previous step's transmit mask on READ, then
    compensate. Bitwise identical to masking eagerly after the previous
    sparsify (multiply is deterministic), but the mask multiply rides the
    compensate pass instead of costing its own full-buffer write+read
    (reference order: memory.update zeros transmitted coords, memory.py:
    72-77; the next compensate reads them, memory.py:50-63). ``sent`` is
    the transmit COUNT vector (0 = keep), see :func:`keep_from_sent`.

    With a narrower state dtype (bf16 error feedback) the mask multiply
    runs in the GRADIENT dtype after the up-cast — multiplying by exactly
    1.0/0.0 is value-preserving either way, so this matches the
    per-tensor path's ``where(sent, 0, state)`` in state dtype."""
    sdt = mmt.dtype
    kf = keep_from_sent(sent).astype(grad.dtype)
    m_in = mmt.astype(grad.dtype)
    if momentum_masking:
        m_in = m_in * kf
    om, ov = fused_compensate_reference(grad, m_in,
                                        vec.astype(grad.dtype) * kf,
                                        momentum, nesterov)
    return om.astype(sdt), ov.astype(sdt)


def _compensate_masked_kernel(g_ref, m_ref, v_ref, k_ref, om_ref, ov_ref, *,
                              momentum: float, nesterov: bool,
                              momentum_masking: bool):
    g = g_ref[:]
    # sent is the f32 transmit count (sub-word masks are NOT used: their
    # scatter lowers to a serial while-loop on v5e, see
    # FlatDGCEngine.init_memory); 0 means keep
    keep = (k_ref[:] == 0).astype(g.dtype)
    m0 = m_ref[:].astype(g.dtype)
    if momentum_masking:
        m0 = m0 * keep
    v0 = v_ref[:].astype(g.dtype) * keep
    if nesterov:
        m = (m0 + g) * momentum
        ov_ref[:] = (v0 + m + g).astype(ov_ref.dtype)
    else:
        m = momentum * m0 + g
        ov_ref[:] = (v0 + m).astype(ov_ref.dtype)
    om_ref[:] = m.astype(om_ref.dtype)


@functools.partial(jax.jit, static_argnames=("momentum", "nesterov",
                                             "momentum_masking"))
def fused_compensate_masked(grad: jax.Array, mmt: jax.Array, vec: jax.Array,
                            sent: jax.Array, momentum: float,
                            nesterov: bool = False,
                            momentum_masking: bool = True
                            ) -> Tuple[jax.Array, jax.Array]:
    """Single-pass mask-on-read + compensate over flat buffers: reads
    (grad, mmt, vec, sent count), writes (mmt', vec') — one extra input
    stream vs :func:`fused_compensate` instead of a separate masked-buffer
    materialization (measured 0.83 ms/step of full-[T] traffic at
    ResNet-50 scale on v5e). ``sent`` is the transmit-count vector
    (:func:`keep_from_sent`; 0 = keep), f32: sub-word scatters lower to a
    serial while-loop on v5e. ``mmt``/``vec`` may be a narrower dtype
    than ``grad`` (bf16 error-feedback state)."""
    n = grad.shape[0]
    # any sub-4-byte ref needs the 16-sublane bf16 tile granularity
    sub = _SUBLANE * (2 if min(grad.dtype.itemsize, mmt.dtype.itemsize,
                               vec.dtype.itemsize) < 4 else 1)
    pad = (-n) % (sub * _LANE)
    if pad:
        grad, mmt, vec = (jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
                          for x in (grad, mmt, vec))
        sent = jnp.concatenate([sent, jnp.zeros((pad,), sent.dtype)])
    rows = (n + pad) // _LANE
    shape2d = (rows, _LANE)
    g2, m2, v2, k2 = (x.reshape(shape2d) for x in (grad, mmt, vec, sent))

    block_rows = min(_CHUNK_ROWS, rows)
    grid = pl.cdiv(rows, block_rows)
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    om, ov = pl.pallas_call(
        functools.partial(_compensate_masked_kernel, momentum=momentum,
                          nesterov=nesterov,
                          momentum_masking=momentum_masking),
        grid=(grid,),
        out_shape=(jax.ShapeDtypeStruct(shape2d, mmt.dtype),
                   jax.ShapeDtypeStruct(shape2d, vec.dtype)),
        in_specs=[spec, spec, spec, spec],
        out_specs=(spec, spec),
        # in-place state update (see fused_compensate_bits)
        input_output_aliases={1: 0, 2: 1},
        interpret=_interpret(),
        name="fused_compensate_masked",
    )(g2, m2, v2, k2)
    om, ov = om.reshape(-1), ov.reshape(-1)
    return (om[:n], ov[:n]) if pad else (om, ov)


# ------------------------------------------------------------------ #
# bit-packed transmit record                                         #
# ------------------------------------------------------------------ #

#: flat elements covered by one 128-lane row of packed words: 32 rows of
#: the [rows, 128] f32 view share one word row (bit = row % 32)
_BITS_GROUP = 32 * _LANE


def num_sent_words(total: int) -> int:
    """Length of the packed transmit record for a [total] buffer:
    ceil(total / 4096) * 128 int32 words (total must be lane-aligned;
    the layout's _ALIGN guarantees it). When total % 4096 == 2048 the
    last word group covers 16 real rows only — the phantom rows' bits
    are simply never set, so they read keep=1."""
    assert total % _LANE == 0, total  # engine T is _ALIGN-aligned
    return -(-total // _BITS_GROUP) * _LANE


@_trace.phased("pack")
def pack_sent_bits(indices: jax.Array, total: int,
                   sentinel=None) -> jax.Array:
    """Transmit indices -> packed one-bit-per-coordinate record.

    Word layout matches the compensate kernel's in-VMEM expansion: flat
    position p (of the [rows, 128] row-major view: row = p // 128,
    lane = p % 128) maps to word ``(p // 4096) * 128 + (p % 128)``, bit
    ``(p // 128) % 32`` — i.e. word (a, l) of the [W // 128, 128] word
    view holds rows a*32 .. a*32+31 of lane l. The record replaces the
    v0.3 full-[T] f32 count vector: 32x less HBM on the compensate
    kernel's mask stream, the per-step zero-init, and the state carried
    between steps (docs/RESULTS.md lists the measured costs).

    ``indices`` must be unique apart from ``sentinel`` entries (padded
    payload slots), which are dropped — the engine's fixed-size selection
    guarantees this (distinct per-row top-k positions, disjoint rows);
    duplicate REAL indices would carry into a neighboring row's bit,
    unlike the old count vector which tolerated them.
    """
    W = num_sent_words(total)
    # W must fit int32 for the scatter (total < 2**36 slots = 256 GiB of
    # f32 parameters — beyond any current HBM; the int64-wire layouts
    # stay far under this)
    assert W < 2 ** 31, total
    idx = indices
    w = (idx >> 12) * 128 + (idx & 127)
    bit = ((idx >> 7) & 31).astype(jnp.int32)
    if sentinel is not None:
        # padded slots all carry the sentinel index: their repeated adds
        # would carry across bits, so route them out of bounds and drop
        w = jnp.where(idx == sentinel, W, w)
    return jnp.zeros((W,), jnp.int32).at[w.astype(jnp.int32)].add(
        jnp.left_shift(jnp.int32(1), bit), mode="drop")


def keep_from_bits(bits: jax.Array, total: int) -> jax.Array:
    """Packed transmit record -> multiplicative keep mask [total] (1.0 =
    not transmitted). jnp reference of the kernel's in-VMEM expansion;
    used off the hot path (checkpoint materialization, the dense-branch
    pending-mask flush)."""
    W = bits.shape[0]
    assert W == num_sent_words(total), (W, total)
    b3 = bits.reshape(-1, 1, _LANE)                       # [A, 1, 128]
    m = jnp.arange(32, dtype=jnp.int32)[None, :, None]    # [1, 32, 1]
    keep = (jnp.right_shift(b3, m) & 1) == 0              # [A, 32, 128]
    return keep.reshape(-1)[:total].astype(jnp.float32)


def realign_bits(bits: jax.Array, base: int, n: int) -> jax.Array:
    """Window the packed transmit record onto region ``[base, base+n)``:
    returns ``num_sent_words(n)`` words such that
    ``keep_from_bits(out, n) == keep_from_bits(bits, total)[base:base+n]``.

    The word layout ties bit position to ``row % 32`` of the [_, 128]
    row view, so a region whose start row ``S = base // 128`` is not a
    multiple of 32 needs a funnel shift across adjacent word groups:
    ``out[j] = (w[q+j] >>> sh) | (w[q+j+1] << (32-sh))`` with
    ``q = S // 32``, ``sh = S % 32`` (logical shifts, computed in
    uint32). ``base``/``n`` are static and lane-aligned (every bucket
    base and every span the engine builds is — cols are multiples of
    128); group-aligned regions reduce to a pure slice."""
    assert base % _LANE == 0 and n % _LANE == 0, (base, n)
    W = num_sent_words(n)
    Wr = W // _LANE                       # word groups of the window
    S = base // _LANE                     # region start row
    q, sh = S // 32, S % 32
    w2 = bits.reshape(-1, _LANE)
    need = q + Wr + 1 - w2.shape[0]       # one zero guard group for hi
    if need > 0:
        w2 = jnp.concatenate(
            [w2, jnp.zeros((need, _LANE), w2.dtype)])
    if sh == 0:
        return w2[q:q + Wr].reshape(-1)
    u = w2.astype(jnp.uint32)
    lo = u[q:q + Wr]
    hi = u[q + 1:q + Wr + 1]
    out = (lo >> jnp.uint32(sh)) | (hi << jnp.uint32(32 - sh))
    return out.astype(jnp.int32).reshape(-1)


def _realign_bits_rows(bits: jax.Array, base: int, R: int,
                       nblk: int) -> jax.Array:
    """Per-bucket-row transmit-record windows for the forward megakernel:
    row ``r`` of a bucket at ``base`` with ``nblk`` 128-lane blocks per
    row starts at flat row ``S_r = base//128 + r*nblk`` — each needs its
    own funnel shift (:func:`realign_bits` semantics, vectorized over
    rows with host-static shift amounts). Returns [R, ceil(nblk/32), 128]
    int32; word ``j`` of row ``r`` covers the row's local 128-lane blocks
    ``32j .. 32j+31`` (bit = local block % 32)."""
    Wr = -(-nblk // 32)
    S = base // _LANE + np.arange(R, dtype=np.int64) * nblk
    q = S // 32
    sh = (S % 32).astype(np.uint32)
    w2 = bits.reshape(-1, _LANE)
    need = int(q.max()) + Wr + 1 - w2.shape[0]
    if need > 0:
        w2 = jnp.concatenate(
            [w2, jnp.zeros((need, _LANE), w2.dtype)])
    u = w2.astype(jnp.uint32)
    gidx = jnp.asarray(q[:, None] + np.arange(Wr)[None, :], jnp.int32)
    lo = u[gidx]                                      # [R, Wr, 128]
    hi = u[gidx + 1]
    shv = jnp.asarray(sh)[:, None, None]
    # shift-by-32 is undefined: rows with sh == 0 take lo verbatim and
    # the dead (32 - sh) lane shifts by 0 instead
    shl = jnp.asarray(
        np.where(sh == 0, 0, 32 - sh).astype(np.uint32))[:, None, None]
    out = jnp.where(shv == jnp.uint32(0), lo, (lo >> shv) | (hi << shl))
    return out.astype(jnp.int32)


def fused_compensate_bits_reference(grad, mmt, vec, bits, momentum: float,
                                    nesterov: bool, momentum_masking: bool):
    """jnp reference: unpack the bit record to a keep mask, then compensate
    — the mask multiply runs in the GRADIENT dtype exactly like
    :func:`fused_compensate_masked_reference` (multiplying by 1.0/0.0 is
    value-preserving in any dtype, so this is bitwise the per-tensor
    path's eager ``where(sent, 0, state)``)."""
    sdt = mmt.dtype
    kf = keep_from_bits(bits, grad.shape[0]).astype(grad.dtype)
    m_in = mmt.astype(grad.dtype)
    if momentum_masking:
        m_in = m_in * kf
    om, ov = fused_compensate_reference(grad, m_in,
                                        vec.astype(grad.dtype) * kf,
                                        momentum, nesterov)
    return om.astype(sdt), ov.astype(sdt)


def _compensate_math(g, m0, v0, keep, *, momentum: float, nesterov: bool,
                     momentum_masking: bool):
    """The masked-compensate arithmetic every bit-masked kernel shares:
    mask-on-read then momentum correction, math in the GRADIENT dtype.
    ONE source of truth so the plain kernel, the fused candidates
    kernel, and the forward megakernel cannot drift (their state outputs
    must stay bitwise identical — the fused forms' contract). Returns
    ``(mmt', vec')`` in the gradient dtype."""
    m0 = m0.astype(g.dtype)
    if momentum_masking:
        m0 = m0 * keep
    v0 = v0.astype(g.dtype) * keep
    if nesterov:
        m = (m0 + g) * momentum
        ov = v0 + m + g
    else:
        m = momentum * m0 + g
        ov = v0 + m
    return m, ov


def _bits_compensate_core(g_ref, m_ref, v_ref, b_ref, *, momentum: float,
                          nesterov: bool, momentum_masking: bool):
    """Shared VMEM body of the bit-masked compensate kernels: in-VMEM
    bit expansion + mask-on-read + momentum correction
    (:func:`_compensate_math`). Returns ``(mmt', vec')`` in the gradient
    dtype.

    Bit expansion: word (a, l) -> rows a*32..a*32+31 of lane l. The
    broadcast+reshape is sublane-local (the lane dim never moves),
    which Mosaic legalizes; a jnp.repeat formulation and a 4-way-where
    word select over a [rows, 4] word layout both failed to lower
    (docs/RESULTS.md round-3 negative results)."""
    g = g_ref[:]
    rows = g.shape[0]
    b = b_ref[:]                                          # [rows//32, 128]
    exp = jnp.broadcast_to(b[:, None, :], (rows // 32, 32, _LANE)).reshape(
        rows, _LANE)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 0)
    keep = (((exp >> (r & 31)) & 1) == 0).astype(g.dtype)
    return _compensate_math(g, m_ref[:], v_ref[:], keep, momentum=momentum,
                            nesterov=nesterov,
                            momentum_masking=momentum_masking)


def _compensate_bits_kernel(g_ref, m_ref, v_ref, b_ref, om_ref, ov_ref, *,
                            momentum, nesterov, momentum_masking):
    m, ov = _bits_compensate_core(g_ref, m_ref, v_ref, b_ref,
                                  momentum=momentum, nesterov=nesterov,
                                  momentum_masking=momentum_masking)
    ov_ref[:] = ov.astype(ov_ref.dtype)
    om_ref[:] = m.astype(om_ref.dtype)


@functools.partial(jax.jit, static_argnames=("momentum", "nesterov",
                                             "momentum_masking"))
def fused_compensate_bits(grad: jax.Array, mmt: jax.Array, vec: jax.Array,
                          bits: jax.Array, momentum: float,
                          nesterov: bool = False,
                          momentum_masking: bool = True
                          ) -> Tuple[jax.Array, jax.Array]:
    """Single-pass mask-on-read + compensate with the transmit record
    bit-PACKED: reads (grad, mmt, vec) plus a 32x-smaller int32 word
    stream instead of the f32 count vector of
    :func:`fused_compensate_masked` — the expansion happens in VMEM
    (measured bitwise-equal and slightly faster on v5e; the real win is
    the removed [T] zero-init + scatter and the 32x smaller carried
    state, scripts/proto_bitpack.py). ``bits`` must come from
    :func:`pack_sent_bits` (same word layout). ``mmt``/``vec`` may be a
    narrower dtype than ``grad`` (bf16 error-feedback state).

    Alignment: the data buffers pad only to the usual sublane tile (like
    the other compensate kernels) — NOT to the 4096-element word group.
    The engine's T is frequently ``≡ 2048 (mod 4096)`` (the _ALIGN
    granularity), and padding there would copy all three [T] streams
    every step (~1 ms at ResNet-50, ~5 ms at VGG — the first integration
    measured exactly that regression). Instead the grid's ragged last
    block is masked by Mosaic; the word array always covers
    ``ceil(n / 4096)`` groups, so half-group tails read bits that are
    never set (keep)."""
    n = grad.shape[0]
    assert bits.shape[0] == num_sent_words(n), (bits.shape, n)
    # any sub-4-byte ref needs the 16-sublane bf16 tile granularity
    sub = _SUBLANE * (2 if min(grad.dtype.itemsize, mmt.dtype.itemsize,
                               vec.dtype.itemsize) < 4 else 1)
    pad = (-n) % (sub * _LANE)
    if pad:
        grad, mmt, vec = (jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
                          for x in (grad, mmt, vec))
    rows = (n + pad) // _LANE
    shape2d = (rows, _LANE)
    g2, m2, v2 = (x.reshape(shape2d) for x in (grad, mmt, vec))
    b2 = bits.reshape(-1, _LANE)       # [ceil(n/4096), 128] word groups

    # the in-kernel expansion needs a whole number of 32-row word groups
    # per block; a block may overhang the array (ragged masking)
    block_rows = min(_CHUNK_ROWS, _round_up(rows, 32))
    grid = pl.cdiv(rows, block_rows)
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((block_rows // 32, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    om, ov = pl.pallas_call(
        functools.partial(_compensate_bits_kernel, momentum=momentum,
                          nesterov=nesterov,
                          momentum_masking=momentum_masking),
        grid=(grid,),
        out_shape=(jax.ShapeDtypeStruct(shape2d, mmt.dtype),
                   jax.ShapeDtypeStruct(shape2d, vec.dtype)),
        in_specs=[spec, spec, spec, bspec],
        out_specs=(spec, spec),
        # in-place state update: mmt/vec have no consumer after this
        # call (the returned buffers replace them), so aliasing spares
        # two [T] output allocations and the copies the surrounding
        # carry otherwise pays
        input_output_aliases={1: 0, 2: 1},
        interpret=_interpret(),
        name="fused_compensate_bits",
    )(g2, m2, v2, b2)
    om, ov = om.reshape(-1), ov.reshape(-1)
    return (om[:n], ov[:n]) if pad else (om, ov)


# ------------------------------------------------------------------ #
# threshold-ladder counts                                            #
# ------------------------------------------------------------------ #

def ladder_counts_reference(imp_rows: jax.Array, thr: jax.Array,
                            lower_bound: float, levels: int) -> jax.Array:
    """jnp reference: ``counts[r, i] = sum(imp_rows[r] >= thr[r] * lb**i)``.

    ``imp_rows`` is the padded [R, maxN] row view (padding = -1, never
    counted since thresholds are >= 0). One compare+reduce per level (XLA
    fuses the sibling reductions over the shared read) — no [R, maxN, L]
    broadcast, so memory stays O(R * maxN)."""
    cols = [jnp.sum(imp_rows >= (lower_bound ** i) * thr[:, None], axis=1,
                    dtype=jnp.int32) for i in range(levels)]
    return jnp.stack(cols, axis=1)                        # [R, L]


#: column chunk per grid step: 8 rows x 128K cols x 4 B = 4 MB VMEM
_LADDER_COL_CHUNK = 128 * 1024


def ladder_cols(max_n: int) -> int:
    """Padded row width the ladder kernel requires: lane-aligned, and a
    multiple of the column chunk once chunking kicks in (ragged column
    blocks would read unspecified values into the counts). The engine's
    layout bakes this width into its bucket tiles so columns never need a
    device-side pad; ROWS are deliberately unpadded in storage (padding
    them would inflate every persistent buffer, flat._BucketGeom) and pay
    one small in-trace pad here instead."""
    cols = _round_up(max_n, _LANE)
    if cols > _LADDER_COL_CHUNK:
        cols = _round_up(cols, _LADDER_COL_CHUNK)
    return cols


def _round_up(n: int, align: int) -> int:
    return -(-n // align) * align


def _ladder_kernel(imp_ref, thr_ref, out_ref, *, lower_bound, levels):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _():
        out_ref[:] = jnp.zeros_like(out_ref)

    imp = imp_ref[:]                                      # [8, chunk]
    t = thr_ref[:]                                        # [8, 1]
    partial = jnp.stack(
        [jnp.sum((imp >= (lower_bound ** i) * t).astype(jnp.int32), axis=1)
         for i in range(levels)], axis=1)                 # [8, L]
    lane = jax.lax.broadcasted_iota(jnp.int32, (8, _LANE), 1)
    padded = jnp.where(lane < levels,
                       jnp.pad(partial, ((0, 0), (0, _LANE - levels))),
                       0)
    out_ref[:] = out_ref[:] + padded


@functools.partial(jax.jit, static_argnames=("lower_bound", "levels"))
def ladder_counts(imp_rows: jax.Array, thr: jax.Array, lower_bound: float,
                  levels: int) -> jax.Array:
    """Per-row pass counts for the whole threshold ladder, one HBM read.

    Grid: (row blocks of 8) x (column chunks); the [8, 128]-int32 output
    block is revisited across column chunks and accumulated. Inputs that
    are not (8, ladder_cols)-aligned pay one in-trace pad copy; the
    engine's bucket views are column-aligned by construction but
    deliberately row-unpadded (see flat._BucketGeom), so adaptive buckets
    pay the small row pad here each step rather than inflating every
    persistent buffer."""
    assert levels <= _LANE
    R, maxN = imp_rows.shape
    rpad = (-R) % _SUBLANE
    cpad = ladder_cols(maxN) - maxN
    if rpad or cpad:
        imp_rows = jnp.pad(imp_rows, ((0, rpad), (0, cpad)),
                           constant_values=-1.0)
    if rpad:
        thr = jnp.pad(thr, (0, rpad))
    R8, cols = R + rpad, maxN + cpad
    chunk = min(_LADDER_COL_CHUNK, cols)
    out = pl.pallas_call(
        functools.partial(_ladder_kernel, lower_bound=lower_bound,
                          levels=levels),
        grid=(R8 // _SUBLANE, cols // chunk),
        out_shape=jax.ShapeDtypeStruct((R8, _LANE), jnp.int32),
        in_specs=[
            pl.BlockSpec((_SUBLANE, chunk), lambda r, c: (r, c),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((_SUBLANE, 1), lambda r, c: (r, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((_SUBLANE, _LANE), lambda r, c: (r, 0),
                               memory_space=pltpu.VMEM),
        interpret=_interpret(),
        name="ladder_counts",
    )(imp_rows, thr.reshape(-1, 1))
    return out[:R, :levels]


# ------------------------------------------------------------------ #
# per-row top-k by iterative max extraction                          #
# ------------------------------------------------------------------ #

def topk_rows_reference(x: jax.Array, k: int):
    """jnp reference: ``jax.lax.top_k`` per row (values desc, ties by first
    occurrence)."""
    return jax.lax.top_k(x, k)


#: largest [rows, cols] f32 input block the top-k kernel keeps VMEM-resident
#: (same budget the ladder kernel's column chunk uses)
_TOPK_VMEM_BYTES = 4 * 1024 * 1024


def _topk_kernel(x_ref, v_ref, i_ref, *, k, cols):
    x = x_ref[:]                                          # [8, cols]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], _LANE), 1)

    def body(j, carry):
        taken, v, i = carry
        # an explicit taken-mask (rather than overwriting extracted slots
        # with -inf) keeps rows containing real -inf entries correct: once
        # only -inf remains, extraction still proceeds in ascending index
        # order over untaken slots, matching lax.top_k exactly. The mask is
        # carried as int32 — Mosaic cannot legalize an i1 vector loop carry.
        free = taken == 0
        m = jnp.max(jnp.where(free, x, -jnp.inf), axis=1,
                    keepdims=True)                        # [8, 1]
        # first untaken index attaining the max (lax.top_k's tie order)
        idx = jnp.min(jnp.where(free & (x >= m), lane, cols), axis=1,
                      keepdims=True)                      # [8, 1]
        v = jnp.where(out_lane == j, m, v)
        i = jnp.where(out_lane == j, idx, i)
        return jnp.where(lane == idx, 1, taken), v, i

    _, v, i = jax.lax.fori_loop(
        0, k, body, (jnp.zeros(x.shape, jnp.int32),
                     jnp.full((x.shape[0], _LANE), -jnp.inf, x.dtype),
                     jnp.zeros((x.shape[0], _LANE), jnp.int32)))
    v_ref[:] = v
    i_ref[:] = i


@functools.partial(jax.jit, static_argnames=("k",))
@_trace.phased("select")
def topk_rows(x: jax.Array, k: int):
    """Per-row ``(values, indices)`` of the k largest elements, identical to
    ``jax.lax.top_k`` (descending values, ties broken by first occurrence)
    for NaN-free input — the engine's importance values are |v| or the
    -1/-inf sentinels. Rows containing NaN are unspecified (extraction
    stalls where lax.top_k would surface the NaN first).

    One VMEM-resident pass per row block: k sequential max-extractions.
    The engine (``flat.FlatDGCEngine._exact_topk``) routes exact selection
    through this kernel on TPU below a WORK-BASED crossover of ~2M
    element-extractions per row block (k * cols): below it the kernel's
    sequential extraction beats XLA's sort-based TopK (measured on v5e,
    device profile: [22, 36864] k=37 — kernel 0.14 vs sort 0.16 ms), above
    it the sort wins ([19, 65536] k=66 — kernel 0.52 vs sort 0.42 ms). At
    small row counts the two are at parity ([8, 36864] k=37: 0.242 vs
    0.238 ms), so the gate is conservative there. Independently of that
    gate, this function self-delegates to ``lax.top_k`` when k exceeds the
    lane width or a row block exceeds the VMEM budget. Non-lane-aligned
    widths pay one -inf pad copy.

    Sub-4-byte inputs (bf16 importance under the bf16 error-feedback
    state) that reach the kernel path run through one up-cast to f32: the
    kernel's 8-sublane tiles and int32 taken-mask carry are f32-shaped,
    and bf16->f32 is monotone and injective, so ordering, tie-breaking,
    and the down-cast values are all exact. The delegation gates are
    checked FIRST (at f32-equivalent VMEM cost) so a delegating call
    never pays the up-cast copy — lax.top_k handles bf16 natively."""
    R, cols = x.shape
    # k > cols delegates so lax.top_k raises its usual error; k > _LANE
    # exceeds the [8, 128] output block; oversized rows exceed VMEM
    # (sized at 4 B/elem: sub-word inputs are up-cast for the kernel)
    if (k > _LANE or k > cols
            or 8 * _round_up(cols, _LANE) * max(x.dtype.itemsize, 4)
            > _TOPK_VMEM_BYTES):
        return jax.lax.top_k(x, k)
    if x.dtype.itemsize < 4:
        v, i = topk_rows(x.astype(jnp.float32), k)
        return v.astype(x.dtype), i
    rpad = (-R) % _SUBLANE
    cpad = (-cols) % _LANE
    if rpad or cpad:
        x = jnp.pad(x, ((0, rpad), (0, cpad)), constant_values=-jnp.inf)
    R8, cols = R + rpad, cols + cpad
    spec_x = pl.BlockSpec((_SUBLANE, cols), lambda r: (r, 0),
                          memory_space=pltpu.VMEM)
    spec_o = pl.BlockSpec((_SUBLANE, _LANE), lambda r: (r, 0),
                          memory_space=pltpu.VMEM)
    v, i = pl.pallas_call(
        functools.partial(_topk_kernel, k=k, cols=cols),
        grid=(R8 // _SUBLANE,),
        out_shape=(jax.ShapeDtypeStruct((R8, _LANE), x.dtype),
                   jax.ShapeDtypeStruct((R8, _LANE), jnp.int32)),
        in_specs=[spec_x],
        out_specs=(spec_o, spec_o),
        interpret=_interpret(),
        name="topk_rows",
    )(x)
    return v[:R, :k], i[:R, :k]


# ------------------------------------------------------------------ #
# fused threshold -> select -> pack (the compress-side epilogue)     #
# ------------------------------------------------------------------ #

def select_pack_rows_reference(x: jax.Array, numels: jax.Array, k: int):
    """jnp reference: the engine's unfused exact-selection sequence — mask
    the row tail to importance -1, ``lax.top_k`` over |x|, then gather the
    SIGNED values at the selected columns."""
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    imp = jnp.where(lane < numels[:, None], jnp.abs(x),
                    jnp.full((), -1.0, x.dtype))
    scores, cols = jax.lax.top_k(imp, k)
    return scores, jnp.take_along_axis(x, cols, axis=1), cols


def _select_pack_kernel(x_ref, n_ref, s_ref, v_ref, i_ref, *, k, cols):
    x = x_ref[:]                                          # [8, cols] signed
    n = n_ref[:]                                          # [8, 1] int32
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    out_lane = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], _LANE), 1)
    # importance masking fused in: row tails (and the -inf column pad)
    # read -1, exactly the engine's imp_rows array — which this kernel
    # makes disappear from HBM
    imp = jnp.where(lane < n, jnp.abs(x), jnp.full((), -1.0, x.dtype))

    def body(j, carry):
        taken, s, v, i = carry
        # same extraction order as _topk_kernel (see its taken-mask note):
        # max over untaken importance, first attaining index wins ties
        free = taken == 0
        m = jnp.max(jnp.where(free, imp, -jnp.inf), axis=1,
                    keepdims=True)                        # [8, 1]
        idx = jnp.min(jnp.where(free & (imp >= m), lane, cols), axis=1,
                      keepdims=True)                      # [8, 1]
        # the SIGNED payload value at the extracted column — a one-hot
        # row sum instead of a gather (no dynamic indexing on TPU)
        val = jnp.sum(jnp.where(lane == idx, x, jnp.zeros((), x.dtype)),
                      axis=1, keepdims=True)              # [8, 1]
        s = jnp.where(out_lane == j, m, s)
        v = jnp.where(out_lane == j, val, v)
        i = jnp.where(out_lane == j, idx, i)
        return jnp.where(lane == idx, 1, taken), s, v, i

    _, s, v, i = jax.lax.fori_loop(
        0, k, body, (jnp.zeros(x.shape, jnp.int32),
                     jnp.full((x.shape[0], _LANE), -jnp.inf, x.dtype),
                     jnp.zeros((x.shape[0], _LANE), x.dtype),
                     jnp.zeros((x.shape[0], _LANE), jnp.int32)))
    s_ref[:] = s
    v_ref[:] = v
    i_ref[:] = i


@functools.partial(jax.jit, static_argnames=("k",))
@_trace.phased("select")
def select_pack_rows(x: jax.Array, numels: jax.Array, k: int):
    """Fused threshold->select->pack over a bucket's [R, cols] SIGNED value
    block: per row, ``(scores, values, cols)`` of the k most important
    (|x|) elements among the first ``numels[r]`` columns — bitwise
    :func:`select_pack_rows_reference` (and therefore bitwise the engine's
    unfused ``imp_rows`` + ``topk_rows`` + ``take_along_axis`` sequence)
    for NaN-free input.

    One VMEM-resident pass replaces THREE [R, cols]-scale touches of the
    unfused compress side: the masked-importance materialization, the
    top-k read, and the value gather — the compress-side twin of
    :func:`payload_apply_bits`, attacking the fixed per-step overhead
    that makes DGC lose to dense psum on fast fabrics. Each of the k
    extractions emits the signed value through a one-hot row sum in the
    same loop iteration that finds the column, so the block is read once.

    Dispatch: ``k`` beyond :data:`_MR_MAX_K` (or beyond the row width)
    falls back to the reference; sub-4-byte inputs up-cast once to f32
    (monotone, injective — ordering, ties, and the cast-back values all
    exact); ``k`` beyond the lane width or a row block beyond the VMEM
    budget routes to the chunked multi-round kernel
    (:func:`_select_pack_rows_mr` — bitwise this same contract), which
    kills the old ``max_sel <= 128`` reference-delegate cliff (the
    VGG-16 fc select outlier, 11.3 ms/step of XLA sort); only the small
    single-block regime keeps this one-pass kernel, byte-identical to
    its pre-multi-round form."""
    R, cols = x.shape
    numels = numels.astype(jnp.int32)
    if k > _MR_MAX_K or k > cols:
        return select_pack_rows_reference(x, numels, k)
    if x.dtype.itemsize < 4:
        s, v, i = select_pack_rows(x.astype(jnp.float32), numels, k)
        return s.astype(x.dtype), v.astype(x.dtype), i
    if (k > _LANE
            or 8 * _round_up(cols, _LANE) * max(x.dtype.itemsize, 4)
            > _TOPK_VMEM_BYTES):
        return _select_pack_rows_mr(x, numels, k)
    rpad = (-R) % _SUBLANE
    cpad = (-cols) % _LANE
    if rpad or cpad:
        # value pad is 0, masked to importance -1 by the padded numels
        x = jnp.pad(x, ((0, rpad), (0, cpad)))
    if rpad:
        numels = jnp.pad(numels, (0, rpad))
    R8, colsp = R + rpad, cols + cpad
    spec_x = pl.BlockSpec((_SUBLANE, colsp), lambda r: (r, 0),
                          memory_space=pltpu.VMEM)
    spec_n = pl.BlockSpec((_SUBLANE, 1), lambda r: (r, 0),
                          memory_space=pltpu.VMEM)
    spec_o = pl.BlockSpec((_SUBLANE, _LANE), lambda r: (r, 0),
                          memory_space=pltpu.VMEM)
    s, v, i = pl.pallas_call(
        functools.partial(_select_pack_kernel, k=k, cols=colsp),
        grid=(R8 // _SUBLANE,),
        out_shape=(jax.ShapeDtypeStruct((R8, _LANE), x.dtype),
                   jax.ShapeDtypeStruct((R8, _LANE), x.dtype),
                   jax.ShapeDtypeStruct((R8, _LANE), jnp.int32)),
        in_specs=[spec_x, spec_n],
        out_specs=(spec_o, spec_o, spec_o),
        interpret=_interpret(),
        name="select_pack_rows",
    )(x, numels.reshape(-1, 1))
    return s[:R, :k], v[:R, :k], i[:R, :k]


#: widest selection the multi-round kernel serves (8 output lanes of
#: 128): beyond it the carry blocks stop paying for themselves vs the
#: XLA sort and the reference takes over
_MR_MAX_K = 8 * _LANE
#: column chunk per multi-round grid step: 8 rows x 16K cols x 4 B =
#: 512 KB per f32 VMEM stream (values + importance + taken mask + column
#: iota ≈ 2 MB resident), small enough that the carry blocks and the
#: next chunk's DMA fit alongside
_MR_COL_CHUNK = 16 * 1024


def _select_pack_mr_kernel(x_ref, n_ref, s_ref, v_ref, i_ref, *, k, kp,
                           colsp):
    """One column chunk of the multi-round selection: merge the running
    top-k carry (the revisited output blocks — the :func:`_ladder_kernel`
    accumulation pattern) with this chunk's candidates by k rounds of
    max extraction over their UNION. Ties break to the smallest flat
    column exactly like :func:`_select_pack_kernel`: carry positions are
    always left of this chunk's, so first-occurrence order is preserved
    across chunks and the final blocks are bitwise ``lax.top_k`` over
    the whole row."""
    c = pl.program_id(1)
    x = x_ref[:]                                          # [8, chunk]
    n = n_ref[:]                                          # [8, 1] int32
    chunk = x.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    gcol = c * chunk + lane                               # flat columns
    imp = jnp.where(gcol < n, jnp.abs(x), jnp.full((), -1.0, x.dtype))

    @pl.when(c == 0)
    def _():
        # empty carry: importance sentinel -2.0 sits strictly below the
        # -1.0 structural-pad floor, so a sentinel slot can never win a
        # round (every chunk offers >= k candidates at >= -1.0); the
        # position sentinel colsp never collides with a real column
        s_ref[:] = jnp.full((x.shape[0], kp), -2.0, x.dtype)
        v_ref[:] = jnp.zeros((x.shape[0], kp), x.dtype)
        i_ref[:] = jnp.full((x.shape[0], kp), colsp, jnp.int32)

    s0 = s_ref[:]
    v0 = v_ref[:]
    i0 = i_ref[:]
    ko = jax.lax.broadcasted_iota(jnp.int32, (x.shape[0], kp), 1)

    def body(j, carry):
        tc, tk, ns, nv, ni = carry
        freec = tc == 0
        freek = tk == 0
        mc = jnp.max(jnp.where(freec, imp, -jnp.inf), axis=1,
                     keepdims=True)                       # [8, 1]
        mk = jnp.max(jnp.where(freek, s0, -jnp.inf), axis=1,
                     keepdims=True)
        mx = jnp.maximum(mc, mk)
        # smallest position attaining the max, across carry AND chunk
        pc = jnp.min(jnp.where(freec & (imp >= mx), gcol, colsp), axis=1,
                     keepdims=True)
        pk = jnp.min(jnp.where(freek & (s0 >= mx), i0, colsp), axis=1,
                     keepdims=True)
        pos = jnp.minimum(pc, pk)
        # the signed value rides from whichever side owns the position
        # (disjoint: carry positions < c*chunk <= chunk positions)
        val = (jnp.sum(jnp.where(gcol == pos, x, jnp.zeros((), x.dtype)),
                       axis=1, keepdims=True)
               + jnp.sum(jnp.where(freek & (i0 == pos), v0,
                                   jnp.zeros((), x.dtype)),
                         axis=1, keepdims=True))
        ns = jnp.where(ko == j, mx, ns)
        nv = jnp.where(ko == j, val, nv)
        ni = jnp.where(ko == j, pos, ni)
        return (jnp.where(gcol == pos, 1, tc),
                jnp.where(freek & (i0 == pos), 1, tk), ns, nv, ni)

    _, _, ns, nv, ni = jax.lax.fori_loop(
        0, k, body,
        (jnp.zeros(x.shape, jnp.int32),
         jnp.zeros((x.shape[0], kp), jnp.int32),
         jnp.full((x.shape[0], kp), -2.0, x.dtype),
         jnp.zeros((x.shape[0], kp), x.dtype),
         jnp.full((x.shape[0], kp), colsp, jnp.int32)))
    s_ref[:] = ns
    v_ref[:] = nv
    i_ref[:] = ni


def _select_pack_rows_mr(x: jax.Array, numels: jax.Array, k: int):
    """Chunked multi-round :func:`select_pack_rows` for 128 < k <= 1024
    or rows beyond the single-block VMEM budget: the row streams through
    :data:`_MR_COL_CHUNK`-column chunks (inner grid dimension — the
    Pallas pipeline double-buffers the next chunk's DMA under the
    current merge) while the running top-k lives in the revisited
    [8, kp] output blocks. Each chunk runs k merge rounds over carry ∪
    chunk, so the selection is EXACT — bitwise
    :func:`select_pack_rows_reference` — where the engine previously
    delegated to the XLA sort (the VGG-16 fc cliff) or fell back to
    ``approx_max_k``."""
    R, cols = x.shape
    kp = _round_up(k, _LANE)
    rpad = (-R) % _SUBLANE
    chunk = min(_MR_COL_CHUNK, _round_up(cols, _LANE))
    colsp = _round_up(cols, chunk)
    cpad = colsp - cols
    if rpad or cpad:
        # value pad is 0, masked to importance -1 by the padded numels
        x = jnp.pad(x, ((0, rpad), (0, cpad)))
    if rpad:
        numels = jnp.pad(numels, (0, rpad))
    R8 = R + rpad
    spec_x = pl.BlockSpec((_SUBLANE, chunk), lambda r, c: (r, c),
                          memory_space=pltpu.VMEM)
    spec_n = pl.BlockSpec((_SUBLANE, 1), lambda r, c: (r, 0),
                          memory_space=pltpu.VMEM)
    spec_o = pl.BlockSpec((_SUBLANE, kp), lambda r, c: (r, 0),
                          memory_space=pltpu.VMEM)
    s, v, i = pl.pallas_call(
        functools.partial(_select_pack_mr_kernel, k=k, kp=kp, colsp=colsp),
        grid=(R8 // _SUBLANE, colsp // chunk),
        out_shape=(jax.ShapeDtypeStruct((R8, kp), x.dtype),
                   jax.ShapeDtypeStruct((R8, kp), x.dtype),
                   jax.ShapeDtypeStruct((R8, kp), jnp.int32)),
        in_specs=[spec_x, spec_n],
        out_specs=(spec_o, spec_o, spec_o),
        interpret=_interpret(),
        name="select_pack_rows_mr",
    )(x, numels.reshape(-1, 1))
    return s[:R, :k], v[:R, :k], i[:R, :k]


# ------------------------------------------------------------------ #
# per-(lane, segment) top-2 candidate extraction                     #
# ------------------------------------------------------------------ #

#: 128-lane blocks per candidate segment. Sized so the per-(row, lane)
#: candidate density at the published ratios keeps the top-k capture
#: high: a top-k element is lost only when >= 3 of the row's top-k land
#: in ONE (lane, segment) cell; with cells = 128 * nb/256 the cell
#: occupancy is Poisson(~0.26) at the VGG-fc operating point, losing
#: ~0.9% of the top set — recomposed with the downstream approx
#: selection this matches the previous PartialReduce path's measured
#: recall. The value is a power of two so ladder-aligned buckets
#: (cols a multiple of 128K elements) and their bases are always
#: block-divisible (see seg_top2_eligible).
_SEG_BLOCKS = 256


def seg_top2_eligible(total_blocks: int, base: int, cols: int,
                      rows: int = 1) -> bool:
    """Whether a bucket's [rows, cols] region can be read by the
    candidates kernel straight out of the flat [T] buffer: the base and
    the row width must be whole multiples of the segment span so the
    BlockSpec index map lands on block boundaries (no slicing, hence no
    copy), and the whole region must lie inside the buffer."""
    span = _SEG_BLOCKS * _LANE
    return (base % span == 0 and cols % span == 0
            and (total_blocks * _LANE) >= base + rows * cols)


def seg_cols_local(blks: jax.Array) -> jax.Array:
    """Per-segment block indices -> bucket-local columns, flattened per
    row. ``blks`` is [R, nseg, 2, 128] (the candidate layout every
    seg-top-2 producer emits); the result is [R, nseg*2*128] in (seg,
    slot, lane) order. ONE source of truth for the recomposition
    ``(blk + seg*SEG_BLOCKS) * 128 + lane`` — the standalone kernel,
    the jnp reference, and the engine's fused-candidates slice all route
    through it, so the bitwise-parity contract between those paths
    cannot drift."""
    R, nseg = blks.shape[0], blks.shape[1]
    lane = jnp.arange(_LANE, dtype=jnp.int32)
    seg0 = (jnp.arange(nseg, dtype=jnp.int32)
            * _SEG_BLOCKS)[None, :, None, None]
    return ((blks + seg0) * _LANE
            + lane[None, None, None, :]).reshape(R, -1)


def seg_top2_reference(v2d: jax.Array, base: int, rows: int, cols: int):
    """jnp reference: per-(row, lane, segment) top-2 by |value| with
    first-occurrence ties, identical candidate order to the kernel.
    Takes the same [T/128, 128] block view as the kernel. Returns
    (signed values [R, C], local cols [R, C]) with
    C = (cols // (SEG_BLOCKS*128)) * 2 * 128; candidate (seg, slot,
    lane) flattens in that order."""
    nseg = cols // (_SEG_BLOCKS * _LANE)
    v = v2d.reshape(-1)[base:base + rows * cols].reshape(
        rows, nseg, _SEG_BLOCKS, _LANE).astype(jnp.float32)
    a = jnp.abs(v)
    # top-2 along the segment axis, ties -> lowest block index
    m1 = jnp.max(a, axis=2)                                # [R, S, 128]
    blk = jnp.arange(_SEG_BLOCKS, dtype=jnp.int32)[None, None, :, None]
    am1 = jnp.min(jnp.where(a >= m1[:, :, None], blk, _SEG_BLOCKS),
                  axis=2)
    v1 = jnp.take_along_axis(v, am1[:, :, None], axis=2)[:, :, 0]
    a2 = jnp.where(blk == am1[:, :, None], -1.0, a)
    m2 = jnp.max(a2, axis=2)
    am2 = jnp.min(jnp.where(a2 >= m2[:, :, None], blk, _SEG_BLOCKS),
                  axis=2)
    v2 = jnp.take_along_axis(v, am2[:, :, None], axis=2)[:, :, 0]
    vals = jnp.stack([v1, v2], axis=2)                     # [R, S, 2, 128]
    cols_local = seg_cols_local(jnp.stack([am1, am2], axis=2))
    return (vals.reshape(rows, -1), cols_local)


def _seg_top2_kernel(x_ref, v_ref, i_ref):
    # narrow (bf16) inputs up-cast once in VMEM: the comparison math and
    # the emitted values are f32 (exact for bf16), keeping the output
    # blocks at the f32 tile shape regardless of the state dtype.
    # Cell math lives in _seg_top2_block, shared with the fused
    # compensate+candidates kernel (bitwise-identical candidates).
    x = x_ref[...].astype(jnp.float32)                     # [SEG, 128]
    v, i = _seg_top2_block(x)
    v_ref[...] = v[None]                                   # [1, 2, 128]
    i_ref[...] = i[None]


@functools.partial(jax.jit,
                   static_argnames=("base", "rows", "cols"))
def seg_top2_candidates(v2d: jax.Array, base: int, rows: int, cols: int):
    """Per-(row, lane, segment) top-2 candidates of a bucket, read
    DIRECTLY from the flat [T] buffer (no slice, no copy): one streamed
    pass emitting the signed value and the local column of the two
    largest-|.| elements of every (lane, 256-block segment) cell.

    Replaces the 3-D selection path's slice + abs + PartialReduce +
    candidate-remap + payload-gather chain (measured ~6 ms/step of slice
    copies and payload-scale random gathers at VGG's fc buckets, device
    profile r5): the only payload-scale work left downstream is the
    [R, C]-candidate top-k, and values/columns come out of the stream.
    Caller must check :func:`seg_top2_eligible`. Row tails beyond a
    tensor's numel carry structural zeros: their candidates have value
    0.0 and are masked by the engine's ``cols < numel`` validity.

    ``v2d`` is the [T/128, 128] block view of the flat buffer — the
    caller reshapes ONCE and shares it across every bucket's kernel call
    and the sampling gather (each nested-jit call reshaping its own copy
    cost ~2.5 ms/step of duplicate [T] materializations at VGG, device
    profile r5)."""
    assert seg_top2_eligible(v2d.shape[0], base, cols, rows), (
        base, cols, rows)
    nseg = cols // (_SEG_BLOCKS * _LANE)
    nb = cols // _LANE
    base_blk = base // _LANE
    grid = (rows, nseg)
    vals, blks = pl.pallas_call(
        _seg_top2_kernel,
        grid=grid,
        out_shape=(
            jax.ShapeDtypeStruct((rows * nseg, 2, _LANE), jnp.float32),
            jax.ShapeDtypeStruct((rows * nseg, 2, _LANE), jnp.int32),
        ),
        in_specs=[pl.BlockSpec(
            (_SEG_BLOCKS, _LANE),
            lambda r, s: (base_blk // _SEG_BLOCKS
                          + r * (nb // _SEG_BLOCKS) + s, 0),
            memory_space=pltpu.VMEM)],
        out_specs=(
            pl.BlockSpec((1, 2, _LANE), lambda r, s: (r * nseg + s, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 2, _LANE), lambda r, s: (r * nseg + s, 0, 0),
                         memory_space=pltpu.VMEM),
        ),
        interpret=_interpret(),
        name="seg_top2_candidates",
    )(v2d)
    return (vals.reshape(rows, -1),
            seg_cols_local(blks.reshape(rows, nseg, 2, _LANE)))


# ------------------------------------------------------------------ #
# compensate + candidate extraction, one pass                        #
# ------------------------------------------------------------------ #

def _seg_top2_block(x):
    """Per-(lane) top-2 by |value| of one [SEG_BLOCKS, 128] cell block —
    the exact math of :func:`_seg_top2_kernel`, shared so the fused
    compensate+candidates kernel emits bitwise-identical candidates.
    Returns ([2, 128] signed values, [2, 128] local block indices)."""
    a = jnp.abs(x)
    blk = jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)
    m1 = jnp.max(a, axis=0, keepdims=True)                 # [1, 128]
    am1 = jnp.min(jnp.where(a >= m1, blk, _SEG_BLOCKS), axis=0,
                  keepdims=True)                           # [1, 128]
    v1 = jnp.sum(jnp.where(blk == am1, x, 0.0), axis=0, keepdims=True)
    a2 = jnp.where(blk == am1, -1.0, a)
    m2 = jnp.max(a2, axis=0, keepdims=True)
    am2 = jnp.min(jnp.where(a2 >= m2, blk, _SEG_BLOCKS), axis=0,
                  keepdims=True)
    v2 = jnp.sum(jnp.where(blk == am2, x, 0.0), axis=0, keepdims=True)
    return (jnp.concatenate([v1, v2], axis=0),
            jnp.concatenate([am1, am2], axis=0))


def fused_compensate_bits_cands_reference(grad, mmt, vec, bits,
                                          momentum: float, nesterov: bool,
                                          momentum_masking: bool):
    """jnp reference of the fused pass: compensate-with-bit-mask, then
    per-(lane, segment) top-2 candidates over the STORED velocity (the
    state-dtype round-trip makes narrow-state candidates match the
    standalone :func:`seg_top2_reference` on the stored buffer exactly).
    ``grad`` may be LONGER than the state (the engine passes the whole
    flat [P] buffer so no [:T] slice is ever materialized); only the
    first ``mmt.shape[0]`` elements participate. Returns candidates for
    the ``n // span`` COMPLETE segments only — the compiled kernel's
    output has ``grid * segments_per_block >= n // span`` rows whose
    tail (straddling or grid-overhang segments) is unspecified, so
    comparisons against this reference must slice the compiled output
    to ``[:n // span]`` (see scripts/tpu_check.py); callers only ever
    consume segments fully inside an eligible bucket, which end on
    segment boundaries."""
    n = mmt.shape[0]
    om, ov = fused_compensate_bits_reference(grad[:n], mmt, vec, bits,
                                             momentum, nesterov,
                                             momentum_masking)
    span = _SEG_BLOCKS * _LANE
    nseg = n // span
    x = ov[:nseg * span].astype(jnp.float32).reshape(nseg, _SEG_BLOCKS,
                                                     _LANE)
    cvs, cis = [], []
    for s in range(nseg):
        v, i = _seg_top2_block(x[s])
        cvs.append(v)
        cis.append(i)
    cv = (jnp.stack(cvs) if cvs
          else jnp.zeros((0, 2, _LANE), jnp.float32))
    ci = (jnp.stack(cis) if cis
          else jnp.zeros((0, 2, _LANE), jnp.int32))
    return om, ov, cv, ci


def _compensate_bits_cands_kernel(g_ref, m_ref, v_ref, b_ref, om_ref,
                                  ov_ref, cv_ref, ci_ref, *, momentum,
                                  nesterov, momentum_masking):
    m, ov = _bits_compensate_core(g_ref, m_ref, v_ref, b_ref,
                                  momentum=momentum, nesterov=nesterov,
                                  momentum_masking=momentum_masking)
    ov_ref[:] = ov.astype(ov_ref.dtype)
    om_ref[:] = m.astype(om_ref.dtype)
    # candidates read the STORED velocity value: one round-trip through
    # the state dtype (no-op for f32) keeps them bitwise what the
    # standalone kernel would read back from HBM
    x_all = ov.astype(ov_ref.dtype).astype(jnp.float32)
    rows = x_all.shape[0]
    cvs, cis = [], []
    for s in range(rows // _SEG_BLOCKS):
        v, i = _seg_top2_block(x_all[s * _SEG_BLOCKS:(s + 1) * _SEG_BLOCKS])
        cvs.append(v)
        cis.append(i)
    cv_ref[...] = jnp.stack(cvs)                          # [spb, 2, 128]
    ci_ref[...] = jnp.stack(cis)


@functools.partial(jax.jit, static_argnames=("momentum", "nesterov",
                                             "momentum_masking"))
def fused_compensate_bits_cands(grad: jax.Array, mmt: jax.Array,
                                vec: jax.Array, bits: jax.Array,
                                momentum: float, nesterov: bool = False,
                                momentum_masking: bool = True):
    """:func:`fused_compensate_bits` that ALSO emits the segment-top-2
    selection candidates from the same pass.

    Motivation (r5 device profile at VGG-16): the compensate kernel is
    bandwidth-bound (five [T]-scale streams, VPU mostly idle) and the
    standalone :func:`seg_top2_candidates` kernel re-reads the velocity
    it just wrote — a full extra [T] stream plus its own kernel launch
    (1.7 ms/step at VGG). Extracting the per-(lane, 256-block segment)
    top-2 while the compensated block is still VMEM-resident removes
    that stream; the candidate compute hides under the DMA waits.

    Two deliberate signature deltas vs the plain kernel:

    * ``grad`` may be LONGER than the state buffers — the engine passes
      the whole flat [P] gradient so XLA never materializes the
      ``flat_grad[:T]`` slice as a Pallas operand copy. Only rows
      covering ``mmt.shape[0]`` are written back (ragged stores masked).
    * returns ``(mmt', vec', cand_vals [NS, 2, 128] f32,
      cand_blks [NS, 2, 128] int32)`` where NS covers every grid
      block's segments. Segments past the last complete one (and any
      grid-overhang tail) carry unspecified values — eligible buckets
      end on segment boundaries (:func:`seg_top2_eligible`), so the
      engine never reads them. Candidate (value, block) pairs are
      bitwise :func:`seg_top2_candidates` on the stored velocity.

    Alignment: the state length must tile the sublane group (the
    engine's T is _ALIGN-aligned, so this never pads); ``grad`` length
    must be lane-aligned (layout.total is _ALIGN-aligned)."""
    n = mmt.shape[0]
    assert vec.shape[0] == n and grad.shape[0] >= n, (grad.shape, n)
    assert bits.shape[0] == num_sent_words(n), (bits.shape, n)
    sub = _SUBLANE * (2 if min(grad.dtype.itemsize, mmt.dtype.itemsize,
                               vec.dtype.itemsize) < 4 else 1)
    assert n % (sub * _LANE) == 0, n
    assert grad.shape[0] % _LANE == 0, grad.shape
    rows = n // _LANE
    g2 = grad.reshape(-1, _LANE)
    m2, v2 = mmt.reshape(rows, _LANE), vec.reshape(rows, _LANE)
    b2 = bits.reshape(-1, _LANE)

    # blocks must hold whole 256-block segments AND whole 32-row word
    # groups; the grid's ragged last block is masked for the state
    # stores, candidate tails are unspecified (see docstring)
    block_rows = min(_CHUNK_ROWS, _round_up(rows, _SEG_BLOCKS))
    # _CHUNK_ROWS is a multiple of _SEG_BLOCKS today; if either constant
    # drifts, spb silently truncates and candidate segments misalign
    assert block_rows % _SEG_BLOCKS == 0, (block_rows, _SEG_BLOCKS)
    grid = pl.cdiv(rows, block_rows)
    spb = block_rows // _SEG_BLOCKS
    ns = grid * spb
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((block_rows // 32, _LANE), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    cspec = pl.BlockSpec((spb, 2, _LANE), lambda i: (i, 0, 0),
                         memory_space=pltpu.VMEM)
    om, ov, cv, ci = pl.pallas_call(
        functools.partial(_compensate_bits_cands_kernel, momentum=momentum,
                          nesterov=nesterov,
                          momentum_masking=momentum_masking),
        grid=(grid,),
        out_shape=(jax.ShapeDtypeStruct((rows, _LANE), mmt.dtype),
                   jax.ShapeDtypeStruct((rows, _LANE), vec.dtype),
                   jax.ShapeDtypeStruct((ns, 2, _LANE), jnp.float32),
                   jax.ShapeDtypeStruct((ns, 2, _LANE), jnp.int32)),
        in_specs=[spec, spec, spec, bspec],
        out_specs=(spec, spec, cspec, cspec),
        # in-place state update (see fused_compensate_bits)
        input_output_aliases={1: 0, 2: 1},
        interpret=_interpret(),
        name="fused_compensate_bits_cands",
    )(g2, m2, v2, b2)
    return om.reshape(-1), ov.reshape(-1), cv, ci


# ------------------------------------------------------------------ #
# forward megakernel: compensate -> select -> pack, one pass         #
# ------------------------------------------------------------------ #

def dgc_forward_rows_reference(grad, mmt, vec, bits, base: int,
                               numels, k: int, momentum: float,
                               nesterov: bool = False,
                               momentum_masking: bool = True):
    """jnp reference of :func:`dgc_forward_rows`: the engine's unfused
    sequence over one bucket region — window the transmit record
    (:func:`realign_bits`), bit-masked compensate, then exact
    select+pack over the [R, cols] row view. ``grad``/``mmt``/``vec``
    are the flat ``[R * cols]`` region slices."""
    n = mmt.shape[0]
    R = numels.shape[0]
    cols = n // R
    rb = realign_bits(bits, base, n)
    om, ov = fused_compensate_bits_reference(grad, mmt, vec, rb, momentum,
                                             nesterov, momentum_masking)
    s, v, c = select_pack_rows_reference(
        ov.reshape(R, cols), jnp.asarray(numels, jnp.int32), k)
    return om, ov, s, v, c


def _dgc_forward_kernel(n_ref, g_ref, m_ref, v_ref, b_ref, om_ref, ov_ref,
                        s_ref, pv_ref, pi_ref, *, k, kp, cols, momentum,
                        nesterov, momentum_masking):
    """One grid step = one bucket row: expand the row's pre-realigned
    transmit-record window, masked compensate (:func:`_compensate_math`
    — bitwise the unfused kernels), then k rounds of in-VMEM max
    extraction over the compensated velocity (same tie order as
    :func:`_select_pack_kernel`, flat column = 128-block * 128 + lane).
    The candidate values and indices never leave VMEM between the
    compensate and the pack."""
    r = pl.program_id(0)
    numel = n_ref[r]
    g = g_ref[...]                                        # [nblk, 128]
    nblk = g.shape[0]
    b = b_ref[0]                                          # [Wr, 128]
    wr = b.shape[0]
    exp = jnp.broadcast_to(b[:, None, :],
                           (wr, 32, _LANE)).reshape(wr * 32, _LANE)[:nblk]
    blk = jax.lax.broadcasted_iota(jnp.int32, (nblk, _LANE), 0)
    keep = (((exp >> (blk & 31)) & 1) == 0).astype(g.dtype)
    m, ov = _compensate_math(g, m_ref[...], v_ref[...], keep,
                             momentum=momentum, nesterov=nesterov,
                             momentum_masking=momentum_masking)
    om_ref[...] = m.astype(om_ref.dtype)
    ov_ref[...] = ov.astype(ov_ref.dtype)

    lane = jax.lax.broadcasted_iota(jnp.int32, (nblk, _LANE), 1)
    col = blk * _LANE + lane                              # row-local column
    imp = jnp.where(col < numel, jnp.abs(ov), jnp.full((), -1.0, ov.dtype))
    ko = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)

    def body(j, carry):
        taken, s, v, i = carry
        free = taken == 0
        m1 = jnp.max(jnp.where(free, imp, -jnp.inf), axis=0, keepdims=True)
        mx = jnp.max(m1, axis=1, keepdims=True)           # [1, 1]
        p1 = jnp.min(jnp.where(free & (imp >= mx), col, cols), axis=0,
                     keepdims=True)
        pos = jnp.min(p1, axis=1, keepdims=True)          # [1, 1]
        v1 = jnp.sum(jnp.where(col == pos, ov, jnp.zeros((), ov.dtype)),
                     axis=0, keepdims=True)
        val = jnp.sum(v1, axis=1, keepdims=True)          # [1, 1]
        s = jnp.where(ko == j, mx, s)
        v = jnp.where(ko == j, val, v)
        i = jnp.where(ko == j, pos, i)
        return jnp.where(col == pos, 1, taken), s, v, i

    _, s, v, i = jax.lax.fori_loop(
        0, k, body,
        (jnp.zeros((nblk, _LANE), jnp.int32),
         jnp.full((1, kp), -jnp.inf, ov.dtype),
         jnp.zeros((1, kp), ov.dtype),
         jnp.zeros((1, kp), jnp.int32)))
    s_ref[0] = s
    pv_ref[0] = v
    pi_ref[0] = i


@functools.partial(jax.jit, static_argnames=("base", "k", "momentum",
                                             "nesterov", "momentum_masking"))
def dgc_forward_rows(grad: jax.Array, mmt: jax.Array, vec: jax.Array,
                     bits: jax.Array, base: int, numels, k: int,
                     momentum: float, nesterov: bool = False,
                     momentum_masking: bool = True):
    """Forward megakernel: masked error-feedback compensate → momentum
    correction → threshold → select → pack for ONE bucket in ONE Pallas
    pass (grid = bucket rows, the Pallas pipeline double-buffers each
    row's five DMA streams under the previous row's extraction rounds).

    The unfused path launches a compensate kernel over [T], spills the
    compensated velocity to HBM, then re-reads each bucket's region for
    selection; here the compensated row never leaves VMEM between the
    momentum correction and the k-round partial selection, and the
    packed (scores, values, cols) payload is the only selection traffic
    that touches HBM. Selection is EXACT for any ``k`` up to the
    multi-round bound — the ``max_sel <= 128`` delegate cliff does not
    exist on this path.

    ``grad``/``mmt``/``vec`` are the flat ``[R * cols]`` REGION slices
    (f32 only — the engine gates the bf16 error-feedback state out);
    ``bits`` is the full-model packed transmit record (windowed per row
    in-trace via :func:`_realign_bits_rows`); ``numels`` the per-row
    valid widths; ``base`` the bucket's flat base offset. Returns
    ``(mmt' [n], vec' [n], scores [R, k], values [R, k], cols [R, k])``
    — bitwise :func:`dgc_forward_rows_reference`, i.e. bitwise the
    unfused compensate+select engine sequence. State updates ride
    in-place via ``input_output_aliases`` like every compensate kernel."""
    n = mmt.shape[0]
    R = int(numels.shape[0])
    if grad.dtype != jnp.float32 or mmt.dtype != jnp.float32 \
            or vec.dtype != jnp.float32:
        raise ValueError(
            "dgc_forward_rows is f32-only (the bf16 error-feedback state "
            f"must stay on the unfused path): got {grad.dtype}/"
            f"{mmt.dtype}/{vec.dtype}")
    assert grad.shape[0] == n and vec.shape[0] == n, (grad.shape, n)
    assert n % R == 0, (n, R)
    cols = n // R
    assert cols % _LANE == 0, cols
    assert base % _LANE == 0, base
    assert 0 < k <= min(cols, _MR_MAX_K), (k, cols)
    nblk = cols // _LANE
    kp = _round_up(k, _LANE)
    numels = jnp.asarray(numels, jnp.int32)
    rb = _realign_bits_rows(bits, base, R, nblk)          # [R, Wr, 128]
    wr = rb.shape[1]
    g2, m2, v2 = (a.reshape(R * nblk, _LANE) for a in (grad, mmt, vec))

    dspec = pl.BlockSpec((nblk, _LANE), lambda r, nn: (r, 0),
                         memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((1, wr, _LANE), lambda r, nn: (r, 0, 0),
                         memory_space=pltpu.VMEM)
    # [R, 1, kp] payload outputs: a (1, kp) block of an [R, kp] array
    # is neither (8, 128)-divisible nor the full extent, which Mosaic
    # refuses; with the unit middle dim the block's last two dims ARE
    # the array's
    ospec = pl.BlockSpec((1, 1, kp), lambda r, nn: (r, 0, 0),
                         memory_space=pltpu.VMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(R,),
        in_specs=[dspec, dspec, dspec, bspec],
        out_specs=(dspec, dspec, ospec, ospec, ospec),
    )
    om, ov, s, v, i = pl.pallas_call(
        functools.partial(_dgc_forward_kernel, k=k, kp=kp, cols=cols,
                          momentum=momentum, nesterov=nesterov,
                          momentum_masking=momentum_masking),
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((R * nblk, _LANE), mmt.dtype),
                   jax.ShapeDtypeStruct((R * nblk, _LANE), vec.dtype),
                   jax.ShapeDtypeStruct((R, 1, kp), vec.dtype),
                   jax.ShapeDtypeStruct((R, 1, kp), vec.dtype),
                   jax.ShapeDtypeStruct((R, 1, kp), jnp.int32)),
        # in-place state update (see fused_compensate_bits); indices
        # count the scalar-prefetch operand first
        input_output_aliases={2: 0, 3: 1},
        interpret=_interpret(),
        name="dgc_forward_rows",
    )(numels, g2, m2, v2, rb)
    return (om.reshape(-1), ov.reshape(-1),
            s[:, 0, :k], v[:, 0, :k], i[:, 0, :k])


# ------------------------------------------------------------------ #
# fused payload-apply epilogue                                       #
# ------------------------------------------------------------------ #

#: flat elements covered by one apply chunk — one VMEM-resident
#: [_CHUNK_ROWS, 128] output block of the fused pass
_APPLY_CHUNK = _CHUNK_ROWS * _LANE
#: rows of one apply sub-block, the M of one one-hot matmul (a power of
#: two, a multiple of 256; 512 against 256 on the chip, PR 31: 1.78 |
#: 2.08 ms a pass at 138,360 pairs, 3.90 | 3.87 at 553,440)
_APPLY_SUB = 512
#: 128-pair windows (lane rows of the sorted arrays) per pipelined input
#: block; a chunk holding more pairs than one block takes further pages
_APPLY_WPB = 32


def payload_apply_bits_reference(values, indices, flags, total: int):
    """jnp reference of :func:`payload_apply_bits`: the engine's historic
    XLA epilogue — a zeros-operand scatter-add decompress of the gathered
    payload plus the packed transmit-record scatter over the flagged
    entries (the local worker's non-sentinel coordinates)."""
    acc = jnp.zeros((total,), values.dtype).at[indices].add(values)
    routed = jnp.where(flags, indices, total)
    bits = pack_sent_bits(routed, total, sentinel=total)
    return acc, bits


def _one_hot(mask):
    """A 32-bit compare's mask as bf16 0/1 (selected in f32, then cast:
    Mosaic will not relayout an i1 mask onto packed bf16 operands)."""
    return jnp.where(mask, 1.0, 0.0).astype(jnp.bfloat16)  # dgcver: ok[dtype-flow] — 0.0 and 1.0 are exact in bf16


def _dot_nt(a, b):
    """``a [M, K] @ b [N, K]^T`` on the MXU, f32 accumulate. The bf16
    operands are exact parts and one-hot factors: one MXU pass is the
    whole product, whatever ``jax.default_matmul_precision`` the caller
    runs under (under ``highest`` Mosaic refuses a bf16 product that
    states none)."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=jax.lax.Precision.DEFAULT,
                               preferred_element_type=jnp.float32)


def _expand_pairs(pc_ref, pb_ref, first_ref, cw0_ref, cw1_ref, kmin_ref,
                  kmax_ref, k_ref, v_ref, f_ref, acc_ref, bits_ref):
    """One grid step applies one block of the SORTED pairs to its
    chunk's VMEM-resident block ``acc_ref`` (and ``bits_ref``). Pages of
    one chunk are consecutive, so the block stays in VMEM between them;
    the chunk's first page zero-fills it (every chunk owns a page, so
    every block is defined whatever it held).

    No pair is touched alone. A window is 128 consecutive sorted pairs,
    one lane row; a sub-block is :data:`_APPLY_SUB` rows of the output.
    A window's first and last index (prefetched) say which sub-blocks
    it reaches; for each of them the sub-block's values are

        OneHotRow [SUB, 128 pairs] @ (OneHotLane [128 lanes, 128 pairs]
                                      * value)^T

    on the MXU: both factors are vector compares of an iota against the
    window's row / lane numbers, pairs of other sub-blocks match no row
    and fall out by themselves, and the f32 value travels as three bf16
    parts (hi + mid + lo, 8 mantissa bits each) through three passes
    whose f32 sum is the value again, bit for bit. The transmit bits
    are a fourth product, ``2 * SUB / 32`` rows tall: the flagged
    pairs' word rows against ``2**(bit % 16)`` in their lanes, the low
    and the high half word kept in separate rows so that every sum
    stays under 2**16 and exact. The lane factors are built once a
    window, the row factors once a (window, sub-block)."""
    p = pl.program_id(0)
    c, b = pc_ref[p], pb_ref[p]
    sub, wr = _APPLY_SUB, _APPLY_SUB // 32
    nsb = _CHUNK_ROWS // sub
    shift = (sub * _LANE).bit_length() - 1
    bf16, f32 = jnp.bfloat16, jnp.float32

    @pl.when(first_ref[p] == 1)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        bits_ref[...] = jnp.zeros_like(bits_ref)

    row_iota = jax.lax.broadcasted_iota(jnp.int32, (sub, _LANE), 0)
    lane_iota = jax.lax.broadcasted_iota(jnp.int32, (_LANE, _LANE), 0)
    half_iota = jax.lax.broadcasted_iota(jnp.int32, (2 * wr, _LANE), 0)

    def window(w, carry):
        wl = w - b * _APPLY_WPB
        k = k_ref[pl.ds(wl, 1), :]                        # [1, 128]
        v = v_ref[pl.ds(wl, 1), :]
        f = f_ref[pl.ds(wl, 1), :]
        in_lane = lane_iota == (k & (_LANE - 1))          # [128, 128]

        def lanes(x):                        # x of a pair in its lane
            return jnp.where(in_lane, x, 0.0).astype(bf16)  # dgcver: ok[dtype-flow] — x is one of three bf16-exact parts whose f32 sum is the value

        # v = hi + mid + lo, each exact in bf16 (f32 here: the selects
        # run on 32-bit masks, the casts after them)
        hi_p = v.astype(bf16).astype(f32)  # dgcver: ok[dtype-flow] — the remainder travels in mid and lo
        mid_p = (v - hi_p).astype(bf16).astype(f32)  # dgcver: ok[dtype-flow] — the remainder travels in lo
        c_hi, c_mid, c_lo = lanes(hi_p), lanes(mid_p), lanes(
            (v - hi_p) - mid_p)
        # transmit bits (word layout of pack_sent_bits): word row
        # p // 4096, word lane p % 128, bit (p // 128) % 32; rows
        # [0, wr) of the product take bits 0..15, rows [wr, 2 wr)
        # bits 16..31
        row = k >> 7
        bit = row & 31
        c_bit = lanes(jnp.left_shift(1, bit & 15).astype(f32))
        wrow = jnp.where(f != 0, k >> 12, -1)    # unflagged: no word row
        up = jnp.where(bit >= 16, wr, 0)

        def sub_block(s, carry):
            r0 = pl.multiple_of((s - c * nsb) * sub, sub)
            b0 = pl.multiple_of((s - c * nsb) * wr, wr)
            a = _one_hot(row_iota == row - s * sub)       # [sub, 128]
            out = (_dot_nt(a, c_hi) + _dot_nt(a, c_mid)) + _dot_nt(a, c_lo)
            acc_ref[pl.ds(r0, sub), :] = acc_ref[pl.ds(r0, sub), :] + out
            local = wrow - s * wr
            half = jnp.where((local >= 0) & (local < wr), local + up, -1)
            words = _dot_nt(
                _one_hot(half_iota == half),
                c_bit).astype(jnp.int32)  # dgcver: ok[dtype-flow] — sums of distinct powers of two under 2**16: integers, exact in f32
            bits_ref[pl.ds(b0, wr), :] = (
                bits_ref[pl.ds(b0, wr), :]
                | words[:wr] | jnp.left_shift(words[wr:], 16))
            return carry

        # the sub-blocks of THIS chunk the window reaches (a pad
        # window's first index lies past every chunk: none)
        jax.lax.fori_loop(
            jnp.maximum(kmin_ref[w] >> shift, c * nsb),
            jnp.minimum(kmax_ref[w] >> shift, c * nsb + nsb - 1) + 1,
            sub_block, 0)
        return carry

    jax.lax.fori_loop(jnp.maximum(cw0_ref[c], b * _APPLY_WPB),
                      jnp.minimum(cw1_ref[c], (b + 1) * _APPLY_WPB),
                      window, 0)


def _payload_apply_kernel(*refs):
    """:func:`_expand_pairs` into the output block itself: the chunk
    reaches HBM once, when its last page is done."""
    *maps_and_pairs, bits_donor_ref, acc_ref, bits_ref = refs
    del bits_donor_ref  # alias donor: never dereferenced
    _expand_pairs(*maps_and_pairs, acc_ref, bits_ref)


#: rows of one slab of the update kernel's rule: the chunk's gradient,
#: its state blocks and the rule's temporaries at 8 vregs an array
_UPDATE_ROWS = 64


def _payload_update_kernel(rule, total: int, nstate: int, *refs):
    """:func:`_expand_pairs` into a VMEM scratch block, and on the
    chunk's LAST page ``rule`` over that block and the chunk's state
    blocks, slab by slab: the gradient never reaches HBM, and each state
    stream is read once and written once, in place. Coordinates at or
    past ``total`` (the caller's tail behind the block, the last
    chunk's ragged end) keep what they held."""
    # prefetched: the seven window maps, ``last``, the caller's scalars;
    # then the pairs, the donor, the state in and out, the bits, the
    # scratch block
    *maps, last_ref = refs[:8]
    fixed = 6 + 2 * nstate
    scalar_refs = refs[8:-fixed]
    k_ref, v_ref, f_ref, bits_donor_ref, *state_refs, bits_ref, acc_ref = (
        refs[-fixed:])
    del bits_donor_ref  # alias donor: never dereferenced
    old_refs, new_refs = state_refs[:nstate], state_refs[nstate:]
    _expand_pairs(*maps, k_ref, v_ref, f_ref, acc_ref, bits_ref)
    p = pl.program_id(0)
    base = maps[0][p] * _APPLY_CHUNK
    rows = _UPDATE_ROWS

    @pl.when(last_ref[p] == 1)
    def _update():
        scalars = tuple(s[0] for s in scalar_refs)
        offset = (jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 0)
                  * _LANE
                  + jax.lax.broadcasted_iota(jnp.int32, (rows, _LANE), 1))

        def slab(j, carry):
            r0 = pl.multiple_of(j * rows, rows)
            idx = base + r0 * _LANE + offset
            old = tuple(s[pl.ds(r0, rows), :] for s in old_refs)
            new = rule(acc_ref[pl.ds(r0, rows), :], idx, scalars, *old)
            inside = idx < total
            for o, n, s in zip(new_refs, new, old):
                o[pl.ds(r0, rows), :] = jnp.where(inside, n, s)
            return carry

        jax.lax.fori_loop(0, _CHUNK_ROWS // rows, slab, 0)


def _count_below(samples, queries):
    """``searchsorted(samples, queries, side="left")`` for the staging's
    short sorted arrays: one fused compare-and-count where that is
    small (a binary search is ~20 dependent steps of launch latency,
    0.3–0.7 ms on the chip whatever the sizes), the unrolled search
    above it."""
    small = samples.shape[0] * queries.shape[0] <= 1 << 26
    return jnp.searchsorted(
        samples, queries, side="left",
        method="compare_all" if small else "scan_unrolled"
    ).astype(jnp.int32)


def _sorted_pairs(values, indices, flags, total: int, divisor, max_dup):
    """Pair-scale staging of the apply kernels: ONE ``lax.sort`` of
    (index, position | flag, value), then elementwise work only. Returns
    the sorted arrays as [windows, 128] lane rows, padded by whole
    blocks of (INT32_MAX, 0.0, 0) — pads sort last and match no row —
    and the scalar-prefetch maps, all window- or chunk-sized:

    ``kmin`` / ``kmax`` [windows] — a window's first and last index;
    ``cw0`` / ``cw1`` [nchunks] — the windows that hold a chunk's pairs
    (counted against ``kmax`` / ``kmin``); ``page_chunk`` /
    ``page_block`` / ``first`` [npages] — grid step -> output chunk,
    sorted-array block, and whether the step opens its chunk. A chunk
    owns the blocks its windows lie in and at least one page; steps
    past the last used one revisit the last chunk with the all-pad
    block, which holds nothing.

    The position rides the sort as its second key, so equal indices
    keep payload order (what a stable sort gives, with one operand
    less). Cross-worker duplicates of one coordinate are then adjacent,
    and are summed HERE, left to right in f32 as a scatter-add in
    payload order would: ``max_dup - 1`` shifted adds where the caller
    bounds the run length (the engine: one pair a worker, so W; 1 skips
    the fold), else rounds until nothing changes. The run's last pair
    carries the sum and the others 0.0, so the kernel adds one non-zero
    term a coordinate and its result does not depend on the MXU's
    accumulation order."""
    n = values.shape[0]
    bp = _APPLY_WPB * _LANE
    nblocks = -(-n // bp) + 1                   # + one all-pad block
    pad = nblocks * bp - n
    i32 = jnp.int32
    assert 2 * nblocks * bp < 2 ** 31, n
    with _trace.phase("apply", part="sort"):
        if divisor is not None:
            values = values / divisor   # worker average, per entry (IEEE)
        order = (jnp.arange(n, dtype=i32) << 1) | flags.astype(i32)
        si, so, sv = jax.lax.sort(
            (jnp.concatenate([indices.astype(i32),
                              jnp.full((pad,), jnp.iinfo(i32).max, i32)]),
             jnp.concatenate([order, jnp.zeros((pad,), i32)]),
             jnp.concatenate([values, jnp.zeros((pad,), values.dtype)])),
            num_keys=2, is_stable=False)
    with _trace.phase("apply", part="stage"):
        return _window_maps(si, so, sv, total, nblocks, max_dup)


def _window_maps(si, so, sv, total: int, nblocks: int, max_dup):
    """What :func:`_sorted_pairs` makes of the sorted arrays: the
    duplicate fold and the scalar-prefetch maps (its docstring)."""
    i32 = jnp.int32
    if max_dup != 1:  # dgclint: ok[tracer-branch] — static by contract (the engine passes the Python world size)
        same = jnp.concatenate([jnp.zeros((1,), bool), si[1:] == si[:-1]])

        def fold(run):
            return jnp.where(same, jnp.concatenate(
                [jnp.zeros((1,), sv.dtype), run[:-1]]) + sv, sv)

        def fold_changed(state):
            nxt = fold(state[0])
            # bit compare: a NaN must not keep the loop alive
            return nxt, jnp.any(
                jax.lax.bitcast_convert_type(nxt, i32)
                != jax.lax.bitcast_convert_type(state[0], i32))

        if max_dup is None:
            run, _ = jax.lax.while_loop(lambda s: s[1], fold_changed,
                                        (sv, jnp.ones((), bool)))
        else:
            run = sv
            for _ in range(max_dup - 1):
                run = fold(run)
        last = jnp.concatenate([~same[1:], jnp.ones((1,), bool)])
        sv = jnp.where(last, run, jnp.zeros((), sv.dtype))

    rows = (nblocks * _APPLY_WPB, _LANE)
    sk = si.reshape(rows)
    kmin, kmax = sk[:, 0], sk[:, _LANE - 1]
    nchunks = -(-total // _APPLY_CHUNK)
    starts = jnp.arange(nchunks + 1, dtype=i32) * _APPLY_CHUNK
    cw0 = _count_below(kmax, starts[:-1])
    cw1 = _count_below(kmin, starts[1:])
    b0 = cw0 // _APPLY_WPB
    pages_per = jnp.maximum((cw1 - 1) // _APPLY_WPB, b0) - b0 + 1
    page_start = jnp.concatenate(
        [jnp.zeros((1,), i32), jnp.cumsum(pages_per).astype(i32)])
    npages = nchunks + nblocks                   # static capacity bound
    pageid = jnp.arange(npages, dtype=i32)
    page_chunk = jnp.clip(
        jnp.searchsorted(page_start, pageid, side="right",
                         method="compare_all").astype(i32) - 1,
        0, nchunks - 1)
    within = pageid - page_start[page_chunk]
    used = pageid < page_start[-1]
    page_block = jnp.where(used, b0[page_chunk] + within, nblocks - 1)
    first = (used & (within == 0)).astype(i32)
    return (page_chunk, page_block, first, cw0, cw1, kmin, kmax,
            sk, sv.reshape(rows), (so & 1).reshape(rows), npages)


@_trace.phased("apply")
def payload_apply_bits(values, indices, flags, total: int,
                       bits_donor=None, out_total=None, max_dup=None):
    """Fused apply epilogue: decompress scatter-add + transmit-record
    pack in ONE streamed pass over the flat [total] buffer.

    ``values``/``indices``/``flags`` are the flattened gathered payload
    ([W * payload]; values already worker-averaged): ``acc[idx] += v``
    for every entry, and the packed transmit bit set for entries with
    ``flags`` nonzero (the engine flags the LOCAL worker's non-sentinel
    entries, reproducing :func:`pack_sent_bits` on the local indices).

    Pair-scale work is one ``lax.sort`` and elementwise passes
    (:func:`_sorted_pairs`): no payload-sized scatter, gather or
    argsort. The kernel walks the buffer chunk by chunk, takes each
    chunk's pairs straight from the sorted arrays (a BlockSpec indexed
    by a scalar-prefetched block number) and expands them 128 at a time
    by vector compares and one-hot matrix products
    (:func:`_payload_apply_kernel`), so the flat buffer is written
    exactly once, with no zero-fill before it, and the transmit record
    is built in the same visit. ``bits_donor`` (the PREVIOUS step's dead
    ``sent_bits`` buffer) is donated via ``input_output_aliases`` so the
    record is rebuilt in place; the kernel never reads it.

    ``out_total`` (>= ``total``, lane-aligned) sizes ``acc`` for a
    caller that places more behind the [total] it asked for (the
    engine's dense tail): ``acc[total:]`` is NOT defined — the caller
    overwrites it in place, which costs no buffer-sized copy where a
    ``concatenate`` behind a custom call's output would. ``max_dup``
    (static) bounds how often one coordinate may occur with a non-zero
    value; None makes no assumption.

    Numerics: bitwise :func:`payload_apply_bits_reference` for unique
    real indices; cross-worker duplicates sum left to right in payload
    order (the order of a sequential scatter-add; XLA:TPU's own order is
    unspecified, so there the two agree to f32 rounding). A non-finite
    value poisons its sub-block's lane (0 * inf in the products), not
    its coordinate alone. f32 values and int32 indices only (the engine
    gates). Returns ``(acc [out_total or total], bits
    [num_sent_words(total)])``."""
    return _payload_apply_call("payload_apply_bits", values, indices, flags,
                               total, bits_donor, None, out_total, max_dup)


def _apply_staging(values, indices, flags, total: int, bits_donor,
                   divisor, max_dup):
    """What the apply-pass kernels share before their launch: the
    contract's asserts, :func:`_sorted_pairs`, the donated record as
    lane rows, and the BlockSpecs of a block of sorted pairs, of a
    chunk of the flat buffer and of its transmit words (index maps over
    the grid step and the prefetched ``page_chunk`` / ``page_block``)."""
    n = values.shape[0]
    assert total % _LANE == 0 and total + _APPLY_CHUNK < 2 ** 31, total  # dgclint: ok[tracer-branch] — buffer lengths are static
    assert indices.shape == (n,) and flags.shape == (n,)
    assert values.dtype == jnp.float32, values.dtype
    brows = num_sent_words(total) // _LANE
    *maps, sk, sv, sf, npages = _sorted_pairs(
        values, indices, flags, total, divisor, max_dup)
    with _trace.phase("apply", part="stage"):
        if bits_donor is None:
            bits_donor = jnp.zeros((brows, _LANE), jnp.int32)
        else:
            assert bits_donor.shape == (brows * _LANE,), bits_donor.shape
            bits_donor = bits_donor.reshape(brows, _LANE)
    pspec = pl.BlockSpec((_APPLY_WPB, _LANE),
                         lambda p, pc, pb, *_: (pb[p], 0),
                         memory_space=pltpu.VMEM)
    cspec = pl.BlockSpec((_CHUNK_ROWS, _LANE),
                         lambda p, pc, *_: (pc[p], 0),
                         memory_space=pltpu.VMEM)
    bspec = pl.BlockSpec((_CHUNK_ROWS // 32, _LANE),
                         lambda p, pc, *_: (pc[p], 0),
                         memory_space=pltpu.VMEM)
    return maps, (sk, sv, sf), npages, bits_donor, (pspec, cspec, bspec)


def _payload_apply_call(name: str, values, indices, flags, total: int,
                        bits_donor, divisor, out_total, max_dup):
    """Shared staging + launch of the apply-epilogue kernels
    (:func:`payload_apply_bits` and :func:`dgc_apply_rows` differ only
    in the static divisor of the staging and the ``name`` their device
    events carry)."""
    out_total = total if out_total is None else out_total
    assert total <= out_total and out_total % _LANE == 0, (total, out_total)  # dgclint: ok[tracer-branch] — buffer lengths are static
    maps, pairs, npages, bits_donor, (pspec, cspec, bspec) = _apply_staging(
        values, indices, flags, total, bits_donor, divisor, max_dup)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(maps),
        grid=(npages,),
        in_specs=[
            pspec, pspec, pspec,
            pl.BlockSpec(memory_space=pl.ANY),        # bits donor
        ],
        out_specs=(cspec, bspec),
    )
    acc, bits = pl.pallas_call(
        _payload_apply_kernel,
        grid_spec=grid_spec,
        out_shape=(jax.ShapeDtypeStruct((out_total // _LANE, _LANE),
                                        values.dtype),
                   jax.ShapeDtypeStruct(bits_donor.shape, jnp.int32)),
        # the dead previous-step record is rebuilt in place
        input_output_aliases={len(maps) + 3: 1},
        interpret=_interpret(),
        name=name,
    )(*maps, *pairs, bits_donor)
    return acc.reshape(-1), bits.reshape(-1)


@_trace.phased("apply")
def payload_update_bits(values, indices, flags, total: int, state, rule,
                        scalars=(), bits_donor=None, max_dup=None):
    """:func:`payload_apply_bits` for a caller whose next step is an
    elementwise rule over the gradient and its own flat state: the rule
    runs INSIDE the pass, on each chunk while it is in VMEM, so the
    ``[total]`` gradient is never written and never read back.

    ``state`` is a tuple of f32 flat buffers of one length ``>= total``
    (lane-aligned), each an input ALIASED to the output of the same
    position: donated, read once and written once, in place.
    ``rule(g, idx, scalars, *blocks) -> new blocks`` is traced into the
    kernel and sees VMEM values only: ``g`` the chunk's gradient as
    :func:`payload_apply_bits` would have written it (same staging,
    same one-hot products, same left-to-right duplicate fold), ``idx``
    the int32 flat coordinates of the slab, ``scalars`` the 0-d
    ``scalars`` (f32 or int32, prefetched with the window maps) and
    ``blocks`` the state at those coordinates. Whatever lies at or past
    ``total`` keeps its values: a tail the caller updates itself.

    Returns ``(new_state, bits [num_sent_words(total)])``; the bits are
    those of :func:`payload_apply_bits`."""
    nstate = len(state)
    size = state[0].shape[0]
    assert total <= size and size % _LANE == 0, (total, size)  # dgclint: ok[tracer-branch] — buffer lengths are static
    assert all(s.shape == (size,) and s.dtype == jnp.float32 for s in state)  # dgclint: ok[tracer-branch] — shapes and dtypes are static
    maps, pairs, npages, bits_donor, (pspec, cspec, bspec) = _apply_staging(
        values, indices, flags, total, bits_donor, None, max_dup)
    # a chunk's rule runs on its last page: the step before another
    # chunk opens, and the grid's last (pad pages revisit the last chunk)
    with _trace.phase("apply", part="stage"):
        last = jnp.concatenate([maps[2][1:], jnp.ones((1,), jnp.int32)])
        prefetch = (*maps, last, *(jnp.reshape(s, (1,)) for s in scalars))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(npages,),
        in_specs=[
            pspec, pspec, pspec,
            pl.BlockSpec(memory_space=pl.ANY),        # bits donor
            *[cspec] * nstate,
        ],
        out_specs=(*[cspec] * nstate, bspec),
        scratch_shapes=[pltpu.VMEM((_CHUNK_ROWS, _LANE), jnp.float32)],
    )
    *new, bits = pl.pallas_call(
        functools.partial(_payload_update_kernel, rule, total, nstate),
        grid_spec=grid_spec,
        out_shape=(*[jax.ShapeDtypeStruct((size // _LANE, _LANE),
                                          jnp.float32)] * nstate,
                   jax.ShapeDtypeStruct(bits_donor.shape, jnp.int32)),
        # the state moves in place; the dead previous-step record is
        # rebuilt in place
        input_output_aliases={
            len(prefetch) + 3: nstate,
            **{len(prefetch) + 4 + i: i for i in range(nstate)}},
        interpret=_interpret(),
        name="payload_update_bits",
    )(*prefetch, *pairs, bits_donor,
      *(s.reshape(size // _LANE, _LANE) for s in state))
    return tuple(x.reshape(-1) for x in new), bits.reshape(-1)


def dgc_apply_rows_reference(values, indices, flags, total: int,
                             divisor=None):
    """jnp reference of :func:`dgc_apply_rows`: divide the wire by the
    worker count, then the unfused scatter-add + transmit-record
    epilogue (:func:`payload_apply_bits_reference`)."""
    if divisor is not None:
        values = values / jnp.asarray(divisor, values.dtype)
    return payload_apply_bits_reference(values, indices, flags, total)


@_trace.phased("apply")
def dgc_apply_rows(values, indices, flags, total: int, bits_donor=None,
                   divisor=None, out_total=None, max_dup=None):
    """Apply megakernel: unpack → decompress → scatter-apply → sent-bits
    record in ONE streamed pass — :func:`payload_apply_bits` with the
    worker-average divide folded into its pair-scale staging (one
    elementwise pass with the duplicate fold, :func:`_sorted_pairs`),
    so the divided [W * payload] wire is never a step of its own.

    ``divisor`` is static (None = sum semantics, no divide traced —
    the program of :func:`payload_apply_bits`). Per-entry IEEE
    division by the same f32 operand makes the applied values bitwise
    the unfused path's. Same staging, same kernel, same donor aliasing;
    returns ``(acc, bits [num_sent_words(total)])`` bitwise
    :func:`dgc_apply_rows_reference` under unique real indices."""
    if divisor is not None:
        divisor = float(divisor)  # dgclint: ok[host-sync] — static by contract (the engine passes the Python world size), never a tracer
    return _payload_apply_call("dgc_apply_rows", values, indices, flags,
                               total, bits_donor, divisor, out_total,
                               max_dup)


# ------------------------------------------------------------------ #
# opaque identity view                                               #
# ------------------------------------------------------------------ #

def _identity_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def _opaque_copy(x: jax.Array) -> jax.Array:
    """Pallas identity copy — a buffer XLA cannot trace back to its
    source (custom calls are opaque to the simplifier)."""
    n = x.size
    flat = x.reshape(-1)
    pad = (-n) % (_SUBLANE * _LANE)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    rows = (n + pad) // _LANE
    block_rows = min(_CHUNK_ROWS, rows)
    spec = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _identity_kernel,
        grid=(pl.cdiv(rows, block_rows),),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), flat.dtype),
        in_specs=[spec], out_specs=spec,
        interpret=_interpret(),
        name="opaque_view",
    )(flat.reshape(rows, _LANE)).reshape(-1)
    return (out[:n] if pad else out).reshape(x.shape)


@jax.custom_vjp
def opaque_view(x: jax.Array) -> jax.Array:
    """Identity with a REAL buffer boundary, for flat-buffer views whose
    base offset divides their trailing-dims product.

    Motivation (r5 device profile + optimized-HLO inspection at VGG-16):
    under XLA's auto-bf16 conv precision, a weight view
    ``flat[base:base+numel].reshape(shape)`` whose ``base`` is a multiple
    of ``prod(shape[1:])`` lets the simplifier rewrite
    ``convert(slice(P))`` as ``slice(reshape(convert(P)))`` — and it
    then materializes the bf16 convert over the ENTIRE [P] parameter
    buffer to extract one tensor (two such whole-buffer converts, 2.9
    ms/step at VGG: 834 MB of traffic each for a 147 KB conv2 slice and
    a 67 MB fc2 slice; the dense arm fuses the same converts into its
    convolutions). ``optimization_barrier`` does NOT stop the rewrite —
    barriers are stripped before the late backend pass that forms these
    convert-reshapes (the optimized HLO contains no opt-barrier ops; the
    fused-apply epilogue's barrier-free lowering is pinned by the
    ``fused-epilogue-no-opt-barriers`` contract in
    ``dgc_tpu/analysis/suite.py``). A
    custom call is never looked through, so the per-tensor copy this
    kernel pays (proportional to the TENSOR, ~0.2 ms for fc2) replaces
    the whole-buffer converts, and the convert of its output fuses into
    each convolution exactly like the dense build.

    Prefer :func:`opaque_view_from` when the view's geometry allows it —
    this form's pallas operand is itself a slice of the flat buffer,
    which XLA materializes (a second tensor-sized copy; measured 1.25
    ms/step for a 411 MB tensor).

    The backward is the identity on the cotangent (no kernel): gradients
    flow through unchanged, so both train-step arms differentiate the
    same function.
    """
    return _opaque_copy(x)


def _opaque_fwd(x):
    return _opaque_copy(x), None


def _opaque_bwd(_, g):
    return (g,)


opaque_view.defvjp(_opaque_fwd, _opaque_bwd)


def opaque_view_eligible(total: int, base: int, numel: int) -> bool:
    """Whether :func:`opaque_view_from` can stream the view straight out
    of the flat buffer: everything tile-aligned so the BlockSpec index
    map lands on whole blocks (no operand slice, no copy beyond the
    kernel's own output)."""
    return (total % _LANE == 0 and base % (_SUBLANE * _LANE) == 0
            and numel % (_SUBLANE * _LANE) == 0
            and numel > 0 and base + numel <= total)


def opaque_view_from(flat: jax.Array, base: int, numel: int) -> jax.Array:
    """:func:`opaque_view` of ``flat[base:base+numel]`` WITHOUT the
    operand slice: the kernel reads the region directly from the full
    flat buffer through an offset BlockSpec index map, so the only
    traffic is one read + one write of the TENSOR (the sliced form pays
    a second materialized copy for its pallas operand). Caller must
    check :func:`opaque_view_eligible`. Backward scatters the cotangent
    back into a zero [total] buffer via ``dynamic_update_slice`` — the
    exact transpose of the slice this op replaces, which XLA fuses into
    the surrounding gradient pack."""
    assert opaque_view_eligible(flat.shape[0], base, numel), (
        flat.shape, base, numel)
    return _opaque_from(flat, base, numel, flat.shape[0])


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def _opaque_from(flat, base, numel, total):
    rows = numel // _LANE
    base_blk = base // _LANE
    block_rows = math.gcd(math.gcd(rows, base_blk), _CHUNK_ROWS)
    spec_in = pl.BlockSpec(
        (block_rows, _LANE),
        lambda i, _b=base_blk // block_rows: (_b + i, 0),
        memory_space=pltpu.VMEM)
    spec_out = pl.BlockSpec((block_rows, _LANE), lambda i: (i, 0),
                            memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _identity_kernel,
        grid=(rows // block_rows,),
        out_shape=jax.ShapeDtypeStruct((rows, _LANE), flat.dtype),
        in_specs=[spec_in], out_specs=spec_out,
        interpret=_interpret(),
        name="opaque_view_from",
    )(flat.reshape(-1, _LANE))
    return out.reshape(-1)


def _ovf_fwd(flat, base, numel, total):
    return _opaque_from(flat, base, numel, total), None


def _ovf_bwd(base, numel, total, _, g):
    return (jax.lax.dynamic_update_slice(
        jnp.zeros((total,), g.dtype), g, (base,)),)


_opaque_from.defvjp(_ovf_fwd, _ovf_bwd)


# ------------------------------------------------------------------ #
# placement into an aligned slot of a flat buffer                    #
# ------------------------------------------------------------------ #

# appended here, not in the list at the top: a line more up there moves
# every Mosaic body's source locations, which are in the lowered text
__all__ += ["place_rows", "place_rows_eligible"]

#: bytes of one block of :func:`place_rows` (the tensor's rows in, the
#: same bytes of the flat buffer out; both double-buffered: 4x this of
#: VMEM)
_PLACE_BLOCK_BYTES = 2 << 20


def place_rows_eligible(total: int, base: int, rows: int, cols: int) -> bool:
    """Whether :func:`place_rows` can put a [rows, cols] float32 tensor at
    ``flat[base : base + rows * cols]`` of a [total] buffer: the view of
    the tensor is whole (8, 128) tiles, the slot starts and ends on a
    tile of the buffer's [total / 128, 128] view, and eight rows fit a
    block."""
    numel = rows * cols
    return (rows > 0 and cols > 0 and cols % _LANE == 0
            and rows % _SUBLANE == 0
            and total % (_SUBLANE * _LANE) == 0
            and base % (_SUBLANE * _LANE) == 0 and base + numel <= total
            and _SUBLANE * cols * 4 <= _PLACE_BLOCK_BYTES)


def _place_kernel(x_ref, *refs):
    # row r's 128-lane chunk j is row r * chunks + j of the buffer's
    # [total / 128, 128] view: one strided sublane store a chunk
    o_ref = refs[-1]
    block_rows, cols = x_ref.shape
    chunks = cols // _LANE
    for j in range(chunks):
        o_ref[pl.ds(j, block_rows, stride=chunks), :] = (
            x_ref[:, j * _LANE:(j + 1) * _LANE])


def place_rows(x: jax.Array, base: int, total: int,
               into: jax.Array = None) -> jax.Array:
    """``into`` with ``into[base : base + x.size] = x.reshape(-1)``,
    written in ONE pass that reads the 2-D ``x`` in the (8, 128) tiles
    its producer wrote and writes the slot in place: the mirror image of
    :func:`opaque_view_from`, which streams a tensor OUT of an aligned
    slot. ``into`` (flat [total], aliased to the result) may be None:
    the call then CREATES the buffer and every slot outside
    [base, base + x.size) is undefined, so the caller writes each of
    them exactly once. A ``jnp.concatenate`` over the same tensor costs
    two passes on the chip: XLA relays the tiles row-major first (its
    1-D form), then copies the 1-D operand into the result at half the
    chip's rate (PERF.md §6, PR 43). Caller must check
    :func:`place_rows_eligible`. Bitwise a copy."""
    rows, cols = x.shape
    assert (x.dtype.itemsize == 4
            and place_rows_eligible(total, base, rows, cols)), (
        x.dtype, x.shape, base, total)
    chunks = cols // _LANE
    block_rows = _SUBLANE
    while (rows % (2 * block_rows) == 0
           and 2 * block_rows * cols * 4 <= _PLACE_BLOCK_BYTES):
        block_rows *= 2
    out_rows = block_rows * chunks
    base_row = base // _LANE
    in_specs = [pl.BlockSpec((block_rows, cols), lambda i: (i, 0),
                             memory_space=pltpu.VMEM)]
    args = [x]
    if into is not None:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(into.reshape(total // _LANE, _LANE))
    # element-indexed: the slot starts on a tile, not on a block
    out_spec = pl.BlockSpec(
        (pl.Element(out_rows), pl.Element(_LANE)),
        lambda i: (pl.multiple_of(base_row + i * out_rows, _SUBLANE), 0),
        memory_space=pltpu.VMEM)
    out = pl.pallas_call(
        _place_kernel,
        grid=(rows // block_rows,),
        out_shape=jax.ShapeDtypeStruct((total // _LANE, _LANE), x.dtype),
        in_specs=in_specs, out_specs=out_spec,
        input_output_aliases={1: 0} if into is not None else {},
        interpret=_interpret(),
        name="place_rows",
    )(*args)
    return out.reshape(-1)

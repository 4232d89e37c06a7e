"""Pallas kernels (dgc_tpu.ops.kernels) must match their jnp reference
implementations (SURVEY.md §7 item 6 contract; elementwise kernels to one
ULP — FMA contraction — and integer counts exactly). On CPU the kernels run
in interpreter mode — same program the TPU compiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgc_tpu.ops import kernels


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("n", [1, 127, 128, 1024, 65536 + 3, 272474])
def test_fused_compensate_matches_reference(n, nesterov):
    rng = np.random.RandomState(n)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.float32)
    v = jnp.asarray(rng.randn(n), jnp.float32)
    om, ov = kernels.fused_compensate(g, m, v, 0.9, nesterov)
    rm, rv = kernels.fused_compensate_reference(g, m, v, 0.9, nesterov)
    # FMA contraction in the kernel differs by ~1 ULP of the input
    # scale; vec+mmt can cancel, so absolute tolerance covers that scale
    np.testing.assert_allclose(np.asarray(om), np.asarray(rm),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ov), np.asarray(rv),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("n", [127, 2048, 65536 + 3])
def test_fused_compensate_bf16_state(n, nesterov):
    """bf16 error-feedback state: kernel output must match the jnp
    reference BITWISE (one f32-math pass, one round-to-nearest per stored
    value — no FMA ambiguity survives the bf16 rounding at these
    magnitudes), and must equal the all-f32 result after rounding the
    inputs up/down at the same points."""
    rng = np.random.RandomState(n)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n), jnp.bfloat16)
    om, ov = kernels.fused_compensate(g, m, v, 0.9, nesterov)
    rm, rv = kernels.fused_compensate_reference(g, m, v, 0.9, nesterov)
    assert om.dtype == jnp.bfloat16 and ov.dtype == jnp.bfloat16
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(om), f32(rm), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(f32(ov), f32(rv), rtol=1e-2, atol=1e-2)
    # the f32-math contract: compute in f32 from the upcast state, round
    # the outputs once
    em, ev = kernels.fused_compensate_reference(
        g, m.astype(jnp.float32), v.astype(jnp.float32), 0.9, nesterov)
    np.testing.assert_array_equal(f32(rm), f32(em.astype(jnp.bfloat16)))
    np.testing.assert_array_equal(f32(rv), f32(ev.astype(jnp.bfloat16)))


@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_compensate_masked_bf16_state(nesterov):
    """Masked variant with bf16 state: matches its reference and the
    eager mask-then-compensate composition at bf16 precision."""
    n = 2048 + 640
    rng = np.random.RandomState(5)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n), jnp.bfloat16)
    sent = jnp.asarray(rng.rand(n) < 0.3, jnp.float32)
    om, ov = kernels.fused_compensate_masked(g, m, v, sent, 0.9, nesterov,
                                             True)
    rm, rv = kernels.fused_compensate_masked_reference(
        g, m, v, sent, 0.9, nesterov, True)
    assert om.dtype == jnp.bfloat16 and ov.dtype == jnp.bfloat16
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(om), f32(rm), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(f32(ov), f32(rv), rtol=1e-2, atol=1e-2)
    keep = kernels.keep_from_sent(sent).astype(jnp.bfloat16)
    em, ev = kernels.fused_compensate_reference(g, m * keep, v * keep,
                                                0.9, nesterov)
    np.testing.assert_array_equal(f32(rm), f32(em))
    np.testing.assert_array_equal(f32(rv), f32(ev))


@pytest.mark.parametrize("momentum_masking", [False, True])
@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("n", [127, 1024, 65536 + 3])
def test_fused_compensate_masked_matches_reference(n, nesterov,
                                                   momentum_masking):
    """The mask-on-read kernel body must run (interpret mode) and match its
    reference across all nesterov/momentum_masking combinations, and the
    combined op must equal eager mask-then-compensate."""
    rng = np.random.RandomState(n + 7)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.float32)
    v = jnp.asarray(rng.randn(n), jnp.float32)
    # sent = transmit counts (0 = keep); keep = (sent == 0)
    sent = jnp.asarray(rng.rand(n) < 0.3, jnp.float32)
    keep = kernels.keep_from_sent(sent)
    om, ov = kernels.fused_compensate_masked(g, m, v, sent, 0.9, nesterov,
                                             momentum_masking)
    rm, rv = kernels.fused_compensate_masked_reference(
        g, m, v, sent, 0.9, nesterov, momentum_masking)
    np.testing.assert_allclose(np.asarray(om), np.asarray(rm),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ov), np.asarray(rv),
                               rtol=1e-6, atol=1e-6)
    # deferred == eager: masking the buffers first then compensating
    em, ev = kernels.fused_compensate_reference(
        g, m * keep if momentum_masking else m, v * keep, 0.9, nesterov)
    np.testing.assert_allclose(np.asarray(om), np.asarray(em),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ov), np.asarray(ev),
                               rtol=1e-6, atol=1e-6)


def _random_indices(rng, n, frac=0.01):
    k = max(1, int(n * frac))
    return jnp.asarray(rng.choice(n, k, replace=False).astype(np.int32))


@pytest.mark.parametrize("n", [4096, 3 * 4096, 65536 + 2048])
def test_pack_sent_bits_roundtrip(n):
    """pack -> unpack must reproduce the transmitted set exactly,
    including the half-aligned tail case (n % 4096 == 2048: phantom rows
    in the last word group never get bits)."""
    rng = np.random.RandomState(n)
    idx = _random_indices(rng, n, 0.03)
    bits = kernels.pack_sent_bits(idx, n)
    assert bits.dtype == jnp.int32
    assert bits.shape == (kernels.num_sent_words(n),)
    keep = np.asarray(kernels.keep_from_bits(bits, n))
    expect = np.ones((n,), np.float32)
    expect[np.asarray(idx)] = 0.0
    np.testing.assert_array_equal(keep, expect)


def test_pack_sent_bits_drops_sentinel():
    """Padded payload slots all carry the sentinel index; repeated
    single-bit adds there would carry into neighboring rows' bits, so
    the sentinel must be dropped outright."""
    n = 4096
    sentinel = 130
    idx = jnp.asarray([5, sentinel, sentinel, sentinel, 700], jnp.int32)
    bits = kernels.pack_sent_bits(idx, n, sentinel=sentinel)
    keep = np.asarray(kernels.keep_from_bits(bits, n))
    assert keep[5] == 0.0 and keep[700] == 0.0
    assert keep[sentinel] == 1.0              # dropped, not recorded
    assert keep.sum() == n - 2


@pytest.mark.parametrize("momentum_masking", [False, True])
@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("n", [4096, 2 * 4096 + 2048, 65536])
def test_fused_compensate_bits_matches_masked(n, nesterov,
                                              momentum_masking):
    """The bit-packed kernel must equal its jnp reference AND the f32
    count-vector kernel on the same transmitted set (the packed record
    replaces the count vector bitwise)."""
    rng = np.random.RandomState(n + 11)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.float32)
    v = jnp.asarray(rng.randn(n), jnp.float32)
    idx = _random_indices(rng, n, 0.02)
    sent = jnp.zeros((n,), jnp.float32).at[idx].add(1.0)
    bits = kernels.pack_sent_bits(idx, n)
    om, ov = kernels.fused_compensate_bits(g, m, v, bits, 0.9, nesterov,
                                           momentum_masking)
    rm, rv = kernels.fused_compensate_bits_reference(
        g, m, v, bits, 0.9, nesterov, momentum_masking)
    np.testing.assert_allclose(np.asarray(om), np.asarray(rm),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ov), np.asarray(rv),
                               rtol=1e-6, atol=1e-6)
    em, ev = kernels.fused_compensate_masked_reference(
        g, m, v, sent, 0.9, nesterov, momentum_masking)
    np.testing.assert_array_equal(np.asarray(rm), np.asarray(em))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(ev))


@pytest.mark.parametrize("nesterov", [False, True])
def test_fused_compensate_bits_bf16_state(nesterov):
    """Bit-packed masking with the narrow bf16 error-feedback state:
    matches its reference bitwise and the count-vector reference."""
    n = 4096 + 2048
    rng = np.random.RandomState(17)
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.bfloat16)
    v = jnp.asarray(rng.randn(n), jnp.bfloat16)
    idx = _random_indices(rng, n, 0.05)
    sent = jnp.zeros((n,), jnp.float32).at[idx].add(1.0)
    bits = kernels.pack_sent_bits(idx, n)
    om, ov = kernels.fused_compensate_bits(g, m, v, bits, 0.9, nesterov,
                                           True)
    rm, rv = kernels.fused_compensate_bits_reference(
        g, m, v, bits, 0.9, nesterov, True)
    assert om.dtype == jnp.bfloat16 and ov.dtype == jnp.bfloat16
    f32 = lambda x: np.asarray(x, np.float32)
    np.testing.assert_allclose(f32(om), f32(rm), rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(f32(ov), f32(rv), rtol=1e-2, atol=1e-2)
    em, ev = kernels.fused_compensate_masked_reference(
        g, m, v, sent, 0.9, nesterov, True)
    np.testing.assert_array_equal(f32(rm), f32(em))
    np.testing.assert_array_equal(f32(rv), f32(ev))


@pytest.mark.parametrize("shape", [(1, 64), (3, 128), (5, 1000), (16, 4096)])
def test_ladder_counts_matches_reference(shape):
    rng = np.random.RandomState(shape[1])
    imp = np.abs(rng.randn(*shape)).astype(np.float32)
    # padding slots, as the engine produces them
    imp[:, -3:] = -1.0
    thr = np.abs(rng.randn(shape[0])).astype(np.float32) * 0.5
    got = kernels.ladder_counts(jnp.asarray(imp), jnp.asarray(thr), 0.8, 11)
    ref = kernels.ladder_counts_reference(jnp.asarray(imp), jnp.asarray(thr),
                                          0.8, 11)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_ladder_counts_zero_threshold():
    """All-zero gradients: thr == 0, every non-padded element passes every
    level (imp 0 >= 0), padding (-1) never counts."""
    imp = jnp.concatenate([jnp.zeros((2, 10)), -jnp.ones((2, 2))], axis=1)
    thr = jnp.zeros((2,))
    got = np.asarray(kernels.ladder_counts(imp, thr, 0.8, 5))
    assert (got == 10).all()


def test_ladder_adapt_matches_sequential_oracle():
    """The closed-form ladder pick must equal the reference's sequential
    adaptation loop (ops.adapt_threshold with resample=True) row by row."""
    from dgc_tpu.compression.flat import _ladder_adapt
    from dgc_tpu.ops import sparsify as ops

    rng = np.random.RandomState(7)
    R, N = 6, 2000
    imp = np.abs(rng.randn(R, N)).astype(np.float32)
    num_selects = np.full((R,), 20, np.float32)
    # thresholds engineered too high so adaptation must lower them by
    # varying amounts
    thr0 = np.array([np.sort(imp[r])[-3] for r in range(R)], np.float32)
    max_iters = 10

    got = np.asarray(_ladder_adapt(
        jnp.asarray(imp), jnp.asarray(thr0), jnp.asarray(num_selects),
        jnp.ones((R,), bool), 0.8, max_iters))

    for r in range(R):
        want = np.asarray(ops.adapt_threshold(
            jnp.asarray(imp[r]), jnp.asarray(thr0[r]), 20, 0.8, 1.3,
            max_iters, resample=True))
        # sequential loop multiplies cumulatively; ladder uses lb**i —
        # identical picks, float tolerance on the power
        np.testing.assert_allclose(got[r], want, rtol=1e-5)


def test_flat_sparsify_with_adaptation_transmits_enough():
    """End-to-end through the engine: a distribution that defeats the
    sampled threshold still transmits >= lower_bound * num_selects after
    ladder adaptation (the reference's adaptation goal)."""
    from dgc_tpu import DGCCompressor, DGCSGDMemory, DistributedOptimizer, dgc_sgd

    rng = np.random.RandomState(3)
    # heavy-tailed: strided samples overestimate the top-k threshold
    base = np.abs(rng.randn(64, 64)).astype(np.float32)
    base.reshape(-1)[rng.choice(4096, 40, replace=False)] *= 50.0
    params = {"w": jnp.asarray(base)}
    comp = DGCCompressor(0.01, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.01)
    comp.initialize([("w", params["w"])])
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    a = comp.attributes["w"]
    vec = np.zeros((layout.t_compressed,), np.float32)
    off = layout.offsets["w"]
    vec[off:off + layout.sizes["w"]] = base.reshape(-1)
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                         jax.random.PRNGKey(0))
    valid = np.asarray(idx) < layout.t_data
    assert valid.sum() >= int(0.8 * a.num_selects) - 1


@pytest.mark.parametrize("shape,k", [((8, 256), 1), ((8, 256), 37),
                                     ((5, 300), 10), ((16, 1024), 40),
                                     ((8, 128), 128)])
def test_topk_rows_matches_lax_top_k(shape, k):
    """topk_rows must equal jax.lax.top_k exactly: descending values, ties
    broken by first occurrence — on aligned and ragged shapes."""
    from dgc_tpu.ops.kernels import topk_rows

    rng = np.random.RandomState(7)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    v, i = topk_rows(x, k)
    v_ref, i_ref = jax.lax.top_k(x, k)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_topk_rows_tie_order():
    """Duplicated values must come out in ascending index order, exactly as
    lax.top_k orders them."""
    from dgc_tpu.ops.kernels import topk_rows

    x = jnp.asarray([[1.0, 3.0, 3.0, 0.0, 3.0, -1.0, 2.0, 2.0]] * 8,
                    jnp.float32)
    v, i = topk_rows(x, 6)
    v_ref, i_ref = jax.lax.top_k(x, 6)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_topk_rows_fallback_large():
    """Rows beyond the VMEM budget (or k > lane width) fall back to
    lax.top_k and stay correct."""
    from dgc_tpu.ops.kernels import topk_rows

    rng = np.random.RandomState(8)
    x = jnp.asarray(rng.randn(2, 2 * 1024 * 1024 // 8), jnp.float32)
    v, i = topk_rows(x, 5)
    v_ref, i_ref = jax.lax.top_k(x, 5)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    x2 = jnp.asarray(rng.randn(4, 512), jnp.float32)
    v2, i2 = topk_rows(x2, 200)       # k > lane width
    v2_ref, i2_ref = jax.lax.top_k(x2, 200)
    np.testing.assert_array_equal(np.asarray(v2), np.asarray(v2_ref))


def test_topk_rows_with_neg_inf_entries():
    """Rows containing real -inf values (and k reaching into them) must
    still match lax.top_k exactly: ascending-index extraction over the
    remaining -inf slots, no duplicate indices."""
    from dgc_tpu.ops.kernels import topk_rows

    ninf = -np.inf
    x = jnp.asarray([[5.0, ninf, 3.0, ninf, 1.0, 0.0, -1.0, 2.0]] * 8,
                    jnp.float32)
    v, i = topk_rows(x, 8)
    v_ref, i_ref = jax.lax.top_k(x, 8)
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    assert len(set(np.asarray(i)[0].tolist())) == 8  # no duplicates


def test_topk_rows_k_exceeding_cols_raises_like_lax():
    """cols < k <= lane width must not silently return pad indices — the
    guard delegates to lax.top_k, which raises."""
    from dgc_tpu.ops.kernels import topk_rows

    x = jnp.zeros((8, 100), jnp.float32)
    with pytest.raises(ValueError):
        topk_rows(x, 110)


@pytest.mark.parametrize("shape,k", [((3, 40), 5), ((8, 128), 128),
                                     ((5, 300), 7), ((12, 64), 64),
                                     ((1, 16), 3)])
def test_select_pack_rows_matches_reference(shape, k):
    """The fused threshold->select->pack kernel must match the unfused
    reference (masked |x| top_k + take_along_axis) bitwise: scores,
    signed values, AND column order — the wire format depends on all
    three."""
    from dgc_tpu.ops.kernels import (select_pack_rows,
                                     select_pack_rows_reference)

    rng = np.random.RandomState(11)
    x = jnp.asarray(rng.randn(*shape), jnp.float32)
    numels = jnp.asarray(
        rng.randint(max(1, k), shape[1] + 1, shape[0]), jnp.int32)
    s, v, i = select_pack_rows(x, numels, k)
    s_ref, v_ref, i_ref = select_pack_rows_reference(x, numels, k)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
    np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


def test_select_pack_rows_ragged_rows_never_select_pad():
    """Slots at/beyond a row's numel are structural zeros: even when every
    real entry is tiny, the kernel must keep selecting real columns (the
    masked importance is -1 there, below any |real| value >= 0)."""
    from dgc_tpu.ops.kernels import select_pack_rows

    x = jnp.full((4, 24), 1e-30, jnp.float32)
    numels = jnp.asarray([5, 24, 1, 8], jnp.int32)
    k = 4
    s, v, i = select_pack_rows(x, numels, k)
    i = np.asarray(i)
    numels_np = np.asarray(numels)
    for r in range(4):
        kr = min(k, int(numels_np[r]))
        assert (i[r, :kr] < numels_np[r]).all()


def test_select_pack_rows_bf16_values():
    """bf16 inputs recurse through the f32 path; returned signed values
    keep the input dtype and equal the gathered originals."""
    from dgc_tpu.ops.kernels import (select_pack_rows,
                                     select_pack_rows_reference)

    rng = np.random.RandomState(3)
    x = jnp.asarray(rng.randn(6, 48), jnp.bfloat16)
    numels = jnp.full((6,), 48, jnp.int32)
    s, v, i = select_pack_rows(x, numels, 9)
    s_ref, v_ref, i_ref = select_pack_rows_reference(x, numels, 9)
    assert v.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))
    np.testing.assert_array_equal(np.asarray(v.astype(jnp.float32)),
                                  np.asarray(v_ref.astype(jnp.float32)))


def test_select_pack_rows_large_k_stays_exact():
    """k past the lane width routes to the chunked multi-round kernel
    (NOT the reference — tests/test_megakernel.py asserts the
    non-delegation); past _MR_MAX_K the reference takes over. Both
    regimes stay exact."""
    from dgc_tpu.ops.kernels import (_MR_MAX_K, select_pack_rows,
                                     select_pack_rows_reference)

    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(2, 2048), jnp.float32)
    numels = jnp.asarray([2048, 1500], jnp.int32)
    for k in (200, _MR_MAX_K + 1):
        s, v, i = select_pack_rows(x, numels, k)
        s_ref, v_ref, i_ref = select_pack_rows_reference(x, numels, k)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s_ref))
        np.testing.assert_array_equal(np.asarray(v), np.asarray(v_ref))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i_ref))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_seg_top2_kernel_matches_reference(dtype):
    """seg_top2_candidates (interpret mode on CPU) == seg_top2_reference
    bitwise — the same compiled-vs-reference contract every other kernel
    carries (tpu_check.py re-asserts it compiled on the real chip). Runs
    the pallas_call path explicitly, since the engine picks the reference
    off-TPU and would otherwise leave the kernel body unexercised by CI.
    Covers base != 0 (BlockSpec offset arithmetic), multi-row, ties, a
    structural-zero tail, and the narrow (bf16) state input — both ends
    up-cast in the same place, so outputs are f32 and bitwise equal."""
    from dgc_tpu.ops import kernels

    span = kernels._SEG_BLOCKS * 128
    rng = np.random.RandomState(7)
    base, rows, cols = span, 2, 2 * span
    vec = rng.randn(base + rows * cols + span).astype(np.float32)
    vec[base + cols - span // 2:base + cols] = 0.0   # a zero tail region
    # force ties inside one segment: equal |values| at two blocks
    vec[base + 5 * 128 + 3] = 9.0
    vec[base + 9 * 128 + 3] = -9.0
    v2d = jnp.asarray(vec, dtype).reshape(-1, 128)
    cvk, cck = kernels.seg_top2_candidates(v2d, base, rows, cols)
    cvr, ccr = kernels.seg_top2_reference(v2d, base, rows, cols)
    assert cvk.dtype == jnp.float32 and cvr.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(cvk), np.asarray(cvr))
    np.testing.assert_array_equal(np.asarray(cck), np.asarray(ccr))
    # the tie resolved to the FIRST block (lax.top_k order) and the
    # second slot holds the other of the pair
    nseg = cols // span
    cv4 = np.asarray(cvk).reshape(rows, nseg, 2, 128)
    cc4 = np.asarray(cck).reshape(rows, nseg, 2, 128)
    assert cv4[0, 0, 0, 3] == 9.0 and cv4[0, 0, 1, 3] == -9.0
    assert cc4[0, 0, 0, 3] == 5 * 128 + 3
    assert cc4[0, 0, 1, 3] == 9 * 128 + 3


@pytest.mark.parametrize("nesterov,masking", [(False, True), (True, False)])
@pytest.mark.parametrize("sdt", [jnp.float32, jnp.bfloat16])
def test_fused_compensate_bits_cands_matches_composition(nesterov, masking,
                                                         sdt):
    """The fused compensate+candidates kernel (interpret mode on CPU) ==
    (fused_compensate_bits_reference, then seg_top2_reference over the
    stored velocity) bitwise — state updates AND candidates. Covers a
    grad buffer LONGER than the state (the engine passes the whole flat
    [P] so no [:T] operand slice is materialized), the bf16 state
    round-trip, and both compensate variants."""
    from dgc_tpu.ops import kernels

    span = kernels._SEG_BLOCKS * 128
    n = 3 * span                       # 3 complete segments
    rng = np.random.RandomState(11)
    grad = jnp.asarray(rng.randn(n + 2048).astype(np.float32))
    mmt = jnp.asarray(rng.randn(n).astype(np.float32), sdt)
    vec = jnp.asarray(rng.randn(n).astype(np.float32), sdt)
    idx = jnp.asarray(rng.choice(n, 500, replace=False).astype(np.int32))
    bits = kernels.pack_sent_bits(idx, n)

    om, ov, cv, ci = kernels.fused_compensate_bits_cands(
        grad, mmt, vec, bits, 0.9, nesterov, masking)
    # the state contract: bitwise the plain bits KERNEL this fused form
    # replaces (kernel-vs-jnp-reference parity for the compensate math is
    # the plain kernel's own test; at some sizes XLA CPU's fusion of the
    # nesterov multiply-add chain differs by ULPs between the two
    # programs, a pre-existing interpret-mode wobble that the engine
    # never sees: CPU runs the references, TPU runs the kernels and
    # tpu_check pins compiled==interpret)
    omr, ovr = kernels.fused_compensate_bits(
        grad[:n], mmt, vec, bits, 0.9, nesterov, masking)
    np.testing.assert_array_equal(np.asarray(om), np.asarray(omr))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(ovr))
    # candidates == the standalone kernel's over the STORED velocity,
    # viewed as one row spanning the whole region
    cvr, ccr = kernels.seg_top2_reference(ovr.reshape(-1, 128), 0, 1, n)
    nseg = n // span
    cv_flat = np.asarray(cv[:nseg]).reshape(1, -1)
    np.testing.assert_array_equal(cv_flat, np.asarray(cvr))
    # reference emits bucket-local columns; the fused kernel emits
    # per-segment block indices — recompose and compare
    lane = np.arange(128, dtype=np.int32)
    seg0 = (np.arange(nseg, dtype=np.int32)
            * kernels._SEG_BLOCKS)[None, :, None, None]
    cols = ((np.asarray(ci[:nseg]).reshape(1, nseg, 2, 128) + seg0) * 128
            + lane[None, None, None, :]).reshape(1, -1)
    np.testing.assert_array_equal(cols, np.asarray(ccr))


def test_fused_compensate_bits_cands_ragged_tail():
    """A state length that is NOT a whole number of segments: the state
    update must still be exact over all of [0, n); candidate segments
    fully inside the data must match the standalone reference (the
    straddling tail segment is unspecified and unused by the engine —
    eligible buckets end on segment boundaries)."""
    from dgc_tpu.ops import kernels

    span = kernels._SEG_BLOCKS * 128
    n = span + 16 * 128                # one complete segment + a tail
    rng = np.random.RandomState(3)
    grad = jnp.asarray(rng.randn(n).astype(np.float32))
    mmt = jnp.asarray(rng.randn(n).astype(np.float32))
    vec = jnp.asarray(rng.randn(n).astype(np.float32))
    bits = kernels.pack_sent_bits(
        jnp.asarray(rng.choice(n, 64, replace=False).astype(np.int32)), n)
    om, ov, cv, ci = kernels.fused_compensate_bits_cands(
        grad, mmt, vec, bits, 0.9, False, True)
    omr, ovr = kernels.fused_compensate_bits_reference(
        grad, mmt, vec, bits, 0.9, False, True)
    np.testing.assert_array_equal(np.asarray(om), np.asarray(omr))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(ovr))
    cvr, ccr = kernels.seg_top2_reference(ovr.reshape(-1, 128), 0, 1, span)
    np.testing.assert_array_equal(np.asarray(cv[0]).reshape(1, -1),
                                  np.asarray(cvr))
    lane = np.arange(128, dtype=np.int32)
    cols = (np.asarray(ci[0]).reshape(1, 2, 128) * 128
            + lane[None, None, :]).reshape(1, -1)
    np.testing.assert_array_equal(cols, np.asarray(ccr))


def test_seg_top2_eligible_bounds():
    """Eligibility rejects regions that would read past the buffer end
    (rows > 1 must be accounted for) and misaligned bases/widths."""
    from dgc_tpu.ops import kernels

    span = kernels._SEG_BLOCKS * 128
    blocks = (4 * span) // 128
    assert kernels.seg_top2_eligible(blocks, 0, span, rows=4)
    assert not kernels.seg_top2_eligible(blocks, 0, span, rows=5)
    assert not kernels.seg_top2_eligible(blocks, span + 128, span, rows=1)
    assert not kernels.seg_top2_eligible(blocks, 0, span + 128, rows=1)


def test_opaque_view_identity_and_grad():
    """opaque_view is a bitwise identity with an identity backward —
    the convert-hoisting guard must not change the differentiated
    function (training/step.py's guarded unpack)."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(3)
    for shape in [(3, 3, 64, 64), (13, 7), (1024,)]:
        x = jnp.asarray(rng.randn(*shape).astype(np.float32))
        np.testing.assert_array_equal(np.asarray(kernels.opaque_view(x)),
                                      np.asarray(x))
        g = jax.grad(lambda a: jnp.sum(kernels.opaque_view(a) ** 2))(x)
        np.testing.assert_array_equal(np.asarray(g), 2 * np.asarray(x))


def test_opaque_view_from_matches_slice():
    """opaque_view_from streams flat[base:base+numel] without an operand
    slice; forward is bitwise the slice, backward is its exact transpose
    (zeros + dynamic_update_slice), including under jit."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(4)
    total = 64 * 1024
    flat = jnp.asarray(rng.randn(total).astype(np.float32))
    for base, numel in [(0, 1024), (2048, 3 * 1024), (31 * 1024, 33 * 1024)]:
        assert kernels.opaque_view_eligible(total, base, numel)
        out = kernels.opaque_view_from(flat, base, numel)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(flat[base:base + numel]))
        g = jax.jit(jax.grad(
            lambda f: jnp.sum(kernels.opaque_view_from(f, base, numel) ** 2)
        ))(flat)
        ref = np.zeros(total, np.float32)
        ref[base:base + numel] = 2 * np.asarray(flat)[base:base + numel]
        np.testing.assert_array_equal(np.asarray(g), ref)
    # misalignment and overrun are rejected
    assert not kernels.opaque_view_eligible(total, 128, 1024)
    assert not kernels.opaque_view_eligible(total, total - 1024, 2048)


@pytest.mark.parametrize("total", [
    45 * 4096 + 2048,                       # single ragged chunk
    2 * 2048 * 128 + 37 * 4096 + 2048,      # multi-chunk, ragged tail
])
def test_payload_apply_bits_matches_reference(total):
    """The fused apply epilogue vs the jnp reference (the engine's XLA
    scatter pair): with unique real indices any scatter order agrees, so
    acc is BITWISE and the transmit record exact — including empty
    chunks, a stale (garbage) donated record buffer, and sentinel-style
    zero-value pad entries."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(11)
    n = 4000
    idx = jnp.asarray(rng.choice(total, n, replace=False).astype(np.int32))
    vals = jnp.asarray(rng.randn(n).astype(np.float32))
    flags = jnp.asarray((rng.rand(n) < 0.4).astype(np.int32))
    acc_r, bits_r = jax.jit(
        lambda v, i, f: kernels.payload_apply_bits_reference(
            v, i, f, total))(vals, idx, flags)
    acc_k, bits_k = jax.jit(
        lambda v, i, f: kernels.payload_apply_bits(
            v, i, f, total))(vals, idx, flags)
    np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_r))
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))

    # the donated previous-step record must never leak: fill it with
    # garbage and require the identical fresh record
    donor = jnp.asarray(rng.randint(
        -2**31, 2**31 - 1, size=kernels.num_sent_words(total),
        dtype=np.int64).astype(np.int32))
    acc_d, bits_d = jax.jit(
        lambda v, i, f, d: kernels.payload_apply_bits(
            v, i, f, total, bits_donor=d))(vals, idx, flags, donor)
    np.testing.assert_array_equal(np.asarray(acc_d), np.asarray(acc_r))
    np.testing.assert_array_equal(np.asarray(bits_d), np.asarray(bits_r))

    # sentinel-style pads: repeated index, zero value, flag 0 — no-ops
    sent = total - 1
    idx2 = jnp.concatenate([idx, jnp.full((300,), sent, jnp.int32)])
    v2 = jnp.concatenate([vals, jnp.zeros((300,), jnp.float32)])
    f2 = jnp.concatenate([flags, jnp.zeros((300,), jnp.int32)])
    acc_s, bits_s = jax.jit(
        lambda v, i, f: kernels.payload_apply_bits(
            v, i, f, total))(v2, idx2, f2)
    np.testing.assert_array_equal(np.asarray(acc_s), np.asarray(acc_r))
    np.testing.assert_array_equal(np.asarray(bits_s), np.asarray(bits_r))


_CHUNK = 2048 * 128


def _apply_pairs(case, W, rng):
    """[W, n] indices (unique within a worker), values, the sentinel and
    the buffer length of one case of the parity matrix."""
    total = 3 * _CHUNK + (2048 if case == "ragged" else 0)
    n, sentinel = 700, total - 1
    if case == "duplicates":
        # half of every worker's coordinates are worker 0's
        shared = rng.choice(total - 1, n, replace=False)
        idx = np.stack([np.where(
            rng.rand(n) < 0.5, shared,
            rng.choice(total - 1, n, replace=False)) for _ in range(W)])
        idx[0] = shared
        idx = np.stack([np.unique(r, return_index=True)[0][:600]
                        for r in idx])
    elif case == "empty_chunks":
        # everything lands in the LAST chunk: the others take no pair
        idx = (2 * _CHUNK + rng.choice(_CHUNK - 1, W * n, replace=False)
               ).reshape(W, n)
    elif case == "overfull_chunk":
        # the middle chunk holds more pairs than one 4096-pair block
        n = 4800 // W + 300
        idx = (_CHUNK + rng.choice(_CHUNK, W * n, replace=False)
               ).reshape(W, n)
    else:
        idx = rng.choice(total - 1, W * n, replace=False).reshape(W, n)
    vals = rng.randn(*idx.shape).astype(np.float32)
    if case == "sentinel":
        pad = rng.rand(*idx.shape) < 0.3
        idx, vals = np.where(pad, sentinel, idx), np.where(pad, 0, vals)
    return idx.astype(np.int32), vals.astype(np.float32), sentinel, total


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("case", ["unique", "duplicates", "empty_chunks",
                                  "overfull_chunk", "sentinel", "ragged"])
def test_apply_pass_parity(case, W):
    """The one-pass apply (sorted pairs -> one-hot products, values and
    transmit bits in one visit) against the XLA scatter pair, over the
    worker count and the shapes a payload takes. Values: bitwise for
    unique coordinates; duplicates are folded left to right in payload
    order, which is what ``np.add.at`` does (bitwise) and what the
    reference scatter gives to f32 rounding. Bits: bitwise
    ``pack_sent_bits`` of the local worker's indices, always. The
    donated record is garbage and is never read."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(31 + W)
    idx, vals, sentinel, total = _apply_pairs(case, W, rng)
    assert total % 4096 == (2048 if case == "ragged" else 0)
    me = W - 1
    flags = np.zeros(idx.shape, bool)
    flags[me] = idx[me] != sentinel
    donor = jnp.asarray(rng.randint(
        -2**31, 2**31 - 1, size=kernels.num_sent_words(total),
        dtype=np.int64).astype(np.int32))
    acc_k, bits_k = jax.jit(
        lambda v, i, f, d: kernels.payload_apply_bits(
            v, i, f, total, bits_donor=d))(
        jnp.asarray(vals.reshape(-1)), jnp.asarray(idx.reshape(-1)),
        jnp.asarray(flags.reshape(-1)), donor)
    acc_r, _ = kernels.payload_apply_bits_reference(
        jnp.asarray(vals.reshape(-1)), jnp.asarray(idx.reshape(-1)),
        jnp.asarray(flags.reshape(-1)), total)
    bits_r = kernels.pack_sent_bits(jnp.asarray(idx[me]), total,
                                    sentinel=sentinel)
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))
    if case == "duplicates":
        assert len(np.unique(idx)) < idx.size or W == 1
        np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                                   rtol=1e-6, atol=1e-7)
        acc_r = np.zeros(total, np.float32)
        np.add.at(acc_r, idx.reshape(-1), vals.reshape(-1))
    np.testing.assert_array_equal(np.asarray(acc_k), np.asarray(acc_r))
    if case == "empty_chunks":
        assert not np.asarray(acc_k[:2 * _CHUNK]).any()
        assert not np.asarray(bits_k[:2 * _CHUNK // 32]).any()


def test_apply_pass_places_a_tail_in_a_longer_buffer():
    """``out_total`` sizes the accumulator for a caller that writes its
    own tail behind [total]: the first ``total`` entries are the
    reference's whatever the length."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(5)
    total, out_total = _CHUNK + 6144, _CHUNK + 6144 + 2048
    idx = jnp.asarray(rng.choice(total, 900, replace=False).astype(np.int32))
    vals = jnp.asarray(rng.randn(900).astype(np.float32))
    flags = jnp.asarray(rng.rand(900) < 0.5)
    acc_k, bits_k = kernels.payload_apply_bits(vals, idx, flags, total,
                                               out_total=out_total)
    acc_r, bits_r = kernels.payload_apply_bits_reference(vals, idx, flags,
                                                         total)
    assert acc_k.shape == (out_total,)
    np.testing.assert_array_equal(np.asarray(acc_k[:total]),
                                  np.asarray(acc_r))
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))


def test_payload_apply_bits_duplicates_and_empty_chunks():
    """Cross-worker duplicate coordinates: the staged adds run in
    stable sorted order (payload order within a coordinate), summing the
    same contribution sets as the reference scatter — within one f32
    rounding; the record (an OR over unique local coordinates) stays
    exact. A chunk with no payload at all must come back all-zero."""
    from dgc_tpu.ops import kernels

    rng = np.random.RandomState(13)
    total = 3 * 2048 * 128
    base = rng.choice(4096, 500, replace=False)
    # worker-style duplication: same coordinates contributed 3x, plus a
    # block landing only in the LAST chunk, leaving the middle one empty
    idx = np.concatenate([base, base, base,
                          2 * 2048 * 128 + rng.choice(4096, 200,
                                                      replace=False)])
    vals = rng.randn(idx.size).astype(np.float32)
    flags = np.zeros(idx.size, np.int32)
    flags[:500] = 1
    idx, vals, flags = (jnp.asarray(idx.astype(np.int32)),
                        jnp.asarray(vals), jnp.asarray(flags))
    acc_r, bits_r = jax.jit(
        lambda v, i, f: kernels.payload_apply_bits_reference(
            v, i, f, total))(vals, idx, flags)
    acc_k, bits_k = jax.jit(
        lambda v, i, f: kernels.payload_apply_bits(
            v, i, f, total))(vals, idx, flags)
    np.testing.assert_allclose(np.asarray(acc_k), np.asarray(acc_r),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))
    # empty middle chunk: all-zero despite never receiving an entry
    mid = np.asarray(acc_k[2048 * 128:2 * 2048 * 128])
    assert not mid.any()


# ------------------------------------------------------------------ #
# the optimizer's rule inside the apply pass                         #
# ------------------------------------------------------------------ #

#: powers of two: a product by one is exact, so nothing here depends on
#: which multiply XLA:CPU's LLVM contracts into an FMA (it chose
#: fma(wd, p, m * buf) in the interpreted kernel and fma(m, buf, wd * p)
#: in the optimizer's fusion; tests/test_update_in_apply.py says more)
_EXACT = dict(momentum=0.5, weight_decay=2.0 ** -7)


def _exact_lr(count):
    return 0.125 * 0.5 ** count.astype(jnp.float32)


def _cutting_mask(total, size):
    """A ``flat.LayoutMask`` whose runs cut a slab, a chunk and the
    block's end, and reach into the tail."""
    from dgc_tpu.compression.flat import LayoutMask
    cuts = [0, 1000, 4096 + 77, _CHUNK + 64 * 128 + 5, 2 * _CHUNK - 3,
            total - 129, total + 100, size]
    mask = LayoutMask(size, jnp.int32,
                      [(a, b, k % 2 == 0)
                       for k, (a, b) in enumerate(zip(cuts, cuts[1:]))])
    assert mask.form == "runs" and len(mask.runs) == 4
    return mask


def _update_case(case, W, rng):
    """Pairs of one parity case and a [size] state behind them: a block
    of ``total`` coordinates that is not a whole number of chunks, and a
    tail the pass must leave alone."""
    idx, vals, sentinel, total = _apply_pairs(case, W, rng)
    total += 37 * 4096 + 2048
    size = total + 4096 + 128
    flags = np.zeros(idx.shape, bool)
    flags[W - 1] = idx[W - 1] != sentinel
    p = rng.randn(size).astype(np.float32)
    buf = rng.randn(size).astype(np.float32)
    return (jnp.asarray(vals.reshape(-1)), jnp.asarray(idx.reshape(-1)),
            jnp.asarray(flags.reshape(-1)), jnp.asarray(p),
            jnp.asarray(buf), total, size)


def _fused_and_unfused(opt, total, size, W, count):
    """The fused pass, and ``payload_apply_bits`` followed by the
    optimizer's own ``update`` and the add (today's two passes)."""
    from dgc_tpu.optim.sgd import SGDState

    rule = opt.rule

    @jax.jit
    def fused(v, i, f, p, b, donor):
        state = SGDState(count, b)
        return kernels.payload_update_bits(
            v, i, f, total, rule.blocks(state, p), rule.step,
            rule.scalars(state), bits_donor=donor, max_dup=W)

    @jax.jit
    def unfused(v, i, f, p, b, donor):
        acc, bits = kernels.payload_apply_bits(
            v, i, f, total, bits_donor=donor, out_total=size, max_dup=W)
        inside = jnp.arange(size) < total
        acc = jnp.where(inside, acc, 0.0)           # the tail is undefined
        upd, state = opt.update(acc, SGDState(count, b), p)
        return ((jnp.where(inside, p + upd, p),
                 jnp.where(inside, state.momentum_buffer, b)), bits)

    return fused, unfused


@pytest.mark.parametrize("W, case", [(1, "unique"), (4, "duplicates"),
                                     (4, "sentinel"), (1, "overfull_chunk"),
                                     (4, "empty_chunks")])
@pytest.mark.parametrize("opt_name, nesterov, masked, count", [
    ("dgc_sgd", False, False, 0),
    ("dgc_sgd", True, True, 3),
    ("dgc_sgd", False, True, 0),
    ("sgd", True, False, 3),
    ("sgd", False, True, 2),
])
def test_update_pass_is_apply_then_the_optimizer(opt_name, nesterov, masked,
                                                 count, W, case):
    """``payload_update_bits`` with ``dgc_sgd``'s / ``sgd``'s rule against
    ``payload_apply_bits`` followed by the optimizer's ``update`` and the
    add, BITWISE: parameters, momentum buffer and transmit bits, over
    nesterov, a weight-decay mask whose spans cut slabs and chunks,
    ``first`` true and false under a scheduled ``lr``, one worker and
    four with cross-worker duplicates, sentinel pairs, a chunk that
    takes several pages, chunks that take none, and a block that is not
    a whole number of chunks with a tail behind it that keeps its
    values. The donated record is garbage and is never read."""
    from dgc_tpu.optim import dgc_sgd, sgd

    rng = np.random.RandomState(41 + W)
    v, i, f, p, b, total, size = _update_case(case, W, rng)
    assert total % _CHUNK and size > total
    mask = _cutting_mask(total, size) if masked else None
    opt = {"dgc_sgd": dgc_sgd, "sgd": sgd}[opt_name](
        _exact_lr, nesterov=nesterov, weight_decay_mask=mask, **_EXACT)
    donor = jnp.asarray(rng.randint(
        -2**31, 2**31 - 1, size=kernels.num_sent_words(total),
        dtype=np.int64).astype(np.int32))
    fused, unfused = _fused_and_unfused(opt, total, size, W,
                                        jnp.asarray(count, jnp.int32))
    (p_k, b_k), bits_k = fused(v, i, f, p, b, donor)
    (p_r, b_r), bits_r = unfused(v, i, f, p, b, donor)
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_array_equal(np.asarray(b_k), np.asarray(b_r))
    # the tail behind the block is the caller's
    np.testing.assert_array_equal(np.asarray(p_k[total:]),
                                  np.asarray(p[total:]))
    np.testing.assert_array_equal(np.asarray(b_k[total:]),
                                  np.asarray(b[total:]))
    # and the pass did the optimizer's work on the block
    assert not np.array_equal(np.asarray(p_k[:total]), np.asarray(p[:total]))


def test_update_pass_with_the_benchmarks_constants():
    """VGG-16-BN's recipe (momentum 0.9, weight decay 5e-5, no mask):
    parameters bitwise; the buffer to the last bit of one FMA, which is
    XLA:CPU's to place (see ``_EXACT``)."""
    from dgc_tpu.optim import dgc_sgd

    rng = np.random.RandomState(3)
    v, i, f, p, b, total, size = _update_case("unique", 1, rng)
    opt = dgc_sgd(0.0125, momentum=0.9, weight_decay=5e-5)
    fused, unfused = _fused_and_unfused(opt, total, size, 1,
                                        jnp.asarray(5, jnp.int32))
    (p_k, b_k), bits_k = fused(v, i, f, p, b, None)
    (p_r, b_r), bits_r = unfused(v, i, f, p, b, None)
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))
    np.testing.assert_array_equal(np.asarray(p_k), np.asarray(p_r))
    np.testing.assert_array_max_ulp(np.asarray(b_k), np.asarray(b_r),
                                    maxulp=1)


def test_update_pass_without_a_buffer():
    """An optimizer with no momentum buffer (``dgc_sgd`` without weight
    decay) offers a rule over the parameters alone: one state stream."""
    from dgc_tpu.optim import dgc_sgd
    from dgc_tpu.optim.sgd import SGDState

    rng = np.random.RandomState(9)
    v, i, f, p, _, total, size = _update_case("unique", 1, rng)
    opt = dgc_sgd(0.125)
    assert not opt.rule.use_buf
    state = SGDState(jnp.zeros((), jnp.int32), None)
    (p_k,), bits_k = kernels.payload_update_bits(
        v, i, f, total, opt.rule.blocks(state, p), opt.rule.step,
        opt.rule.scalars(state))
    acc, bits_r = kernels.payload_apply_bits_reference(v, i, f, total)
    want = np.asarray(p).copy()
    want[:total] = want[:total] + np.float32(-0.125) * np.asarray(acc)
    np.testing.assert_array_equal(np.asarray(p_k), want)
    np.testing.assert_array_equal(np.asarray(bits_k), np.asarray(bits_r))

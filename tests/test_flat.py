"""Flat-buffer engine (dgc_tpu.compression.flat): layout roundtrips, flat-vs-
per-tensor equivalence, vector weight-decay masks, and the flat train step on
the fake 8-device CPU mesh.

Equivalence strategy: with ``sample_ratio=1.0`` the sampled threshold is the
exact k-th largest importance and no RNG enters selection, so the flat and
per-tensor paths must produce identical exchanged gradients and memory state
(modulo float op order)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dgc_tpu import (
    Compression,
    DGCCompressor,
    DGCSGDMemory,
    DistributedOptimizer,
    dgc_sgd,
    sgd,
)
from dgc_tpu.compression.flat import _REGIMES, ParamLayout
from dgc_tpu.utils.pytree import named_flatten
from dgc_tpu.utils.compat import enable_x64, shard_map

W = 8


def _params():
    rng = np.random.RandomState(0)
    return {
        "conv1": {"kernel": jnp.asarray(rng.randn(3, 3, 4, 8), jnp.float32)},
        "conv2": {"kernel": jnp.asarray(rng.randn(3, 3, 8, 8), jnp.float32)},
        "dense": {"kernel": jnp.asarray(rng.randn(32, 10), jnp.float32),
                  "bias": jnp.asarray(rng.randn(10), jnp.float32)},
        "bn": {"scale": jnp.asarray(rng.randn(8), jnp.float32)},
    }


def _make_dist(sample_ratio=1.0, ratio=0.05, **kw):
    params = _params()
    named, _ = named_flatten(params)
    comp = DGCCompressor(ratio, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=sample_ratio, **kw)
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9, weight_decay=1e-4),
                                comp, world_size=W)
    return params, comp, dist


def test_layout_roundtrip():
    params = _params()
    named, _ = named_flatten(params)
    compressed = [n for n, p in named.items() if p.ndim > 1]
    layout = ParamLayout(params, compressed)
    flat = layout.flatten(params)
    assert flat.shape == (layout.total,)
    assert layout.num_params == sum(p.size for p in named.values())
    # compressed block is the row-aligned prefix; the gap holds the sentinel
    t_real = sum(named[n].size for n in compressed)
    assert layout.t_data >= t_real          # row tails are structural pads
    assert layout.sentinel == layout.t_data
    assert layout.t_compressed >= layout.t_data + 1
    assert layout.t_compressed % 1024 == 0 and layout.total % 1024 == 0
    # every compressed tensor sits inside exactly one bucket row
    for g in layout.buckets:
        for r, n in enumerate(g.names):
            assert layout.offsets[n] == g.base + r * g.cols
            assert layout.sizes[n] <= g.cols
    # every slot not covered by a real tensor is a structural zero
    fl = np.asarray(flat)
    covered = np.zeros((layout.total,), bool)
    for n in layout.names:
        covered[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    assert (fl[~covered] == 0).all()
    back = layout.unflatten(flat)
    for n, p in named_flatten(back)[0].items():
        np.testing.assert_array_equal(np.asarray(p), np.asarray(named[n]))


def test_int64_index_wire_path():
    """A flat buffer at/above 2**31 slots forces the int64 index wire
    format (BASELINE 'int64 idx' row): the layout reports index_dtype
    int64, the engine refuses to build without jax x64 mode (clear error,
    not a silent wrap), and under x64 the traced sparsify emits int64
    indices with the exact per-tensor payload. Shape-only structs +
    eval_shape keep the test allocation-free."""
    from dgc_tpu.compression.flat import FlatDGCEngine

    huge = {"w": jax.ShapeDtypeStruct((2 ** 31 + 128,), jnp.float32)}
    layout = ParamLayout(huge, ["w"])
    assert layout.index_dtype == np.int64
    numel = 2 ** 31 + 128
    comp = DGCCompressor(1e-6, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize([("w", (numel, (numel,)))])
    with pytest.raises(RuntimeError, match="x64"):
        FlatDGCEngine(comp, layout)
    with enable_x64(True):
        engine = FlatDGCEngine(comp, layout)
        assert engine.index_dtype == jnp.int64
        assert engine.payload_size == comp.attributes["w"].num_selects
        out = jax.eval_shape(
            engine.sparsify,
            jax.ShapeDtypeStruct((layout.t_compressed,), jnp.float32),
            jax.random.PRNGKey(0))
        assert out[1].dtype == jnp.int64
        assert out[0].shape == out[1].shape == (engine.payload_size,)
    # small layouts keep the int32 wire unless explicitly asked otherwise
    ok = {"w": jax.ShapeDtypeStruct((2 ** 20,), jnp.float32)}
    small = ParamLayout(ok, ["w"])
    assert small.index_dtype == np.int32
    comp2 = DGCCompressor(0.01, memory=DGCSGDMemory(momentum=0.9),
                          int32_indices=False)
    comp2.initialize([("w", (2 ** 20, (2 ** 20,)))])
    # int64-by-config also requires x64 (same clear error)
    with pytest.raises(RuntimeError, match="x64"):
        FlatDGCEngine(comp2, small)
    with enable_x64(True):
        assert FlatDGCEngine(comp2, small).index_dtype == jnp.int64


def test_int64_wire_exchange_runs(mesh8):
    """int32_indices=False on a small model under x64: the WHOLE exchange
    (compensate, sparsify, gather, scatter-add, sent-count record) runs
    with int64 wire indices and matches the int32 engine's output exactly
    (same selections — the index dtype is representation only)."""
    from dgc_tpu.utils.pytree import named_unflatten

    params = _params()
    named, treedef = named_flatten(params)
    rng = np.random.RandomState(21)
    grads_w = {n: rng.randn(W, *p.shape).astype(np.float32)
               for n, p in named.items()}

    def build(int32_indices):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, int32_indices=int32_indices)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        layout, engine = dist.make_flat(params)
        flat_g = jnp.stack([layout.flatten(named_unflatten(
            {n: jnp.asarray(grads_w[n][w]) for n in named}, treedef))
            for w in range(W)])
        mem = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
            engine.init_memory())
        f = _flat_exchange_fn(None, engine, mesh8)
        return engine, f(flat_g, mem, jax.random.PRNGKey(0))[0]

    with enable_x64(True):
        engine64, out64 = build(False)
        assert engine64.index_dtype == jnp.int64
        out64 = np.asarray(out64[0])
    engine32, out32 = build(True)
    assert engine32.index_dtype == jnp.int32
    assert np.isfinite(out64).all()
    np.testing.assert_allclose(out64, np.asarray(out32[0]),
                               rtol=1e-6, atol=1e-7)


def test_flat_engine_without_error_feedback(mesh8):
    """DGCCompressor with the no-op base Memory (memory=None): the engine
    runs sparsify+exchange with NO compensate/masking state (mem == {}),
    like the reference compressor when paired with the base Memory —
    output is the scatter-add average of each worker's raw top-k."""
    params = _params()
    named, _ = named_flatten(params)
    comp = DGCCompressor(0.05, sample_ratio=1.0)   # memory=None -> Memory()
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=W)
    layout, engine = dist.make_flat(params)
    assert engine.init_memory() == {}
    rng = np.random.RandomState(23)
    g = np.zeros((W, layout.total), np.float32)
    for n in layout.names:
        o, s = layout.offsets[n], layout.sizes[n]
        g[:, o:o + s] = rng.randn(W, s)

    def worker(fg, key):
        out, mem = engine.exchange(fg[0], {}, key, "data", W)
        assert mem == {}
        return out[None]

    f = jax.jit(shard_map(
        worker, mesh=mesh8, in_specs=(P("data"), P()),
        out_specs=P("data"), check_vma=False))
    out = np.asarray(f(jnp.asarray(g), jax.random.PRNGKey(0)))[0]
    assert np.isfinite(out).all()
    # each worker's top-num_selects contribution averaged; a coordinate
    # every worker selects equals the plain mean there
    name = layout.compressed_names[0]
    o, s = layout.offsets[name], layout.sizes[name]
    a = comp.attributes[name]
    per_worker_tops = [set(np.argsort(-np.abs(g[w, o:o + s]))
                           [:a.num_selects]) for w in range(W)]
    common = set.intersection(*per_worker_tops)
    for c in list(common)[:5]:
        np.testing.assert_allclose(out[o + c], g[:, o + c].mean(),
                                   rtol=1e-5, atol=1e-6)


def test_layout_mask_vector():
    params = _params()
    layout = ParamLayout(params, [])
    mask = np.asarray(layout.mask_vector(lambda n: "bn" not in n))
    named, _ = named_flatten(params)
    assert mask.sum() == sum(p.size for n, p in named.items() if "bn" not in n)
    off, sz = layout.offsets["bn/scale"], layout.sizes["bn/scale"]
    assert (mask[off:off + sz] == 0).all()


def _mem_full(engine, mem, w=None):
    """Split flat memory -> canonical {momentums, velocities} [P] numpy
    view via the engine (materializes any pending deferred mask),
    optionally selecting worker w from a [W]-leading-axis tree."""
    if w is not None:
        mem = jax.tree.map(lambda x: x[w], mem)
    return {k: np.asarray(v) for k, v in engine.memory_full(mem).items()}


def _flat_exchange_fn(dist, engine, mesh):
    def worker(fg, mem, key):
        fg = fg[0]
        mem = jax.tree.map(lambda x: x[0], mem)
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        out, mem = engine.exchange(fg, mem, key, "data", W)
        return out[None], jax.tree.map(lambda x: x[None], mem)

    return jax.jit(shard_map(
        worker, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=(P("data"), P("data")), check_vma=False))


def _pt_exchange_fn(dist, mesh):
    def worker(grads, mem, key):
        grads = jax.tree.map(lambda x: x[0], grads)
        mem = jax.tree.map(lambda x: x[0], mem)
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        out, mem = dist.exchange(grads, mem, key)
        return (jax.tree.map(lambda x: x[None], out),
                jax.tree.map(lambda x: x[None], mem))

    return jax.jit(shard_map(
        worker, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=(P("data"), P("data")), check_vma=False))


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("momentum_masking", [False, True])
def test_flat_matches_per_tensor_exchange(mesh8, nesterov, momentum_masking):
    """Same grads, deterministic selection -> identical exchanged gradients
    and memory on both paths, including over multiple steps (error feedback
    accumulates differently if masking or compensation diverges)."""
    params = _params()
    named, _ = named_flatten(params)

    def make(dist_cls=None):
        comp = DGCCompressor(
            0.05, memory=DGCSGDMemory(momentum=0.9, nesterov=nesterov,
                                      momentum_masking=momentum_masking),
            sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return comp, DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9), comp, world_size=W)

    comp_f, dist_f = make()
    comp_p, dist_p = make()
    layout, engine = dist_f.make_flat(params)

    rng = np.random.RandomState(1)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}

    flat_fn = _flat_exchange_fn(dist_f, engine, mesh8)
    pt_fn = _pt_exchange_fn(dist_p, mesh8)

    mem_f = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine.init_memory())
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         dist_p.init_memory(params))

    from dgc_tpu.utils.pytree import named_unflatten

    def worker_tree(w):
        return named_unflatten({n: grads_w[n][w] for n in named},
                               named_flatten(params)[1])

    flat_grads_w = jnp.stack(
        [layout.flatten(worker_tree(w)) for w in range(W)])

    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_f, mem_f = flat_fn(flat_grads_w, mem_f, key)
        out_p, mem_p = pt_fn(grads_w, mem_p, key)
        named_out_p, _ = named_flatten(out_p)
        named_out_f = layout.unflatten_named(out_f[0])
        for n in layout.names:
            np.testing.assert_allclose(
                np.asarray(named_out_f[n]).reshape(-1),
                np.asarray(named_out_p[n][0]).reshape(-1),
                rtol=1e-5, atol=1e-6,
                err_msg=f"exchanged grads step {step} {n}")
        # memory equivalence (flat stores split buffers; compare per name
        # through the full view)
        full_f = _mem_full(engine, mem_f, w=0)
        for mkey in ("momentums", "velocities"):
            named_m_f = layout.unflatten_named(full_f[mkey], keep_1d=True)
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_m_f[n]),
                    np.asarray(mem_p[mkey][n][0]).reshape(-1),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{mkey} step {step} {n}")


def test_flat_matches_per_tensor_exchange_bf16_memory(mesh8):
    """The opt-in bf16 error-feedback state (DGCSGDMemory(dtype='bfloat16'),
    configs/dgc/bf16mem.py): flat and per-tensor paths round at the same
    points (f32 math, one round per stored value), so with deterministic
    selection they must still agree — at bf16 resolution — on exchanged
    gradients and memory state across steps, and every state buffer must
    actually BE bf16 on both paths."""
    params = _params()
    named, _ = named_flatten(params)

    def make():
        comp = DGCCompressor(
            0.05, memory=DGCSGDMemory(momentum=0.9, dtype="bfloat16"),
            sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return comp, DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9), comp, world_size=W)

    comp_f, dist_f = make()
    comp_p, dist_p = make()
    layout, engine = dist_f.make_flat(params)

    mem0 = engine.init_memory()
    assert mem0["momentums_c"].dtype == jnp.bfloat16
    assert mem0["velocities_d"].dtype == jnp.bfloat16
    # the packed transmit record stays int32 words regardless of the
    # narrow state dtype (word-wide scatter, bit-expansion on read)
    assert mem0["sent_bits"].dtype == jnp.int32
    mem_p0 = dist_p.init_memory(params)
    assert all(v.dtype == jnp.bfloat16 for v in mem_p0["momentums"].values())

    rng = np.random.RandomState(3)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}

    flat_fn = _flat_exchange_fn(dist_f, engine, mesh8)
    pt_fn = _pt_exchange_fn(dist_p, mesh8)

    mem_f = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         mem0)
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         mem_p0)

    from dgc_tpu.utils.pytree import named_unflatten

    def worker_tree(w):
        return named_unflatten({n: grads_w[n][w] for n in named},
                               named_flatten(params)[1])

    flat_grads_w = jnp.stack(
        [layout.flatten(worker_tree(w)) for w in range(W)])

    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_f, mem_f = flat_fn(flat_grads_w, mem_f, key)
        out_p, mem_p = pt_fn(grads_w, mem_p, key)
        named_out_p, _ = named_flatten(out_p)
        named_out_f = layout.unflatten_named(out_f[0])
        for n in layout.names:
            np.testing.assert_allclose(
                np.asarray(named_out_f[n], np.float32).reshape(-1),
                np.asarray(named_out_p[n][0], np.float32).reshape(-1),
                rtol=1e-2, atol=1e-2,
                err_msg=f"exchanged grads step {step} {n}")
        full_f = _mem_full(engine, jax.tree.map(lambda x: x[0], mem_f))
        for mkey in ("momentums", "velocities"):
            assert full_f[mkey].dtype == jnp.bfloat16
            named_m_f = layout.unflatten_named(
                jnp.asarray(full_f[mkey]), keep_1d=True)
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_m_f[n], np.float32),
                    np.asarray(mem_p[mkey][n][0], np.float32).reshape(-1),
                    rtol=1e-2, atol=1e-2,
                    err_msg=f"{mkey} step {step} {n}")


def test_flat_matches_per_tensor_exchange_int8_wire(mesh8):
    """int8 wire values (DGCCompressor(int8_values=True),
    configs/dgc/int8.py): both paths quantize per tensor with the same
    symmetric scale (max|payload|/127, round-to-nearest), so flat and
    per-tensor exchanges must produce identical dequantized gradients,
    and the dequantization error of each transmitted value is bounded by
    scale/2."""
    params = _params()
    named, _ = named_flatten(params)

    def make():
        comp = DGCCompressor(
            0.05, memory=DGCSGDMemory(momentum=0.9), sample_ratio=1.0,
            int8_values=True)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return comp, DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9), comp, world_size=W)

    comp_f, dist_f = make()
    comp_p, dist_p = make()
    layout, engine = dist_f.make_flat(params)
    assert engine._row_map is not None
    assert int(engine._row_map.shape[0]) == engine.payload_size

    rng = np.random.RandomState(5)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}

    flat_fn = _flat_exchange_fn(dist_f, engine, mesh8)
    pt_fn = _pt_exchange_fn(dist_p, mesh8)
    mem_f = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine.init_memory())
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         dist_p.init_memory(params))

    from dgc_tpu.utils.pytree import named_unflatten

    def worker_tree(w):
        return named_unflatten({n: grads_w[n][w] for n in named},
                               named_flatten(params)[1])

    flat_grads_w = jnp.stack(
        [layout.flatten(worker_tree(w)) for w in range(W)])

    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_f, mem_f = flat_fn(flat_grads_w, mem_f, key)
        out_p, mem_p = pt_fn(grads_w, mem_p, key)
        named_out_p, _ = named_flatten(out_p)
        named_out_f = layout.unflatten_named(out_f[0])
        for n in layout.names:
            np.testing.assert_allclose(
                np.asarray(named_out_f[n]).reshape(-1),
                np.asarray(named_out_p[n][0]).reshape(-1),
                rtol=1e-5, atol=1e-6,
                err_msg=f"exchanged grads step {step} {n}")
        # memory equivalence: the error-feedback residual (int8 EF) must
        # land identically on both paths
        full_f = _mem_full(engine, mem_f, w=0)
        for mkey in ("momentums", "velocities"):
            named_m_f = layout.unflatten_named(full_f[mkey], keep_1d=True)
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_m_f[n]),
                    np.asarray(mem_p[mkey][n][0]).reshape(-1),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{mkey} step {step} {n}")


def test_int8_error_feedback_residual_semantics(mesh8):
    """int8 wire + error feedback (the default): after one exchange, the
    velocity at every transmitted coordinate holds exactly the
    quantization residual ``v - q*scale`` (NOT zero), the momentum is
    still masked, and with ``int8_error_feedback=False`` the round-3
    zeroing behavior returns."""
    params = _params()
    named, _ = named_flatten(params)

    def run(ef):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, int8_values=True,
                             int8_error_feedback=ef)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        layout, engine = dist.make_flat(params)
        rng = np.random.RandomState(2)
        from dgc_tpu.utils.pytree import named_unflatten
        grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
                   for n, p in named.items()}
        flat_grads_w = jnp.stack([
            layout.flatten(named_unflatten(
                {n: grads_w[n][w] for n in named},
                named_flatten(params)[1])) for w in range(W)])
        fn = _flat_exchange_fn(dist, engine, mesh8)
        mem = jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
            engine.init_memory())
        out, mem = fn(flat_grads_w, mem, jax.random.PRNGKey(0))
        return layout, engine, flat_grads_w, mem

    layout, engine, fg, mem = run(ef=True)
    # recompute worker 0's selection to find its transmitted coordinates:
    # first step => velocity == momentum-corrected grad == grad (momentum
    # buffers start at zero, vec = 0 + (0*m + g))
    vec0 = np.asarray(fg[0][:layout.t_compressed])
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec0),
                                         jax.random.fold_in(
                                             jax.random.PRNGKey(0), 0))
    vals, idx = np.asarray(vals), np.asarray(idx)
    real = idx != layout.sentinel
    full = _mem_full(engine, mem, w=0)
    vel, mmt = full["velocities"], full["momentums"]
    # per-tensor symmetric scales over the payload rows
    rm = np.asarray(engine._row_map)
    scales = np.zeros(rm.max() + 1, np.float32)
    for rr in np.unique(rm):
        scales[rr] = np.abs(vals[rm == rr]).max() / 127.0
    safe = np.where(scales > 0, scales, 1.0)
    q = np.clip(np.round(vals / safe[rm]), -127, 127)
    resid = vals - q * scales[rm]
    np.testing.assert_allclose(vel[idx[real]], resid[real],
                               rtol=1e-5, atol=1e-7)
    assert np.abs(resid[real]).max() > 0          # feedback is non-trivial
    assert (mmt[idx[real]] == 0).all()            # momentum masked eagerly
    # transmit record stays empty (no deferred zeroing may kill residuals)
    assert not np.asarray(mem["sent_bits"]).any()

    layout0, engine0, _, mem0 = run(ef=False)
    full0 = _mem_full(engine0, mem0, w=0)
    np.testing.assert_array_equal(full0["velocities"][idx[real]], 0.0)


def test_int8_quantization_roundtrip_bound():
    """quantize_int8: dequantized values are within scale/2 of the
    original, zero maps to zero, and an all-zero vector survives."""
    from dgc_tpu.compression.dgc import quantize_int8
    rng = np.random.RandomState(0)
    v = jnp.asarray(rng.randn(1000) * np.exp(rng.randn(1000) * 3),
                    jnp.float32)
    q, scale = quantize_int8(v)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    deq = np.asarray(q, np.float32) * float(scale)
    err = np.abs(deq - np.asarray(v))
    assert err.max() <= float(scale) / 2 + 1e-7
    assert float(scale) == pytest.approx(
        float(jnp.max(jnp.abs(v))) / 127.0)
    qz, sz = quantize_int8(jnp.zeros((16,), jnp.float32))
    assert float(sz) == 0.0 and not np.asarray(qz).any()


def test_warmup_ratio_rebuild_equivalence(mesh8):
    """The full wm5 warm-up schedule (6 ratio changes, reference
    compression.py:91-107) driven through the FLAT ENGINE REBUILD path:
    each ratio change rebuilds the engine (new static attrs, re-jit) while
    the memory buffers — including a pending deferred transmit mask from
    the previous ratio's last step — carry over untouched. The flat path
    must stay step-for-step identical to the per-tensor oracle across
    every transition (sample_ratio=1.0 makes selection deterministic)."""
    params = _params()
    named, _ = named_flatten(params)

    def mk():
        comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, warmup_epochs=5)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return comp, DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9), comp, world_size=W)

    comp_f, dist_f = mk()
    comp_p, dist_p = mk()

    rng = np.random.RandomState(3)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    from dgc_tpu.utils.pytree import named_unflatten

    def worker_tree(w):
        return named_unflatten({n: grads_w[n][w] for n in named},
                               named_flatten(params)[1])

    mem_f = mem_p = None
    layout0 = None
    ratios, payloads = [], []
    for epoch in range(7):
        ch_f = comp_f.warmup_compress_ratio(epoch)
        assert ch_f == comp_p.warmup_compress_ratio(epoch)
        assert ch_f == (epoch <= 5)
        layout, engine = dist_f.make_flat(params)   # the rebuild
        if layout0 is None:
            layout0 = layout
            flat_grads_w = jnp.stack(
                [layout.flatten(worker_tree(w)) for w in range(W)])
            mem_f = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                engine.init_memory())
            mem_p = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                dist_p.init_memory(params))
        # memory shapes are ratio-independent: the rebuilt engine adopts
        # the carried buffers with no conversion
        ratios.append(round(comp_f.compress_ratio, 4))
        payloads.append(engine.payload_size)
        flat_fn = _flat_exchange_fn(dist_f, engine, mesh8)
        pt_fn = _pt_exchange_fn(dist_p, mesh8)
        for s in range(2):
            key = jax.random.PRNGKey(epoch * 10 + s)
            out_f, mem_f = flat_fn(flat_grads_w, mem_f, key)
            out_p, mem_p = pt_fn(grads_w, mem_p, key)
            assert np.isfinite(np.asarray(out_f)).all()
            named_out_p, _ = named_flatten(out_p)
            named_out_f = layout.unflatten_named(out_f[0])
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_out_f[n]).reshape(-1),
                    np.asarray(named_out_p[n][0]).reshape(-1),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"epoch {epoch} step {s} {n}")
        full_f = _mem_full(engine, mem_f, w=0)
        for mkey in ("momentums", "velocities"):
            named_m_f = layout.unflatten_named(full_f[mkey], keep_1d=True)
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_m_f[n]),
                    np.asarray(mem_p[mkey][n][0]).reshape(-1),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{mkey} epoch {epoch} {n}")
    assert ratios == [0.3162, 0.1, 0.0316, 0.01, 0.0032, 0.001, 0.001]
    # payload shrinks with the ratio and is constant once warm-up ends
    assert payloads == sorted(payloads, reverse=True)
    assert payloads[-1] == payloads[-2]
    # error feedback survived to the end: residuals accumulated
    assert np.abs(full_f["velocities"]).sum() > 0


def test_flat_payload_matches_reference_wire_volume():
    """The tight payload is exactly sum(num_selects) — the reference's wire
    size (compression.py:151), no padding inflation."""
    params, comp, dist = _make_dist(sample_ratio=0.25, ratio=0.01)
    layout, engine = dist.make_flat(params)
    expected = sum(a.num_selects for a in comp.attributes.values())
    assert engine.payload_size == expected


def test_flat_sparsify_selects_topk(mesh8):
    """With deterministic sampling, the flat engine selects exactly the
    num_selects largest-|.| coordinates of each tensor."""
    params, comp, dist = _make_dist(sample_ratio=1.0, ratio=0.05)
    layout, engine = dist.make_flat(params)
    rng = np.random.RandomState(2)
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:layout.t_data] = rng.randn(layout.t_data).astype(np.float32)
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                         jax.random.PRNGKey(0))
    vals, idx = np.asarray(vals), np.asarray(idx)
    for name in layout.compressed_names:
        a = comp.attributes[name]
        off = layout.offsets[name]
        seg = vec[off:off + a.numel]
        expect = set(off + np.argsort(-np.abs(seg))[:a.num_selects])
        got = {int(i) for i in idx if off <= i < off + a.numel}
        assert got == expect, name
        for i in idx:
            if off <= i < off + a.numel:
                assert vals[list(idx).index(i)] == seg[i - off]


def test_ladder_from_topk_matches_full_scan():
    """The hot path derives the resample ladder from the selection top-k
    (flat._ladder_adapt_from_topk); it must choose the IDENTICAL adapted
    threshold as the full [R, cols] ladder scan (flat._ladder_adapt) for
    exact top-k — across descending, immediately-passing, and saturated
    count regimes."""
    from dgc_tpu.compression.flat import _ladder_adapt, _ladder_adapt_from_topk

    rng = np.random.RandomState(11)
    R, cols, k = 6, 4096, 64
    imp = jnp.asarray(np.abs(rng.randn(R, cols)).astype(np.float32))
    num_selects = jnp.asarray(
        rng.randint(8, k + 1, R).astype(np.float32))
    adapt = jnp.asarray(np.array([True] * (R - 1) + [False]))
    top_scores = jax.lax.top_k(imp, k)[0]
    for scale in (8.0, 1.0, 0.05):  # high thr (descends) .. low (saturates)
        # per-row thresholds near the selection quantile, scaled
        thr = top_scores[:, k // 2] * scale
        a = _ladder_adapt(imp, thr, num_selects, adapt, 0.8, 10)
        b = _ladder_adapt_from_topk(top_scores, thr, num_selects, adapt,
                                    0.8, 10)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"scale {scale}")


def _sampling_test_data(kind, numel, rng):
    """Gradient distributions the threshold estimator must survive:
    well-behaved Gaussian; heavy-tailed Student-t3 (fc-layer gradients —
    rare huge entries dominate the top-k); low-rank rank-1 + noise
    (structured gradients whose contiguous elements — and hence whole
    128-lane sample blocks — are strongly correlated, the adversarial
    case for lane-block sampling's effective sample size)."""
    if kind == "gauss":
        return rng.randn(numel).astype(np.float32)
    if kind == "t3":
        return rng.standard_t(3, numel).astype(np.float32)
    u = rng.randn(300, 1)
    v = rng.randn(1, 400)
    return (u @ v + 0.05 * rng.randn(300, 400)).astype(np.float32).ravel()


@pytest.mark.parametrize("kind", ["gauss", "t3", "lowrank"])
def test_lane_block_sampling_quantile(kind):
    """Lane-block strided sampling, across gradient distributions: (a)
    the drawn sample count tracks the geometry's num_samples (the old
    nb = n // 128 truncation drew as little as half the budget), and (b)
    the sampled threshold estimates the target quantile — the fraction of
    elements above the raw (pre-adaptation) threshold stays within a
    constant factor of the compress ratio across random phases, at a
    moderate stride. The threshold is an order statistic, so the band is
    distribution-free; within-block correlation (lowrank) widens the
    estimator's variance, which is what the band budgets for."""
    ratio, numel = 0.01, 120_000
    comp = DGCCompressor(ratio, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.05, max_adaptation_iters=0)
    comp.initialize([("w", (numel, (300, 400)))])
    a = comp.attributes["w"]
    assert a.sample_stride > 1  # genuinely strided
    params = {"w": jnp.zeros((300, 400), jnp.float32)}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    [b] = engine.buckets

    data = _sampling_test_data(kind, numel, np.random.RandomState(5))
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:numel] = data
    block = jnp.asarray(vec[:b.rows * b.cols]).reshape(b.rows, b.cols)
    col = jnp.arange(b.cols)[None, :]
    imp_rows = jnp.where(col < int(a.numel), jnp.abs(block), -1.0)

    sample_fn = jax.jit(lambda k: engine._sample_rows(b, imp_rows, k))
    fractions = []
    for seed in range(30):
        smp = np.asarray(sample_fn(jax.random.PRNGKey(seed)))
        drawn = int((smp >= 0).sum())
        # (a) budget: within [1.0, 1.0 + lane-block rounding slack]
        assert a.num_samples <= drawn <= a.num_samples + 128, drawn
        # (b) threshold = top_k_samples-th largest sample (engine rule)
        thr = np.sort(smp[smp >= 0])[-a.top_k_samples]
        fractions.append((np.abs(data) >= thr).sum() / numel)
    med = float(np.median(fractions))
    # quantile error bounded: the ladder's one-sided correction (x0.8 per
    # level) easily covers a [0.4, 2.5]x band
    assert 0.4 * ratio <= med <= 2.5 * ratio, (kind, med)


@pytest.mark.parametrize("kind", ["gauss", "t3", "lowrank"])
def test_selection_count_within_adaptation_bounds(kind):
    """End-to-end selection counts under the full pipeline (sampling +
    ladder adaptation + top-k cap): for every distribution and every
    random phase, the number of REAL transmitted coordinates stays inside
    the adaptation contract [lower_bound * num_selects, num_selects] —
    the ladder must recover whatever bias/variance the distribution
    induces in the raw threshold estimate (reference
    compression.py:128-151 semantics)."""
    ratio, numel = 0.01, 120_000
    comp = DGCCompressor(ratio, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.05)       # default ladder (10 iters)
    comp.initialize([("w", (numel, (300, 400)))])
    a = comp.attributes["w"]
    params = {"w": jnp.zeros((300, 400), jnp.float32)}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    sp = jax.jit(engine.sparsify)
    ns = int(a.num_selects)
    floor = int(comp.compress_lower_bound * ns)
    for seed in range(20):
        data = _sampling_test_data(kind, numel,
                                   np.random.RandomState(100 + seed))
        vec = np.zeros((layout.t_compressed,), np.float32)
        vec[:numel] = data
        _, idx = sp(jnp.asarray(vec), jax.random.PRNGKey(seed))
        real = int((np.asarray(idx) != layout.sentinel).sum())
        assert floor <= real <= ns, (kind, seed, real, floor, ns)


def test_split_bucket_stratified_selection(monkeypatch):
    """Giant single-tensor rows split into segments (flat._SPLIT_COLS):
    the per-tensor quota distributes exactly across segments and, with
    deterministic sampling, each segment selects exactly its top-quota
    coordinates (stratified selection; payload/wire volume unchanged)."""
    import dgc_tpu.compression.flat as flat

    monkeypatch.setattr(flat, "_SPLIT_COLS", 1024)
    monkeypatch.setattr(flat, "_SPLIT_TARGET", 1024)
    params = {"w": {"kernel": jnp.zeros((64, 128), jnp.float32)}}
    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=1.0)
    comp.initialize([("w/kernel", (8192, (64, 128)))])
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    a = comp.attributes["w/kernel"]
    [b] = engine.buckets
    assert b.rows > 1 and b.rows * b.cols == 8192
    assert int(b.num_selects.sum()) == a.num_selects  # exact quota total
    # the wire payload may be the padded [R, max_sel] grid when the
    # inflation stays under flat._PAD_PAYLOAD_MAX_FRAC (identity tight
    # map, no compaction gather) — real transmitted elements stay
    # exactly the per-segment quotas (checked below)
    assert (a.num_selects <= engine.payload_size
            <= (1 + flat._PAD_PAYLOAD_MAX_FRAC) * a.num_selects + 1)

    rng = np.random.RandomState(3)
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:8192] = rng.randn(8192).astype(np.float32)
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                         jax.random.PRNGKey(0))
    idx = np.asarray(idx)
    got = set(int(i) for i in idx if i < 8192)
    expect = set()
    for s in range(b.rows):
        seg = vec[s * b.cols:(s + 1) * b.cols]
        ns = int(b.num_selects[s])
        expect.update(s * b.cols + np.argsort(-np.abs(seg))[:ns])
    assert got == expect


@pytest.mark.parametrize("kw", [
    dict(),                                    # sampled + ladder adaptation
    dict(sample_ratio=1.0),                    # exact (sample-everything)
    dict(strided_sample=False),                # uniform resample
    dict(resample=False),                      # two-sided batched adaptation
])
def test_payload_indices_unique(mesh8, kw):
    """The engine's payload must never contain duplicate non-sentinel
    indices: ``kernels.pack_sent_bits`` scatters single bits ADDITIVELY
    (a repeated index would carry into a neighboring coordinate's bit and
    silently corrupt its error-feedback mask), so uniqueness is a hard
    precondition of the transmit record, not a style point. This pins it
    at the payload level for every selection path — a future selection
    change that emits duplicates fails here loudly."""
    params, comp, dist = _make_dist(ratio=0.05, **kw)
    layout, engine = dist.make_flat(params)
    rng = np.random.RandomState(11)
    vec = np.zeros((layout.t_compressed,), np.float32)
    for n in layout.compressed_names:
        o, s = layout.offsets[n], layout.sizes[n]
        vec[o:o + s] = rng.randn(s).astype(np.float32)
    for step in range(3):
        _, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                          jax.random.PRNGKey(step))
        idx = np.asarray(idx)
        real = idx[idx != layout.sentinel]
        assert len(np.unique(real)) == len(real), kw


def test_payload_indices_unique_split_bucket(monkeypatch):
    """Same duplicate-free guarantee through the segment-split (giant row)
    path: segments partition the tensor, so cross-segment duplicates are
    structurally impossible — assert it anyway at the payload level."""
    import dgc_tpu.compression.flat as flat

    monkeypatch.setattr(flat, "_SPLIT_COLS", 1024)
    monkeypatch.setattr(flat, "_SPLIT_TARGET", 1024)
    params = {"w": {"kernel": jnp.zeros((64, 128), jnp.float32)}}
    comp = DGCCompressor(0.01, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.05)
    comp.initialize([("w/kernel", (8192, (64, 128)))])
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    assert engine.buckets[0].rows > 1
    rng = np.random.RandomState(5)
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:8192] = rng.randn(8192).astype(np.float32)
    _, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                      jax.random.PRNGKey(2))
    idx = np.asarray(idx)
    real = idx[idx != layout.sentinel]
    assert len(np.unique(real)) == len(real)


def test_3d_layout_free_selection_path(monkeypatch):
    """Wide buckets (cols >= SEL3D_MIN_COLS) select through the layout-free
    3-D path (lane-stratified candidates + small final top-k, no 2-D
    relayout). On CPU both approx stages lower to exact, so the selection
    must recover nearly all of the exact top-num_selects (lane caps at
    SEL3D_MARGIN x the mean bind with negligible probability) and the
    payload invariants hold: indices in-tensor, values = vec[idx], valid
    count ladder-bounded. The gate is lowered so a CI-sized tensor takes
    the path (production gates at 3M cols, where the paired A/B says the
    3-D form wins)."""
    from dgc_tpu.compression.flat import FlatDGCEngine

    monkeypatch.setattr(FlatDGCEngine, "SEL3D_MIN_COLS", 1024 * 1024)
    numel = 1_200_000
    comp = DGCCompressor(0.005, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.01)
    comp.initialize([("w", (numel, (numel,)))])
    params = {"w": jax.ShapeDtypeStruct((numel,), jnp.float32)}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    [b] = engine.buckets
    assert engine._use_3d(b), (b.cols, b.strides, b.num_samples)

    a = comp.attributes["w"]
    rng = np.random.RandomState(17)
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:numel] = rng.randn(numel).astype(np.float32)
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                         jax.random.PRNGKey(0))
    vals, idx = np.asarray(vals), np.asarray(idx)
    real = idx != layout.sentinel
    count = int(real.sum())
    # ladder adaptation guarantees at least lower_bound * num_selects pass
    # (and the slot cap bounds above)
    assert 0.8 * a.num_selects * 0.9 <= count <= a.num_selects
    assert (idx[real] < numel).all() and (idx[real] >= 0).all()
    np.testing.assert_array_equal(vals[real], vec[idx[real]])
    assert len(np.unique(idx[real])) == count  # no duplicate coordinates
    # near-exact recall on CPU (both approx stages lower to exact sorts)
    exact = set(np.argsort(-np.abs(vec[:numel]))[:count])
    recall = len(exact & set(idx[real].tolist())) / count
    assert recall >= 0.95, recall


def test_flat_dense_exchange_psum(mesh8):
    params = _params()
    dist = DistributedOptimizer(sgd(0.1), Compression.none(), world_size=W)
    layout, engine = dist.make_flat(params)
    rng = np.random.RandomState(3)
    g = rng.randn(W, layout.total).astype(np.float32)
    f = _flat_exchange_fn(dist, engine, mesh8)
    out, _ = f(jnp.asarray(g), {}, jax.random.PRNGKey(0))
    np.testing.assert_allclose(np.asarray(out[0]), g.mean(0), rtol=1e-5)


def test_vector_wd_mask_matches_tree_mask():
    """dgc_sgd over one flat buffer with a 0/1 mask vector == dgc_sgd over
    the pytree with per-leaf boolean masks."""
    params = _params()
    named, _ = named_flatten(params)
    layout = ParamLayout(params, [])
    rng = np.random.RandomState(4)
    grads = {n: jnp.asarray(rng.randn(*p.shape), jnp.float32)
             for n, p in named.items()}

    pred = lambda n: "bn" not in n and "bias" not in n
    tree_mask = jax.tree_util.tree_map_with_path(
        lambda path, _: pred("/".join(str(getattr(k, 'key', k))
                                      for k in path)), params)
    opt_tree = dgc_sgd(0.1, momentum=0.9, weight_decay=1e-2,
                       weight_decay_mask=tree_mask)
    opt_flat = dgc_sgd(0.1, momentum=0.9, weight_decay=1e-2,
                       weight_decay_mask=layout.mask_vector(pred))

    from dgc_tpu.utils.pytree import named_unflatten
    st_t = opt_tree.init(params)
    flat_p = layout.flatten(params)
    st_f = opt_flat.init(flat_p)
    flat_g = layout.flatten(
        named_unflatten(dict(grads), named_flatten(params)[1]))

    p_t, p_f = params, flat_p
    g_named = grads
    for _ in range(3):
        upd_t, st_t = opt_tree.update(
            jax.tree_util.tree_unflatten(
                named_flatten(params)[1], [g_named[n] for n in named]),
            st_t, p_t)
        upd_f, st_f = opt_flat.update(flat_g, st_f, p_f)
        p_t = jax.tree.map(lambda a, b: a + b, p_t, upd_t)
        p_f = p_f + upd_f
        named_t, _ = named_flatten(p_t)
        named_f = layout.unflatten_named(p_f)
        for n in layout.names:
            np.testing.assert_allclose(np.asarray(named_f[n]).reshape(-1),
                                       np.asarray(named_t[n]).reshape(-1),
                                       rtol=1e-6, atol=1e-7)


def test_flat_train_step_smoke(mesh8):
    """Full flat train step on the CPU mesh: runs, loss finite, params move,
    and a compress-ratio change rebuild keeps working."""
    from dgc_tpu.models import resnet20
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)

    model = resnet20(num_classes=10)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)),
                   train=True)
    named, _ = named_flatten(v["params"])
    comp = DGCCompressor(0.01, memory=DGCSGDMemory(momentum=0.9),
                         warmup_epochs=2)
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    comp.warmup_compress_ratio(0)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9, weight_decay=1e-4),
                                comp, world_size=W)
    setup = make_flat_setup(v, dist)
    state = shard_state(make_flat_state(v, dist, setup, W), mesh8,
                        dist_opt=dist)
    step = build_train_step(model.apply, dist, mesh8, flat=setup)

    rng = np.random.RandomState(5)
    images = jnp.asarray(rng.randn(W * 4, 32, 32, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, W * 4), jnp.int32)
    p0 = np.asarray(state.params)
    state, m = step(state, images, labels, jax.random.PRNGKey(0))
    assert np.isfinite(float(m["loss"]))
    assert int(state.step) == 1
    assert not np.allclose(p0, np.asarray(state.params))

    # ratio change -> rebuild engine + step, state carries over
    changed = comp.warmup_compress_ratio(5)
    assert changed
    setup2 = make_flat_setup(v, dist)
    step2 = build_train_step(model.apply, dist, mesh8, flat=setup2)
    state, m = step2(state, images, labels, jax.random.PRNGKey(1))
    assert np.isfinite(float(m["loss"]))


def test_flat_uninitialized_compressor_degrades_to_dense(mesh8):
    """A DGCCompressor whose initialize() was never called has no attributes:
    every parameter must fall through to the dense psum block (the per-tensor
    path's `name in attributes` guard, dgc.py compress)."""
    params = _params()
    comp = DGCCompressor(0.01, memory=DGCSGDMemory(momentum=0.9))
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=W)
    layout, engine = dist.make_flat(params)
    assert layout.t_compressed == 0 and engine.payload_size == 0
    rng = np.random.RandomState(7)
    g = rng.randn(W, layout.total).astype(np.float32)
    f = _flat_exchange_fn(dist, engine, mesh8)
    mem = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                       engine.init_memory())
    out, _ = f(jnp.asarray(g), mem, jax.random.PRNGKey(0))
    # dense block applies non-accumulating momentum correction to the average;
    # on zero-initialized memory step 1 output == the plain average
    np.testing.assert_allclose(np.asarray(out[0]), g.mean(0), rtol=1e-5)


def test_flat_uniform_sampling_exact_for_tiny_tensors():
    """strided_sample=False with tensors whose numel <= 2/ratio (the
    sample-everything path): the threshold must come from the exact
    importance vector, not a with-replacement draw."""
    params = {"w": jnp.asarray(np.arange(1, 41, dtype=np.float32)
                               .reshape(5, 8))}
    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                         strided_sample=False)
    comp.initialize([("w", params["w"])])
    a = comp.attributes["w"]
    assert a.num_samples == a.numel  # degenerate sample-everything geometry
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=W)
    layout, engine = dist.make_flat(params)
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:40] = np.arange(1, 41, dtype=np.float32)
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec),
                                         jax.random.PRNGKey(3))
    got = {int(i) for v, i in zip(np.asarray(vals), np.asarray(idx))
           if i < layout.t_data}
    expect = set(np.argsort(-vec)[:a.num_selects])
    assert got == expect


def test_flat_ratio_one_routes_dense(mesh8):
    """compress_ratio == 1.0 must transmit everything dense with the
    per-tensor path's non-accumulating correction (dgc.py's
    `compress_ratio < 1.0` guard) — no sparse payload at all."""
    params = _params()
    named, _ = named_flatten(params)
    comp = DGCCompressor(1.0, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=W)
    layout, engine = dist.make_flat(params)
    assert engine.payload_size == 0
    rng = np.random.RandomState(11)
    g = rng.randn(W, layout.total).astype(np.float32)
    f = _flat_exchange_fn(dist, engine, mesh8)
    mem = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                       engine.init_memory())
    out, mem2 = f(jnp.asarray(g), mem, jax.random.PRNGKey(0))
    # zero-initialized memory, step 1: out == momentum-corrected average
    # == 0.9*0 + mean(g)
    np.testing.assert_allclose(np.asarray(out[0]), g.mean(0), rtol=1e-5)
    # velocities untouched on the dense path (memory.py:64-70)
    np.testing.assert_array_equal(
        _mem_full(engine, mem2, w=0)["velocities"], 0)


def test_flat_memory_state_dict_roundtrip():
    params, comp, dist = _make_dist(sample_ratio=1.0, ratio=0.05)
    layout, engine = dist.make_flat(params)
    mem = engine.init_memory()
    mem = {k: v if k == "sent_bits"
           else v + (1.0 if k.startswith("momentums") else 2.0)
           for k, v in mem.items()}
    sd = engine.memory_state_dict(mem)
    assert set(sd) == {"momentums", "velocities"}
    assert set(sd["momentums"]) == set(layout.names)
    back = _mem_full(
        engine, engine.load_memory_state_dict(engine.init_memory(), sd))
    # per-name contents round-trip; gap slots stay structurally zero
    for mkey, val in (("momentums", 1.0), ("velocities", 2.0)):
        named_b = layout.unflatten_named(back[mkey], keep_1d=True)
        for n in layout.names:
            np.testing.assert_allclose(np.asarray(named_b[n]), val)
        b = np.asarray(back[mkey])
        assert (b[layout.t_data:layout.t_compressed] == 0).all()


def test_shard_state_rejects_conflicting_flags():
    from dgc_tpu.parallel import make_mesh
    from dgc_tpu.training import TrainState, shard_state

    state = TrainState(step=jnp.zeros((), jnp.int32), params=jnp.zeros((4,)),
                       opt_state=None, memory={}, batch_stats={})
    dist = DistributedOptimizer(sgd(0.1), Compression.none(), world_size=1)
    with pytest.raises(ValueError, match="not both"):
        shard_state(state, make_mesh(1), per_worker_opt=True, dist_opt=dist)


@pytest.mark.parametrize("global_clip", [False, True])
def test_flat_gradient_clipping_matches_per_tensor(mesh8, global_clip):
    """A gradient_clipping hook plugged into DGCSGDMemory (reference
    memory.py:34,52-53) must behave identically on the flat engine and the
    per-tensor oracle: clip the LOCAL grad inside the accumulating
    compensate and the AVERAGED grad on the dense fallback. Covers both a
    local clip and the psum-backed global-norm clip (clip_grad.py:35-42)."""
    import functools

    from dgc_tpu.utils.clip_grad import (clip_grad_norm,
                                         clip_grad_norm_2_by_global)

    params = _params()
    named, _ = named_flatten(params)
    if global_clip:
        clip = functools.partial(clip_grad_norm_2_by_global, max_norm=0.05,
                                 axis_name="data")
    else:
        clip = functools.partial(clip_grad_norm, max_norm=0.05)

    def make():
        comp = DGCCompressor(
            0.05, memory=DGCSGDMemory(momentum=0.9, gradient_clipping=clip),
            sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return comp, DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                          world_size=W)

    _, dist_f = make()
    _, dist_p = make()
    layout, engine = dist_f.make_flat(params)

    rng = np.random.RandomState(21)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    from dgc_tpu.utils.pytree import named_unflatten
    flat_grads_w = jnp.stack([
        layout.flatten(named_unflatten({n: grads_w[n][w] for n in named},
                                       named_flatten(params)[1]))
        for w in range(W)])

    flat_fn = _flat_exchange_fn(dist_f, engine, mesh8)
    pt_fn = _pt_exchange_fn(dist_p, mesh8)
    mem_f = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine.init_memory())
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         dist_p.init_memory(params))

    clipped_any = False
    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_f, mem_f = flat_fn(flat_grads_w, mem_f, key)
        out_p, mem_p = pt_fn(grads_w, mem_p, key)
        named_out_p, _ = named_flatten(out_p)
        named_out_f = layout.unflatten_named(out_f[0])
        for n in layout.names:
            np.testing.assert_allclose(
                np.asarray(named_out_f[n]).reshape(-1),
                np.asarray(named_out_p[n][0]).reshape(-1),
                rtol=1e-5, atol=1e-6,
                err_msg=f"exchanged grads step {step} {n}")
        full_f = _mem_full(engine, mem_f, w=0)
        for mkey in ("momentums", "velocities"):
            named_m_f = layout.unflatten_named(full_f[mkey], keep_1d=True)
            for n in layout.names:
                np.testing.assert_allclose(
                    np.asarray(named_m_f[n]),
                    np.asarray(mem_p[mkey][n][0]).reshape(-1),
                    rtol=1e-5, atol=1e-6,
                    err_msg=f"{mkey} step {step} {n}")
        # the clip must actually engage: raw grads have norm >> 0.05
        for n in layout.compressed_names:
            seg = full_f["momentums"][
                layout.offsets[n]:layout.offsets[n] + layout.sizes[n]]
            if np.linalg.norm(seg) < 1.0:
                clipped_any = True
    assert clipped_any


# ------------------------------------------------------------------ #
# bit-packed index wire (compression/wirecodec.py, configs/dgc/packidx)
# ------------------------------------------------------------------ #


def test_index_codec_roundtrip():
    """IndexCodec: every in-row index decodes to exactly itself, for
    random payloads across rows of mixed sizes (widths are per-tensor,
    offsets straddle word boundaries)."""
    from dgc_tpu.compression.wirecodec import IndexCodec

    params = _params()
    named, _ = named_flatten(params)
    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=1.0)
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=W)
    layout, engine = dist.make_flat(params)
    codec = IndexCodec(engine.buckets)
    assert codec.payload == engine.payload_size
    # variable widths: the big conv rows need more bits than tiny rows
    assert codec.widths.min() >= 1
    assert codec.bits_per_index < 32

    rng = np.random.RandomState(0)
    for trial in range(5):
        local = (rng.rand(codec.payload)
                 * codec.slot_numel).astype(np.int64)
        gidx = codec.slot_off + local
        words = jax.jit(codec.encode)(jnp.asarray(gidx, jnp.int32))
        assert words.dtype == jnp.uint32
        assert words.shape == (codec.nwords,)
        back = np.asarray(jax.jit(codec.decode)(words))
        np.testing.assert_array_equal(back, gidx)
    # batched decode (the gathered [W, nwords] wire)
    local = (rng.rand(W, codec.payload) * codec.slot_numel).astype(np.int64)
    gidx = codec.slot_off[None] + local
    words = jnp.stack([codec.encode(jnp.asarray(gidx[w], jnp.int32))
                       for w in range(W)])
    back = np.asarray(jax.jit(codec.decode)(words))
    np.testing.assert_array_equal(back, gidx)


def test_index_codec_boundary_values():
    """Word-straddling widths: rows whose numel is one under/over a power
    of two, locals at 0 and numel-1 (all-ones bit patterns)."""
    from dgc_tpu.compression.wirecodec import IndexCodec

    class B:
        pass

    b = B()
    b.rows = 3
    b.row_offsets = np.array([0, 4096, 8192], np.int64)
    b.numels = np.array([4095, 4097, 7], np.int64)
    b.num_selects = np.array([5, 5, 3], np.int32)
    b.max_sel = 5
    # tight payload layout (what _bucket_from_rows builds for these
    # uneven quotas): rows 0-1 full, row 2 takes 3 of 5 grid slots
    b.tight = np.array([0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12],
                       np.int64)
    codec = IndexCodec([b])
    assert list(codec.widths[:5]) == [12] * 5          # 4095 -> 12 bits
    assert list(codec.widths[5:10]) == [13] * 5        # 4097 -> 13 bits
    assert list(codec.widths[10:]) == [3] * 3          # 7 -> 3 bits
    idx = np.array([0, 4094, 1, 4093, 2,
                    4096, 4096 + 4096, 4096 + 1, 4096 + 4095, 4096,
                    8192, 8192 + 6, 8192 + 3], np.int64)
    words = codec.encode(jnp.asarray(idx, jnp.int32))
    back = np.asarray(codec.decode(words))
    np.testing.assert_array_equal(back, idx)


# ------------------------------------------------------------------ #
# delta-coded (Elias-Fano) index wire + int4 nibble packing          #
# (compression/wirecodec.py, the int8_delta_idx/int4_packed regimes) #
# ------------------------------------------------------------------ #


def _fake_bucket(base, cols, numels, num_selects):
    """A bucket-shaped object with the flat engine's grid invariants:
    row r spans [base + r*cols, base + r*cols + numel_r), numel_r <=
    cols, tight payload layout over per-row quotas."""

    class B:
        pass

    b = B()
    b.base = int(base)
    b.cols = int(cols)
    b.rows = len(numels)
    b.row_offsets = base + np.arange(b.rows, dtype=np.int64) * cols
    b.numels = np.asarray(numels, np.int64)
    ns = np.asarray(num_selects, np.int32)
    b.num_selects = ns
    b.max_sel = int(ns.max())
    b.payload = int(ns.sum())
    tight = [r * b.max_sel + k for r, n in enumerate(ns) for k in range(n)]
    b.tight = np.asarray(tight, np.int64)
    return b


def _ef_encode_oracle(codec, gidx):
    """Bit-by-bit NumPy Elias-Fano encoder: per bucket, slot j's low
    ``s`` bits at bit offset ``j*s`` of the low region, high bit at
    position ``high_j + j`` of the high region."""
    words = np.zeros(codec.nwords, np.uint32)

    def set_bit(t):
        words[t >> 5] |= np.uint32(1) << np.uint32(t & 31)

    canon = np.asarray(codec.canonical(jnp.asarray(gidx, jnp.int32)))
    p0 = 0
    for m in codec.meta:
        p, s = m["p"], m["s"]
        for j in range(p):
            g = int(canon[p0 + j]) - m["base"]
            for k in range(s):
                if (g >> k) & 1:
                    set_bit(m["low_w0"] * 32 + j * s + k)
            set_bit(m["high_w0"] * 32 + (g >> s) + j)
        p0 += p
    return words


def _sorted_bucket_indices(rng, bucket):
    """Random in-row indices, sorted within the bucket — the engine's
    pre-encode contract (``_sort_delta_payload``). Rows occupy disjoint
    ascending flat ranges, so the global sort lands each row's indices
    exactly on that row's payload slots."""
    rows = np.asarray(bucket.tight) // bucket.max_sel
    out = [int(bucket.row_offsets[r]) + rng.randint(0, int(bucket.numels[r]))
           for r in rows]
    return np.sort(np.asarray(out, np.int64))


def test_delta_index_codec_roundtrip_edges():
    """Elias-Fano round-trip at the edge geometries: a 1-row bucket, a
    payload == full-grid bucket (s == 0, high-bits-only), a deep-s
    sparse bucket, and a multi-bucket stream with offset bases and
    ragged numels. Indices are bitwise-exact against the input and the
    packed words bitwise-exact against a NumPy bit-by-bit oracle."""
    from dgc_tpu.compression.wirecodec import DeltaIndexCodec

    geometries = [
        # one row, modest sparsity
        [_fake_bucket(0, 64, [50], [5])],
        # max_sel == cols: every grid slot selected, p == U forces s=0
        [_fake_bucket(0, 4, [4, 4], [4, 4])],
        # deep s: 300k-slot grid, 21 selected -> 13 low bits per index
        [_fake_bucket(0, 100_000, [99_997, 100_000, 12_345], [7, 5, 9])],
        # two buckets, second base far from zero, ragged numels
        [_fake_bucket(0, 128, [100, 128, 3], [6, 6, 2]),
         _fake_bucket(4096, 512, [500], [17])],
    ]
    rng = np.random.RandomState(7)
    for buckets in geometries:
        codec = DeltaIndexCodec(buckets)
        assert codec.payload == sum(b.payload for b in buckets)
        assert codec.nwords == sum(codec.bucket_words)
        for _ in range(3):
            gidx = np.concatenate([_sorted_bucket_indices(rng, b)
                                   for b in buckets])
            words = np.asarray(
                jax.jit(codec.encode)(jnp.asarray(gidx, jnp.int32)))
            assert words.dtype == np.uint32
            assert words.shape == (codec.nwords,)
            np.testing.assert_array_equal(
                words, _ef_encode_oracle(codec, gidx),
                err_msg="wire words differ from the NumPy oracle")
            back = np.asarray(jax.jit(codec.decode)(
                jnp.asarray(words, jnp.uint32)))
            np.testing.assert_array_equal(back, gidx)
        # batched decode (the gathered [W, nwords] wire)
        gidx_w = np.stack([np.concatenate(
            [_sorted_bucket_indices(rng, b) for b in buckets])
            for _ in range(W)])
        words_w = jnp.stack([codec.encode(jnp.asarray(gidx_w[w], jnp.int32))
                             for w in range(W)])
        back_w = np.asarray(jax.jit(codec.decode)(words_w))
        np.testing.assert_array_equal(back_w, gidx_w)


def test_delta_index_codec_all_pad_bucket():
    """All-structural-pad bucket: every payload slot carries the global
    scatter sentinel (no threshold passers). The wire must decode to
    the CANONICAL stream — each sentinel clipped to its row's last
    element — which is the decode(encode(x)) fixed point the engine's
    0.0-valued pad slots ride safely."""
    from dgc_tpu.compression.wirecodec import DeltaIndexCodec

    b = _fake_bucket(256, 32, [20, 7, 32], [4, 4, 4])
    codec = DeltaIndexCodec([b])
    sentinel = 10 ** 6  # far outside every row
    gidx = np.full(b.payload, sentinel, np.int64)
    canon = np.asarray(codec.canonical(jnp.asarray(gidx, jnp.int32)))
    # clipped-to-row-end positions are nondecreasing across the tight
    # layout, so the sorted-input contract already holds
    assert np.all(np.diff(canon) >= 0)
    words = codec.encode(jnp.asarray(gidx, jnp.int32))
    back = np.asarray(codec.decode(words))
    np.testing.assert_array_equal(back, canon)
    np.testing.assert_array_equal(
        np.asarray(words), _ef_encode_oracle(codec, gidx))


def test_delta_index_codec_rejects_oversized_universe():
    from dgc_tpu.compression.wirecodec import DeltaIndexCodec

    b = _fake_bucket(0, 2 ** 30, [2 ** 30, 2 ** 30], [1, 1])
    with pytest.raises(ValueError, match="2\\^31"):
        DeltaIndexCodec([b])


def test_int4_pack_unpack_oracle():
    """Two-nibbles-per-byte packing round-trips every value in [-8, 7]
    at odd and even lengths, matches a NumPy byte oracle, and unpacks
    batched (the gathered [W, nbytes] wire)."""
    from dgc_tpu.compression.wirecodec import pack_int4, unpack_int4

    rng = np.random.RandomState(3)
    for n in (1, 2, 7, 8, 33):
        q = rng.randint(-8, 8, size=n).astype(np.int32)
        packed = np.asarray(jax.jit(pack_int4)(jnp.asarray(q)))
        assert packed.dtype == np.int8
        assert packed.shape == ((n + 1) // 2,)
        # byte oracle: even slot = low nibble, odd = high, zero pad
        qp = np.concatenate([q, np.zeros(n % 2, np.int32)])
        oracle = ((qp[0::2] & 15) | ((qp[1::2] & 15) << 4)).astype(
            np.uint8).view(np.int8)
        np.testing.assert_array_equal(packed, oracle)
        back = np.asarray(unpack_int4(jnp.asarray(packed), n))
        np.testing.assert_array_equal(back, q)
    # full nibble range survives sign-extension
    q = np.arange(-8, 8, dtype=np.int32)
    np.testing.assert_array_equal(
        np.asarray(unpack_int4(pack_int4(jnp.asarray(q)), 16)), q)
    # batched leading axis
    qw = rng.randint(-8, 8, size=(W, 9)).astype(np.int32)
    pw = jnp.stack([pack_int4(jnp.asarray(qw[w])) for w in range(W)])
    np.testing.assert_array_equal(np.asarray(unpack_int4(pw, 9)), qw)


def test_flat_delta_idx_bitwise_matches_int8(mesh8):
    """int8_delta_idx is int8 plus a different index wire: the decoded
    exchange and memory state must equal the int8 plan's BITWISE —
    the per-bucket payload sort permutes (value, index) pairs together
    and scatter-add is order-invariant over disjoint canonical slots."""
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.compression.planner import BUILTIN_FABRICS, Plan

    params = _params()
    named, _ = named_flatten(params)
    compressed = [n for n, p in named.items() if p.ndim > 1]
    layout = ParamLayout(params, compressed)
    fab = BUILTIN_FABRICS["32x25GbE"]

    def build(regime):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        nb = len(FlatDGCEngine(comp, layout).buckets)
        engine = FlatDGCEngine(comp, layout,
                               plan=Plan((regime,) * nb, fab, W))
        return engine, _flat_exchange_fn(dist, engine, mesh8)

    eng_d, fn_d = build("int8_delta_idx")
    eng_8, fn_8 = build("int8")
    # the delta wire must actually be smaller than the int32-index int8
    # wire (that is the whole point of the regime)
    assert eng_d.wire_bytes_per_worker() < eng_8.wire_bytes_per_worker()
    # and lane-exact per bucket: the per-bucket split sums to the total
    assert sum(eng_d.bucket_wire_bytes()) == eng_d.wire_bytes_per_worker()

    rng = np.random.RandomState(5)
    g = rng.randn(W, layout.total).astype(np.float32)
    covered = np.zeros((layout.total,), bool)
    for n in layout.names:
        covered[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    g[:, ~covered] = 0.0
    fg = jnp.asarray(g)

    def init_mem(engine):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
            engine.init_memory())

    mem_d, mem_8 = init_mem(eng_d), init_mem(eng_8)
    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_d, mem_d = fn_d(fg, mem_d, key)
        out_8, mem_8 = fn_8(fg, mem_8, key)
        np.testing.assert_array_equal(np.asarray(out_d[0]),
                                      np.asarray(out_8[0]),
                                      err_msg=f"step {step}")
        fd = _mem_full(eng_d, mem_d, w=0)
        f8 = _mem_full(eng_8, mem_8, w=0)
        for mk in ("momentums", "velocities"):
            np.testing.assert_array_equal(fd[mk], f8[mk],
                                          err_msg=f"{mk} step {step}")


def test_flat_int4_plan_tracks_fp32(mesh8):
    """int4_packed: per-bucket scale/7 quantization bounds each
    worker's per-value error by scale/2, so the W-worker sum stays
    within W/14 of the fp32 exchange's dynamic range — and the wire is
    smaller than the int8 regime's."""
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.compression.planner import BUILTIN_FABRICS, Plan

    params = _params()
    named, _ = named_flatten(params)
    compressed = [n for n, p in named.items() if p.ndim > 1]
    layout = ParamLayout(params, compressed)
    fab = BUILTIN_FABRICS["32x25GbE"]

    def build(regime):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        nb = len(FlatDGCEngine(comp, layout).buckets)
        engine = FlatDGCEngine(comp, layout,
                               plan=Plan((regime,) * nb, fab, W))
        return engine, _flat_exchange_fn(dist, engine, mesh8)

    eng_4, fn_4 = build("int4_packed")
    eng_f, fn_f = build("fp32")
    eng_8, _ = build("int8")
    assert eng_4.wire_bytes_per_worker() < eng_8.wire_bytes_per_worker()
    assert sum(eng_4.bucket_wire_bytes()) == eng_4.wire_bytes_per_worker()

    rng = np.random.RandomState(9)
    g = rng.randn(W, layout.total).astype(np.float32)
    covered = np.zeros((layout.total,), bool)
    for n in layout.names:
        covered[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    g[:, ~covered] = 0.0
    fg = jnp.asarray(g)

    def init_mem(engine):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
            engine.init_memory())

    mem_4, mem_f = init_mem(eng_4), init_mem(eng_f)
    for step in range(2):
        key = jax.random.PRNGKey(step)
        out_4, mem_4 = fn_4(fg, mem_4, key)
        out_f, mem_f = fn_f(fg, mem_f, key)
        o4 = np.asarray(out_4[0])
        of = np.asarray(out_f[0])
        scale = np.abs(of).max()
        d = np.abs(o4 - of)
        # guaranteed per-value bound: W workers x scale/14 each
        assert d.max() <= W / 14 * scale + 1e-6, (d.max(), scale)
        # and quantization noise, not bias: tiny RMS over the buffer
        assert np.sqrt(np.mean(d ** 2)) <= 0.05 * scale


def test_flat_packed_indices_matches_unpacked(mesh8):
    """packed_indices=True (configs/dgc/packidx.py): the exchange result
    and memory state equal the int32-index wire's exactly — decoded
    indices are bit-exact for real slots, and padded slots contribute
    value 0.0 wherever they land."""
    params = _params()
    named, _ = named_flatten(params)

    def make(packed):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, packed_indices=packed)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        layout, engine = dist.make_flat(params)
        return dist, layout, engine

    dist_u, layout, engine_u = make(False)
    dist_p, _, engine_p = make(True)
    assert engine_u._codec is None and engine_p._codec is not None

    rng = np.random.RandomState(11)
    from dgc_tpu.utils.pytree import named_unflatten
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    flat_grads_w = jnp.stack([
        layout.flatten(named_unflatten({n: grads_w[n][w] for n in named},
                                       named_flatten(params)[1]))
        for w in range(W)])

    fn_u = _flat_exchange_fn(dist_u, engine_u, mesh8)
    fn_p = _flat_exchange_fn(dist_p, engine_p, mesh8)
    mem_u = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_u.init_memory())
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_p.init_memory())
    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_u, mem_u = fn_u(flat_grads_w, mem_u, key)
        out_p, mem_p = fn_p(flat_grads_w, mem_p, key)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_u),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=f"step {step}")
        fu = _mem_full(engine_u, mem_u, w=0)
        fp = _mem_full(engine_p, mem_p, w=0)
        for mkey in ("momentums", "velocities"):
            np.testing.assert_allclose(fp[mkey], fu[mkey],
                                       rtol=1e-6, atol=1e-7,
                                       err_msg=f"{mkey} step {step}")


def test_flat_packed_indices_with_int8(mesh8):
    """packed indices compose with the int8 value wire: combined wire
    matches the unpacked int8 exchange."""
    params = _params()
    named, _ = named_flatten(params)

    def make(packed):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, int8_values=True,
                             packed_indices=packed)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        layout, engine = dist.make_flat(params)
        return dist, layout, engine

    dist_u, layout, engine_u = make(False)
    dist_p, _, engine_p = make(True)
    rng = np.random.RandomState(13)
    from dgc_tpu.utils.pytree import named_unflatten
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    flat_grads_w = jnp.stack([
        layout.flatten(named_unflatten({n: grads_w[n][w] for n in named},
                                       named_flatten(params)[1]))
        for w in range(W)])
    fn_u = _flat_exchange_fn(dist_u, engine_u, mesh8)
    fn_p = _flat_exchange_fn(dist_p, engine_p, mesh8)
    mem_u = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_u.init_memory())
    mem_p = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_p.init_memory())
    for step in range(2):
        key = jax.random.PRNGKey(step)
        out_u, mem_u = fn_u(flat_grads_w, mem_u, key)
        out_p, mem_p = fn_p(flat_grads_w, mem_p, key)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_u),
                                   rtol=1e-6, atol=1e-7,
                                   err_msg=f"step {step}")


def test_exchange_fused_apply_matches_fallback(mesh8):
    """CPU-oracle parity of the fused apply epilogue
    (``DGCCompressor(fused_apply=True)`` ->
    ``kernels.payload_apply_bits`` in interpret mode) against the XLA
    scatter fallback: per-worker selection keys differ, so cross-worker
    duplicate coordinates exist and the scatter-add order genuinely
    matters — the staging sort is stable, so duplicate contributions
    keep payload order and the comparison is EXACT, transmit record
    included."""
    params = _params()
    named, _ = named_flatten(params)

    def make(fused):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, fused_apply=fused)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        layout, engine = dist.make_flat(params)
        return dist, layout, engine

    dist_u, layout, engine_u = make(False)
    dist_f, _, engine_f = make(True)
    # the routing gate itself: the fused engine must actually take the
    # fused path (flag + memory + f32 wire + aligned T)
    assert not engine_u._use_fused_apply(engine_u._mem, False, jnp.float32)
    assert engine_f._use_fused_apply(engine_f._mem, False, jnp.float32)

    rng = np.random.RandomState(17)
    from dgc_tpu.utils.pytree import named_unflatten
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    flat_grads_w = jnp.stack([
        layout.flatten(named_unflatten({n: grads_w[n][w] for n in named},
                                       named_flatten(params)[1]))
        for w in range(W)])
    fn_u = _flat_exchange_fn(dist_u, engine_u, mesh8)
    fn_f = _flat_exchange_fn(dist_f, engine_f, mesh8)
    mem_u = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_u.init_memory())
    mem_f = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                         engine_f.init_memory())
    for step in range(3):
        key = jax.random.PRNGKey(step)
        out_u, mem_u = fn_u(flat_grads_w, mem_u, key)
        out_f, mem_f = fn_f(flat_grads_w, mem_f, key)
        np.testing.assert_array_equal(np.asarray(out_f), np.asarray(out_u),
                                      err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(mem_f["sent_bits"]),
                                      np.asarray(mem_u["sent_bits"]),
                                      err_msg=f"bits step {step}")
        fu = _mem_full(engine_u, mem_u, w=0)
        ff = _mem_full(engine_f, mem_f, w=0)
        for mkey in ("momentums", "velocities"):
            np.testing.assert_array_equal(ff[mkey], fu[mkey],
                                          err_msg=f"{mkey} step {step}")


def test_sparsify_with_fused_candidates_matches_standalone(monkeypatch):
    """The fused compensate+candidates path: ``sparsify(x, key,
    seg_cands=...)`` with candidates from
    ``kernels.fused_compensate_bits_cands`` must be BITWISE the
    standalone seg-kernel path ``sparsify(x, key)`` — the engine swaps
    where candidates come from, never what they are. Candidates for an
    arbitrary x are obtained by feeding the fused kernel zero state and
    zero bits (then ov == x exactly: m = momentum*0 + x, v = 0 + m)."""
    from dgc_tpu.compression.flat import FlatDGCEngine
    from dgc_tpu.ops import kernels

    monkeypatch.setattr(FlatDGCEngine, "SEL3D_MIN_COLS", 1024 * 1024)
    numel = 1_200_000
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.01)
    comp.initialize([("w", (numel, (numel,)))])
    params = {"w": jax.ShapeDtypeStruct((numel,), jnp.float32)}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    [b] = engine.buckets
    assert engine._use_seg_kernel(b) and engine._seg_fused

    T = layout.t_compressed
    rng = np.random.RandomState(31)
    x = np.zeros((T,), np.float32)
    x[:numel] = rng.randn(numel).astype(np.float32)
    xj = jnp.asarray(x)
    z = jnp.zeros((T,), jnp.float32)
    bits = jnp.zeros((kernels.num_sent_words(T),), jnp.int32)
    _, ov, cv, ci = kernels.fused_compensate_bits_cands(
        xj, z, z, bits, 0.9, False, True)
    np.testing.assert_array_equal(np.asarray(ov), x)
    key = jax.random.PRNGKey(5)
    v0, i0 = jax.jit(engine.sparsify)(xj, key)
    v1, i1 = jax.jit(lambda a, k, c: engine.sparsify(a, k, seg_cands=c))(
        xj, key, (cv, ci))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


def test_sparsify_with_fused_candidates_multi_bucket(monkeypatch):
    """Same bitwise contract as
    test_sparsify_with_fused_candidates_matches_standalone, but across
    MULTIPLE seg-kernel buckets with R>1: two same-size tensors share a
    bucket (R=2) that sits at a nonzero ``b.base``, behind a single-row
    giant bucket at base 0. This exercises the candidate-stream slice
    ``cv_all[sb:sb + R*nsr]`` with a nonzero segment offset ``sb`` and
    the ``sb + r*nsr + s`` segment ordering end to end — a stream
    off-by-one would scramble bucket 1's candidates, not bucket 0's."""
    from dgc_tpu.compression.flat import FlatDGCEngine
    from dgc_tpu.ops import kernels

    monkeypatch.setattr(FlatDGCEngine, "SEL3D_MIN_COLS", 1024 * 1024)
    numels = {"a": 1_200_000, "b": 1_200_000, "c": 2_400_000}
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=0.01)
    comp.initialize([(n, (sz, (sz,))) for n, sz in numels.items()])
    params = {n: jax.ShapeDtypeStruct((sz,), jnp.float32)
              for n, sz in numels.items()}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    # the geometry this test exists for: 2 buckets, all on the kernel
    # path, one multi-row, one at a nonzero segment-aligned base
    assert len(engine.buckets) == 2
    assert all(engine._use_seg_kernel(b) for b in engine.buckets)
    assert engine._seg_fused
    assert any(b.rows > 1 for b in engine.buckets)
    assert any(b.base > 0 for b in engine.buckets)
    span = kernels._SEG_BLOCKS * 128
    assert all(b.base % span == 0 for b in engine.buckets)

    rng = np.random.RandomState(37)
    arrs = {n: jnp.asarray(rng.randn(sz).astype(np.float32))
            for n, sz in numels.items()}
    T = layout.t_compressed
    xj = layout.flatten(arrs)[:T]
    z = jnp.zeros((T,), jnp.float32)
    bits = jnp.zeros((kernels.num_sent_words(T),), jnp.int32)
    _, ov, cv, ci = kernels.fused_compensate_bits_cands(
        xj, z, z, bits, 0.9, False, True)
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(xj))
    key = jax.random.PRNGKey(9)
    v0, i0 = jax.jit(engine.sparsify)(xj, key)
    v1, i1 = jax.jit(lambda a, k, c: engine.sparsify(a, k, seg_cands=c))(
        xj, key, (cv, ci))
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    # both buckets actually transmitted: payload indices land in each
    # bucket's extent (a silent one-bucket selection would still pass
    # the bitwise checks above)
    i0 = np.asarray(i0)
    real = i0[i0 != layout.sentinel]
    for b in engine.buckets:
        hits = ((real >= b.base) & (real < b.base + b.rows * b.cols)).sum()
        assert hits > 0, (b.base, b.rows, b.cols)


@pytest.mark.parametrize("state_dtype", [None, "bfloat16"])
def test_3d_seg_top2_kernel_selection_path(monkeypatch, state_dtype):
    """The segment-top-2 candidates kernel path (cells >= 3*num_selects):
    same payload invariants and near-exact CPU recall as the approx 3-D
    path, with values taken from the kernel's candidate stream instead
    of a payload gather. Parameterized over the narrow (bf16)
    error-feedback state: the kernel up-casts in VMEM and the engine
    casts back, so the vals == vec[idx] round-trip must stay exact."""
    from dgc_tpu.compression.flat import FlatDGCEngine
    from dgc_tpu.ops import kernels

    monkeypatch.setattr(FlatDGCEngine, "SEL3D_MIN_COLS", 1024 * 1024)
    numel = 1_200_000
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9,
                                                    dtype=state_dtype),
                         sample_ratio=0.01)
    comp.initialize([("w", (numel, (numel,)))])
    params = {"w": jax.ShapeDtypeStruct((numel,), jnp.float32)}
    dist = DistributedOptimizer(dgc_sgd(0.1), comp, world_size=1)
    layout, engine = dist.make_flat(params)
    [b] = engine.buckets
    assert engine._use_3d(b)
    cells = (b.cols // 128 // kernels._SEG_BLOCKS) * 128
    assert cells >= 3 * b.max_sel
    assert kernels.seg_top2_eligible(layout.t_compressed // 128, b.base,
                                     b.cols)
    # the ROUTING gate itself — sparsify must actually take the kernel
    # path, not silently fall back to the approx 3-D form
    assert engine._use_seg_kernel(b)

    a = comp.attributes["w"]
    rng = np.random.RandomState(23)
    vdt = jnp.bfloat16 if state_dtype else jnp.float32
    vec = np.zeros((layout.t_compressed,), np.float32)
    vec[:numel] = rng.randn(numel).astype(np.float32)
    vec = np.asarray(jnp.asarray(vec, vdt).astype(jnp.float32))
    vals, idx = jax.jit(engine.sparsify)(jnp.asarray(vec, vdt),
                                         jax.random.PRNGKey(0))
    assert vals.dtype == vdt
    vals = np.asarray(vals.astype(jnp.float32))
    idx = np.asarray(idx)
    real = idx != layout.sentinel
    count = int(real.sum())
    assert 0.8 * a.num_selects * 0.9 <= count <= a.num_selects
    assert (idx[real] < numel).all() and (idx[real] >= 0).all()
    np.testing.assert_array_equal(vals[real], vec[idx[real]])
    assert len(np.unique(idx[real])) == count
    exact = np.argsort(-np.abs(vec[:numel]))[:count]
    recall = len(set(exact.tolist()) & set(idx[real].tolist())) / count
    assert recall >= 0.93 if state_dtype else recall >= 0.95, recall


@pytest.mark.parametrize("sparse_regime", ["fp32", "int8_packed"])
def test_flat_mixed_plan_matches_uniform_mixture(mesh8, sparse_regime):
    """A mixed exchange plan (sparse bucket 0 + dense-planned bucket 1)
    must produce, slab for slab, EXACTLY what the uniform engines
    produce: bucket 0's output and memory match the uniform sparse
    engine, bucket 1's and the dense tail's match the all-dense plan —
    the planner changes the wire, never the math."""
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.compression.planner import BUILTIN_FABRICS, Plan

    rng = np.random.RandomState(0)
    params = {
        "big": {"kernel": jnp.asarray(rng.randn(600, 600), jnp.float32)},
        "small": {"kernel": jnp.asarray(rng.randn(40, 50), jnp.float32)},
        "bias": {"b": jnp.asarray(rng.randn(16), jnp.float32)},
    }
    named, _ = named_flatten(params)
    compressed = [n for n, p in named.items() if p.ndim > 1]
    layout = ParamLayout(params, compressed)
    fab = BUILTIN_FABRICS["32x25GbE"]

    def build(regimes):
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        engine = FlatDGCEngine(comp, layout, plan=Plan(regimes, fab, W))
        return engine, _flat_exchange_fn(dist, engine, mesh8)

    eng_mix, fn_mix = build((sparse_regime, "dense"))
    eng_sp, fn_sp = build((sparse_regime, sparse_regime))
    eng_dn, fn_dn = build(("dense", "dense"))
    assert len(eng_mix.buckets) == 2
    assert eng_mix.regimes == (sparse_regime, "dense")
    assert eng_dn.plan.all_dense

    g = rng.randn(W, layout.total).astype(np.float32)
    # zero the structural-pad slots so flat buffers are well-formed
    covered = np.zeros((layout.total,), bool)
    for n in layout.names:
        covered[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    g[:, ~covered] = 0.0
    fg = jnp.asarray(g)

    def init_mem(engine):
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
            engine.init_memory())

    mems = [init_mem(e) for e in (eng_mix, eng_sp, eng_dn)]
    b0, b1 = eng_mix.buckets
    s0 = slice(b0.base, b0.base + b0.rows * b0.cols)
    s1 = slice(b1.base, b1.base + b1.rows * b1.cols)
    tail = slice(layout.t_compressed, layout.total)

    for step in range(2):
        key = jax.random.PRNGKey(step)
        (o_mix, mems[0]), (o_sp, mems[1]), (o_dn, mems[2]) = (
            fn(fg, m, key) for fn, m in zip((fn_mix, fn_sp, fn_dn), mems))
        o_mix, o_sp, o_dn = (np.asarray(o[0]) for o in (o_mix, o_sp, o_dn))
        # sparse-planned slab == uniform sparse engine, bitwise (the
        # allgather wire carries identical payloads in both builds)
        np.testing.assert_array_equal(o_mix[s0], o_sp[s0],
                                      err_msg=f"step {step} bucket0")
        # dense-planned slab + tail == all-dense plan to 1 ULP: the psum
        # covers a differently-offset buffer (concat wire vs whole [P]),
        # so the ring reduction may associate additions differently
        np.testing.assert_allclose(o_mix[s1], o_dn[s1], rtol=2e-7,
                                   atol=1e-7,
                                   err_msg=f"step {step} bucket1")
        np.testing.assert_allclose(o_mix[tail], o_dn[tail], rtol=2e-7,
                                   atol=1e-7, err_msg=f"step {step} tail")
        full_mix = _mem_full(eng_mix, mems[0], w=0)
        full_sp = _mem_full(eng_sp, mems[1], w=0)
        full_dn = _mem_full(eng_dn, mems[2], w=0)
        for mk in ("momentums", "velocities"):
            np.testing.assert_array_equal(
                full_mix[mk][s0], full_sp[mk][s0],
                err_msg=f"step {step} {mk} bucket0")
            np.testing.assert_allclose(
                full_mix[mk][s1], full_dn[mk][s1], rtol=2e-7, atol=1e-7,
                err_msg=f"step {step} {mk} bucket1")


# ------------------------------------------------------------------ #
# the exchange's stages, alone (no mesh)                             #
# ------------------------------------------------------------------ #

def _three_bucket_engine(regimes, **mem_kw):
    """An engine over three size buckets (700k, 360k and 2k elements a
    row) under ``regimes``: one name for all three, or one a bucket."""
    from dgc_tpu.compression.flat import FlatDGCEngine
    from dgc_tpu.compression.planner import BUILTIN_FABRICS, Plan

    shapes = {"a": (1000, 700), "b": (600, 600), "c": (40, 50)}
    params = {n: {"kernel": jnp.zeros(s, jnp.float32)}
              for n, s in shapes.items()}
    params["bias"] = {"b": jnp.zeros((16,), jnp.float32)}
    named, _ = named_flatten(params)
    compressed = [n for n, p in named.items() if p.ndim > 1]
    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9, **mem_kw),
                         sample_ratio=1.0)
    comp.initialize((n, named[n]) for n in compressed)
    engine = FlatDGCEngine(
        comp, ParamLayout(params, compressed),
        plan=Plan(regimes if isinstance(regimes, tuple) else (regimes,) * 3,
                  BUILTIN_FABRICS["32x25GbE"], W))
    assert len(engine.buckets) == 3
    return engine


_WIRE_PLANS = {r: r for r in _REGIMES if r != "dense"}
_WIRE_PLANS["mixed_plain_and_words"] = ("int8", "fp16_packed", "int4_packed")
_WIRE_PLANS["mixed_words_and_dense"] = ("int8_delta_idx", "dense",
                                        "fp32_packed")


@pytest.mark.parametrize("plan", sorted(_WIRE_PLANS))
def test_wire_roundtrip_without_a_mesh(plan):
    """The wire alone: ``decode(stack([encode(values, indices)]))`` of one
    worker's payload, for every sparse regime and two mixed plans. Indices
    come back exactly (the delta codec's as the sorted permutation it
    ships), values exactly (fp32), to half precision (fp16) or within one
    quantisation step of their row's (int8) or bucket's (int4) scale —
    and the static wire figure is the bytes of the lanes encode made."""
    from dgc_tpu.compression.flat import _Lanes

    engine = _three_bucket_engine(_WIRE_PLANS[plan])
    S = engine.layout.sentinel
    rng = np.random.RandomState(3)
    vec = np.zeros((engine.T,), np.float32)
    for b in engine.buckets:
        for r, n in enumerate(b.numels):
            lo = b.base + r * b.cols
            vec[lo:lo + n] = rng.standard_t(3, n)
    values, indices = engine.sparsify(jnp.asarray(vec),
                                      jax.random.PRNGKey(0))
    if engine._dcodec is not None:
        values, indices = engine._sort_delta_payload(values, indices)
    assert values.shape == indices.shape == (engine.payload_size,)

    lanes, _ = engine._encode_values(values)
    index_lanes = engine._encode_indices(indices)
    lanes = lanes._replace(words=index_lanes.words, plain=index_lanes.plain)
    assert engine.wire_bytes_per_worker() == sum(
        lane.nbytes for lane in lanes if lane is not None)
    stacked = _Lanes(*[None if lane is None else lane[None]
                       for lane in lanes])
    g_values = np.asarray(engine._decode_values(stacked, jnp.float32))
    g_indices, g_chk = engine._decode_indices(stacked)
    assert g_chk is None
    assert g_values.shape == g_indices.shape == (1, engine.payload_size)

    v, i = np.asarray(values), np.asarray(indices)
    real = i != S
    assert real.sum() > 0.9 * engine.payload_size
    # a padded slot may decode to any slot of its row: it carries 0.0
    np.testing.assert_array_equal(np.asarray(g_indices)[0][real], i[real])
    assert not v[~real].any() and not g_values[0][~real].any()
    for (s0, s1), kind, b in zip(engine._payload_slices, engine._kinds,
                                 engine._sparse_buckets):
        got, want = g_values[0, s0:s1], v[s0:s1]
        if kind == "f32":
            np.testing.assert_array_equal(got, want)
        elif kind == "f16":
            np.testing.assert_array_equal(
                got, want.astype(np.float16).astype(np.float32))
        elif kind == "i8":
            row = b.tight // b.max_sel
            scale = np.zeros((b.rows,), np.float32)
            np.maximum.at(scale, row, np.abs(want))
            assert (np.abs(got - want) <= scale[row] / 127 + 1e-12).all()
        else:
            assert kind == "i4"
            assert (np.abs(got - want)
                    <= np.abs(want).max() / 7 + 1e-12).all()


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("momentum_masking", [False, True])
def test_dense_correction_whole_equals_slabs(nesterov, momentum_masking):
    """The dense correction is one helper for the all-dense step (the
    whole compressed block) and for dense-planned slabs: over a partition
    of the block, slab by slab, it is bitwise the whole block's."""
    from dgc_tpu.ops import kernels

    engine = _three_bucket_engine("fp32", nesterov=nesterov,
                                  momentum_masking=momentum_masking)
    T = engine.T
    rng = np.random.RandomState(11)
    avg, mmt, vec = (jnp.asarray(rng.randn(T).astype(np.float32))
                     for _ in range(3))
    sent = rng.choice(T, T // 50, replace=False).astype(np.int32)
    keep = kernels.keep_from_bits(
        kernels.pack_sent_bits(jnp.asarray(sent), T,
                               sentinel=engine.layout.sentinel), T)
    assert 0 < int((np.asarray(keep) == 0).sum()) <= sent.size
    cuts = [0] + [b.base for b in engine.buckets[1:]] + [T]
    for k in (keep, None):
        whole = engine._dense_correct(avg, mmt, vec, k, 0, T)
        slabs = [engine._dense_correct(avg[lo:hi], mmt, vec, k, lo, hi)
                 for lo, hi in zip(cuts[:-1], cuts[1:])]
        for got, want in zip(zip(*slabs), whole):
            np.testing.assert_array_equal(
                np.concatenate([np.asarray(x) for x in got]),
                np.asarray(want))
    # the mask did something, and momentum masking decides where
    _, m_kept, v_kept = engine._dense_correct(avg, mmt, vec, keep, 0, T)
    _, m_all, v_all = engine._dense_correct(avg, mmt, vec, None, 0, T)
    assert (np.asarray(v_kept) != np.asarray(v_all)).any()
    assert (np.asarray(m_kept) != np.asarray(m_all)).any() == momentum_masking

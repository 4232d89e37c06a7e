"""Megakernel parity oracles (dgc_tpu.ops.kernels.dgc_forward_rows /
dgc_apply_rows) and the engine-level megakernel path
(``DGCCompressor(megakernel=True)``) on the fake 8-device CPU mesh.

Kernel oracles compare against the JITTED jnp references: XLA CPU
contracts ``momentum * m + g`` into an FMA under jit but not in eager
mode, so the kernel is bitwise the jitted reference in every flag combo
(and the jitted reference is bitwise the jitted engine path — the thing
that actually matters). Engine tests run ``sample_ratio=1.0`` so
selection is deterministic and the megakernel engine must be BITWISE
the default unfused engine, transmit record and error-feedback state
included."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dgc_tpu import (
    DGCCompressor,
    DGCSGDMemory,
    DistributedOptimizer,
    dgc_sgd,
)
from dgc_tpu.ops import kernels
from dgc_tpu.utils.pytree import named_flatten, named_unflatten
from dgc_tpu.utils.compat import shard_map

W = 8

# jitted references — see module docstring for why jit is mandatory here
_ref_forward = jax.jit(
    kernels.dgc_forward_rows_reference,
    static_argnames=("base", "k", "momentum", "nesterov",
                     "momentum_masking"))
_ref_apply = jax.jit(
    kernels.dgc_apply_rows_reference,
    static_argnames=("total", "divisor"))


def _rand_bits(rng, total):
    """An arbitrary packed transmit record covering [0, total): any bit
    pattern is a valid input — realign/expansion only windows it."""
    w = kernels.num_sent_words(total)
    return jnp.asarray(
        rng.randint(-2 ** 31, 2 ** 31, size=w, dtype=np.int64)
        .astype(np.int32))


def _fwd_case(rng, R, cols, base, numels, k, total=None, **flags):
    n = R * cols
    total = total if total is not None else base + n
    g = jnp.asarray(rng.randn(n), jnp.float32)
    m = jnp.asarray(rng.randn(n), jnp.float32)
    v = jnp.asarray(rng.randn(n), jnp.float32)
    bits = _rand_bits(rng, total)
    numels = jnp.asarray(numels, jnp.int32)
    got = kernels.dgc_forward_rows(g, m, v, bits, base, numels, k, 0.9,
                                   **flags)
    want = _ref_forward(g, m, v, bits, base, numels, k, 0.9, **flags)
    for name, a, b in zip(("mmt", "vec", "scores", "values", "cols"),
                          got, want):
        np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b),
            err_msg=f"{name} R={R} cols={cols} k={k} base={base}")


@pytest.mark.parametrize("R,cols,base,numels,k", [
    (1, 128, 0, [128], 1),                 # minimal geometry
    (2, 256, 640, [256, 100], 16),         # ragged tail + funnel-shift base
    (3, 256, 128, [256, 100, 0], 8),       # an all-structural-pad row
    (1, 512, 0, [512], 129),               # k > 128: no delegate cliff
    (2, 384, 4096, [288, 320], 19),        # the engine's conv bucket shape
])
def test_forward_kernel_matches_jitted_reference(R, cols, base, numels, k):
    rng = np.random.RandomState(3 + R + k)
    _fwd_case(rng, R, cols, base, numels, k, total=base + R * cols + 512)


@pytest.mark.parametrize("nesterov", [False, True])
@pytest.mark.parametrize("momentum_masking", [False, True])
def test_forward_kernel_flag_combos(nesterov, momentum_masking):
    rng = np.random.RandomState(7)
    _fwd_case(rng, 2, 256, 640, [256, 100], 16,
              nesterov=nesterov, momentum_masking=momentum_masking)


def test_forward_kernel_max_multiround_k():
    """k == _MR_MAX_K == 1024: the widest selection the megakernel
    serves — the old ``max_sel <= 128`` reference cliff is 8x past."""
    rng = np.random.RandomState(11)
    _fwd_case(rng, 1, 1024, 0, [1024], kernels._MR_MAX_K)


def test_forward_kernel_refuses_bf16():
    g = jnp.zeros((128,), jnp.bfloat16)
    m = v = jnp.zeros((128,), jnp.float32)
    bits = jnp.zeros((128,), jnp.int32)
    numels = jnp.asarray([128], jnp.int32)
    with pytest.raises(ValueError, match="f32-only"):
        kernels.dgc_forward_rows(g, m, v, bits, 0, numels, 4, 0.9)
    with pytest.raises(ValueError, match="f32-only"):
        kernels.dgc_forward_rows(m, g, v, bits, 0, numels, 4, 0.9)


def _apply_case(rng, total, P_, divisor, donor=False, dupes=False):
    if dupes:
        idx = rng.randint(0, total, size=P_)
        flags = np.zeros(P_, bool)        # dupes may not be flagged
    else:
        idx = rng.choice(total, size=P_, replace=False)
        flags = rng.rand(P_) < 0.5        # pack_sent_bits needs uniqueness
    values = jnp.asarray(rng.randn(P_), jnp.float32)
    indices = jnp.asarray(idx, jnp.int32)
    flags = jnp.asarray(flags)
    bd = _rand_bits(rng, total) if donor else None
    acc, bits = kernels.dgc_apply_rows(values, indices, flags, total,
                                       bits_donor=bd, divisor=divisor)
    want_acc, want_bits = _ref_apply(values, indices, flags, total,
                                     divisor=divisor)
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(want_acc))
    np.testing.assert_array_equal(np.asarray(bits), np.asarray(want_bits))
    return acc, bits


@pytest.mark.parametrize("divisor", [None, 2.0, 8.0])
def test_apply_kernel_matches_jitted_reference(divisor):
    rng = np.random.RandomState(17)
    _apply_case(rng, 12800, 512, divisor)


def test_apply_kernel_donor_never_read():
    """The donated previous-step record only provides the buffer: the
    rebuilt bits equal the fresh-reference bits whatever it held."""
    rng = np.random.RandomState(19)
    _apply_case(rng, 12800, 512, 8.0, donor=True)


def test_apply_kernel_duplicate_indices_stable():
    """Cross-worker duplicate coordinates: the staging argsort is stable,
    so duplicate contributions keep payload order — bitwise the XLA
    scatter-add (which applies updates in order on duplicates)."""
    rng = np.random.RandomState(23)
    _apply_case(rng, 4096, 512, 8.0, dupes=True)


def test_apply_kernel_no_divisor_matches_fused_epilogue():
    """divisor=None is byte-identical semantics to payload_apply_bits —
    the megakernel-off contract at the output level."""
    rng = np.random.RandomState(29)
    total, P_ = 12800, 512
    idx = rng.choice(total, size=P_, replace=False)
    values = jnp.asarray(rng.randn(P_), jnp.float32)
    indices = jnp.asarray(idx, jnp.int32)
    flags = jnp.asarray(rng.rand(P_) < 0.5)
    a1, b1 = kernels.dgc_apply_rows(values, indices, flags, total)
    a2, b2 = kernels.payload_apply_bits(values, indices, flags, total)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))


@pytest.mark.parametrize("W", [1, 4])
@pytest.mark.parametrize("offered", [True, False])
@pytest.mark.parametrize("name, T, payload", [
    ("resnet50", 27_068_416, 25_583),
    ("vgg16_bn", 139_028_480, 138_360),
])
def test_apply_geometry_rule_on_the_benchmark_geometries(name, T, payload,
                                                         offered, W):
    """The rule `_apply`'s docstring states, on the static numbers of
    the two benchmark configurations (PERF.md §4), both world sizes and
    both answers to "does the step offer its optimizer's rule", without
    building a model: VGG-16-BN streams either way; ResNet-50 streams
    where the offer leaves no [T] accumulator to keep on the chip and
    keeps the zeros-scatter where one is written."""
    from dgc_tpu.compression.flat import FlatDGCEngine as E
    streams = offered or name == "vgg16_bn"
    assert E._apply_streams(T, W * payload, offered) is streams, name
    # the argument's default is the case that writes the accumulator
    assert E._apply_streams(T, W * payload) is (name == "vgg16_bn")
    # each case has its own boundary, in the block's bytes and nothing
    # else: the offered one lies under ResNet-50's T, the other over it
    floor = E.APPLY_UPDATE_MIN_BYTES if offered else E.APPLY_STREAM_MIN_BYTES
    assert (E.APPLY_UPDATE_MIN_BYTES < 4 * 27_068_416
            < E.APPLY_STREAM_MIN_BYTES)
    edge = floor // 4
    assert not E._apply_streams(edge, W * payload, offered)
    assert E._apply_streams(edge + 128, W * payload, offered)
    # the 128 MiB edge binds only the case without an offer
    over = E.APPLY_STREAM_MIN_BYTES // 4
    assert E._apply_streams(over, W * payload, offered) is offered
    # and the pairs' window maps have to fit the scalar memory, offer
    # or none
    assert E._apply_streams(over + 128, E.APPLY_STREAM_MAX_PAIRS, offered)
    assert not E._apply_streams(over + 128, E.APPLY_STREAM_MAX_PAIRS + 1,
                                offered)


@pytest.mark.parametrize("k", [257, 1024])
def test_select_pack_rows_no_delegation_past_128(k):
    """The VGG-16 fc regime (k in (128, 1024]) must run the multi-round
    kernel, not the XLA top_k reference — the 11.3 ms/step delegate
    cliff is the megakernel PR's headline kill."""
    rng = np.random.RandomState(31 + k)
    x = jnp.asarray(rng.randn(2, 4096), jnp.float32)
    numels = jnp.asarray([4096, 3000], jnp.int32)
    want = kernels.select_pack_rows_reference(x, numels, k)

    def boom(*a, **kw):
        raise AssertionError("select_pack_rows delegated to the reference")

    orig = kernels.select_pack_rows_reference
    kernels.select_pack_rows_reference = boom
    try:
        got = kernels.select_pack_rows(x, numels, k)
    finally:
        kernels.select_pack_rows_reference = orig
    for name, a, b in zip(("scores", "values", "cols"), got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name} k={k}")


# ------------------------------------------------------------------ #
# engine-level parity on the fake 8-device mesh                      #
# ------------------------------------------------------------------ #

def _params():
    rng = np.random.RandomState(0)
    return {
        "conv1": {"kernel": jnp.asarray(rng.randn(3, 3, 4, 8), jnp.float32)},
        "conv2": {"kernel": jnp.asarray(rng.randn(3, 3, 8, 8), jnp.float32)},
        "dense": {"kernel": jnp.asarray(rng.randn(32, 10), jnp.float32),
                  "bias": jnp.asarray(rng.randn(10), jnp.float32)},
        "bn": {"scale": jnp.asarray(rng.randn(8), jnp.float32)},
    }


def _make_engine(params, **kw):
    named, _ = named_flatten(params)
    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=1.0, **kw)
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=W)
    layout, engine = dist.make_flat(params)
    return layout, engine


def _exchange_fn(engine, mesh, send_frac=None):
    def worker(fg, mem, key):
        fg = fg[0]
        mem = jax.tree.map(lambda x: x[0], mem)
        key = jax.random.fold_in(key, jax.lax.axis_index("data"))
        out, mem = engine.exchange(fg, mem, key, "data", W,
                                   send_frac=send_frac)
        return out[None], jax.tree.map(lambda x: x[None], mem)

    return jax.jit(shard_map(
        worker, mesh=mesh, in_specs=(P("data"), P("data"), P()),
        out_specs=(P("data"), P("data")), check_vma=False))


def _flat_grads(layout, params, seed):
    named, treedef = named_flatten(params)
    rng = np.random.RandomState(seed)
    grads_w = {n: jnp.asarray(rng.randn(W, *p.shape), jnp.float32)
               for n, p in named.items()}
    return jnp.stack([
        layout.flatten(named_unflatten({n: grads_w[n][w] for n in named},
                                       treedef))
        for w in range(W)])


def _mem0(engine):
    return jax.tree.map(lambda x: jnp.broadcast_to(x[None], (W,) + x.shape),
                        engine.init_memory())


def _run_parity(mesh8, steps, mk_kwargs, send_frac=None, seed=37):
    """megakernel engine vs the default unfused engine: bitwise output,
    transmit record, and materialized error-feedback state per step."""
    params = _params()
    _, engine_u = _make_engine(params)
    layout, engine_m = _make_engine(params, **mk_kwargs)

    # the routing gates themselves: the megakernel engine must actually
    # take both fused passes, the default engine neither
    assert engine_u._mk_fwd_ids == ()
    assert engine_m._mk_fwd_ids, "no bucket took the forward megakernel"
    assert engine_m._use_megakernel_apply(engine_m._mem, False, jnp.float32)
    assert not engine_u._use_megakernel_apply(
        engine_u._mem, False, jnp.float32)

    flat_grads_w = _flat_grads(layout, params, seed)
    fn_u = _exchange_fn(engine_u, mesh8, send_frac=send_frac)
    fn_m = _exchange_fn(engine_m, mesh8, send_frac=send_frac)
    mem_u, mem_m = _mem0(engine_u), _mem0(engine_m)
    for step in range(steps):
        key = jax.random.PRNGKey(step)
        out_u, mem_u = fn_u(flat_grads_w, mem_u, key)
        out_m, mem_m = fn_m(flat_grads_w, mem_m, key)
        np.testing.assert_array_equal(np.asarray(out_m), np.asarray(out_u),
                                      err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(mem_m["sent_bits"]),
                                      np.asarray(mem_u["sent_bits"]),
                                      err_msg=f"bits step {step}")
        fu = {k: np.asarray(v) for k, v in engine_u.memory_full(
            jax.tree.map(lambda x: x[0], mem_u)).items()}
        fm = {k: np.asarray(v) for k, v in engine_m.memory_full(
            jax.tree.map(lambda x: x[0], mem_m)).items()}
        for mkey in ("momentums", "velocities"):
            np.testing.assert_array_equal(fm[mkey], fu[mkey],
                                          err_msg=f"{mkey} step {step}")
    return engine_m


def test_exchange_megakernel_matches_default(mesh8):
    """The acceptance pin: DGCCompressor(megakernel=True) over 3 real
    W=8 steps is BITWISE the default engine — exchanged gradient,
    packed transmit record, and folded-back error-feedback state."""
    engine_m = _run_parity(mesh8, 3, dict(megakernel=True))
    # the size DP packs conv1+conv2+dense into ONE multi-row bucket:
    # the megakernel grid covers R > 1 (and a structurally-ragged tail)
    assert any(engine_m.buckets[bi].rows > 1
               for bi in engine_m._mk_fwd_ids)


def test_exchange_megakernel_with_fused_flags(mesh8):
    """megakernel=True composes with (and takes precedence over) the
    standalone fused_select / fused_apply opt-ins: still bitwise the
    plain engine."""
    _run_parity(mesh8, 2, dict(megakernel=True, fused_select=True,
                               fused_apply=True), seed=41)


def test_exchange_megakernel_send_frac(mesh8):
    """Straggler-adaptive masking rides the megakernel selection: the
    post-selection keep mask sees the same (values, indices), so the
    degraded wire stays bitwise the unfused degraded wire."""
    engine_m = _run_parity(mesh8, 2, dict(megakernel=True),
                           send_frac=0.5, seed=43)
    assert engine_m._adaptive_rank is not None


def test_exchange_megakernel_multibucket(mesh8):
    """Two size buckets, each on the megakernel path: a ~328k tensor
    splits off its own bucket under the size DP (its padding would dwarf
    a bucket floor), the small tensors share a second — every bucket
    launches its own forward pass and the reassembled state stays
    bitwise the unfused engine's."""
    rng = np.random.RandomState(5)
    params = {"wide": {"kernel": jnp.asarray(rng.randn(256, 256),
                                             jnp.float32)}}
    for i in range(6):
        params[f"s{i}"] = {
            "kernel": jnp.asarray(rng.randn(16, 20), jnp.float32)}
    named, _ = named_flatten(params)

    def make(mk):
        comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9),
                             sample_ratio=1.0, megakernel=mk)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
        return dist.make_flat(params)

    layout, engine_m = make(True)
    _, engine_u = make(False)
    assert len(engine_m.buckets) >= 2
    assert len(engine_m._mk_fwd_ids) >= 2
    flat_grads_w = _flat_grads(layout, params, 47)
    fn_u = _exchange_fn(engine_u, mesh8)
    fn_m = _exchange_fn(engine_m, mesh8)
    mem_u, mem_m = _mem0(engine_u), _mem0(engine_m)
    for step in range(2):
        key = jax.random.PRNGKey(step)
        out_u, mem_u = fn_u(flat_grads_w, mem_u, key)
        out_m, mem_m = fn_m(flat_grads_w, mem_m, key)
        np.testing.assert_array_equal(np.asarray(out_m), np.asarray(out_u),
                                      err_msg=f"step {step}")
        np.testing.assert_array_equal(np.asarray(mem_m["sent_bits"]),
                                      np.asarray(mem_u["sent_bits"]),
                                      err_msg=f"bits step {step}")


def test_megakernel_bf16_state_keeps_unfused_path():
    """bf16 error-feedback state: the kernel refuses narrow state, so
    the plan-static gate must route every bucket to the unfused path
    even with megakernel=True."""
    params = _params()
    named, _ = named_flatten(params)
    comp = DGCCompressor(
        0.05, memory=DGCSGDMemory(momentum=0.9, dtype="bfloat16"),
        sample_ratio=1.0, megakernel=True)
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=W)
    _, engine = dist.make_flat(params)
    assert engine._megakernel
    assert engine._mk_fwd_ids == ()


def test_megakernel_env_opt_in(monkeypatch):
    """DGC_MEGAKERNEL=1 flips the engine gate without touching the
    compressor ctor — the bench A/B entry point."""
    monkeypatch.setenv("DGC_MEGAKERNEL", "1")
    params = _params()
    _, engine = _make_engine(params)
    assert engine._megakernel
    assert engine._mk_fwd_ids

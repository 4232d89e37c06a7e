"""``ParamLayout.mask_vector`` as geometry (``flat.LayoutMask``): the
weight-decay mask of the flat path is a few merged runs built in-register
from an iota, so the optimizer's fusion streams p, buf and g only.

* the compiled update holds nothing [T]-sized but p, buf and g (PERF.md
  §6, PR 25: as a [P] device array the mask folded into three [P]
  constants in ``dgc_sgd``'s update and one in ``sgd``'s);
* parameters and momentum buffer are bitwise those of the [P] vector;
* over ``LayoutMask.MAX_RUNS`` runs the vector form takes over;
* the recorder counts ``optimizer.wd_mask`` once per traced step.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dgc_tpu import (
    DGCCompressor,
    DGCSGDMemory,
    DistributedOptimizer,
    dgc_sgd,
    sgd,
)
from dgc_tpu.compression.flat import LayoutMask, ParamLayout
from dgc_tpu.utils.pytree import named_flatten

OPTIMIZERS = {"dgc_sgd": dgc_sgd, "sgd": sgd}
BOTH = pytest.mark.parametrize("nesterov", [True, False],
                               ids=["nesterov", "plain"])
EACH = pytest.mark.parametrize("opt_name", sorted(OPTIMIZERS))


def _not_bn(name):
    return "bn" not in name


def _params():
    """Three compressed tensors of different sizes (one bucket: row tails),
    a gap, and a tail of BatchNorm vectors on both sides of a bias."""
    rng = np.random.RandomState(0)

    def arr(*shape):
        return jnp.asarray(rng.randn(*shape), jnp.float32)

    return {
        "a_bn": {"scale": arr(8), "bias": arr(8)},
        "conv1": {"kernel": arr(3, 3, 4, 8)},
        "conv2": {"kernel": arr(3, 3, 8, 8)},
        "fc": {"kernel": arr(32, 10), "bias": arr(10)},
        "z_bn": {"scale": arr(8), "bias": arr(8)},
    }


def _layout(params=None):
    params = _params() if params is None else params
    named, _ = named_flatten(params)
    return params, ParamLayout(params,
                               [n for n, p in named.items() if p.ndim > 1])


def _many_runs_params(layers=LayoutMask.MAX_RUNS + 4):
    rng = np.random.RandomState(1)
    return {f"l{i:02d}": {"bn": jnp.asarray(rng.randn(8), jnp.float32),
                          "w": jnp.asarray(rng.randn(8), jnp.float32)}
            for i in range(layers)}


def _make(opt_name, nesterov, mask):
    return OPTIMIZERS[opt_name](0.1, momentum=0.9, weight_decay=1e-2,
                                nesterov=nesterov, weight_decay_mask=mask)


def _stepper(opt):
    @jax.jit
    def step(p, state, g):
        upd, state = opt.update(g, state, p)
        return jax.tree.map(jnp.add, p, upd), state
    return step


def _three_steps(opt, flat_p, flat_g):
    step, state, p = _stepper(opt), opt.init(flat_p), flat_p
    for i in range(3):
        p, state = step(p, state, flat_g * (1.0 + i))
    return np.asarray(p), np.asarray(state.momentum_buffer)


# --------------------------------------------------------------------- #
# the geometry                                                           #
# --------------------------------------------------------------------- #

def test_runs_merge_across_structural_zeros():
    params, layout = _layout()
    mask = layout.mask_vector(_not_bn)
    assert layout.t_compressed > layout.t_data > 0
    assert any(layout.sizes[n] < g.cols
               for g in layout.buckets for n in g.names)    # row tails
    o, s = layout.offsets["fc/bias"], layout.sizes["fc/bias"]
    # every compressed tensor, its row tail and the gap are ONE run from
    # the buffer's start; the bias between the BatchNorm vectors the other
    last = max(layout.offsets[n] + layout.sizes[n]
               for n in layout.compressed_names)
    assert mask.runs == ((0, last), (o, o + s)) and mask.form == "runs"
    exact = np.asarray(mask)
    assert exact.dtype == np.float32 and exact.shape == (layout.total,)
    named, _ = named_flatten(params)
    assert exact.sum() == sum(p.size for n, p in named.items() if _not_bn(n))
    # a run may say anything on a structural zero, nothing else
    built = np.asarray(mask(jnp.zeros((layout.total,))))
    assert built.dtype == bool
    real = np.zeros((layout.total,), bool)
    for n in layout.names:
        real[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    np.testing.assert_array_equal(built[real], exact[real] == 1)
    assert built[~real].any()


@pytest.mark.parametrize("pred, runs", [
    (lambda n: True, "whole"), (lambda n: False, ()),
    (lambda n: "bn" in n, "tail")])
def test_runs_reach_the_ends_of_the_buffer(pred, runs):
    _, layout = _layout()
    mask = layout.mask_vector(pred)
    if runs == "whole":
        runs = ((0, layout.total),)
    elif runs == "tail":
        o = layout.offsets["fc/bias"]
        runs = ((layout.offsets["a_bn/bias"], o),
                (layout.offsets["z_bn/bias"], layout.total))
    assert mask.runs == runs
    built = np.asarray(mask(jnp.zeros((layout.total,))))
    for n in layout.names:
        o, s = layout.offsets[n], layout.sizes[n]
        assert (built[o:o + s] == pred(n)).all(), n


def test_mask_refuses_anything_but_its_flat_buffer():
    params, layout = _layout()
    mask = layout.mask_vector(_not_bn)
    with pytest.raises(ValueError, match="flat"):
        mask(jnp.zeros((layout.total + 1,)))
    with pytest.raises(ValueError, match="flat"):
        mask(params)


# --------------------------------------------------------------------- #
# the compiled update                                                    #
# --------------------------------------------------------------------- #

_SHAPE = re.compile(r"\b(?:pred|[suf]\d+|bf16)\[([\d,]*)\]")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w.\-]+)\s*=\s*(\(.*?\)|\S+)\s+"
                    r"([\w\-]+)\(")


def _elements(shape_text):
    """Elements of the largest array a shape (or tuple of shapes) names."""
    return max([int(np.prod([int(d) for d in dims.split(",") if d],
                            dtype=np.int64))
                for dims in _SHAPE.findall(shape_text)] or [0])


def _big_values(hlo, floor):
    """What an optimized HLO module holds with ``floor`` or more elements:
    (constants, [(fusion, [(operand, its opcode)])] of the fusions that
    produce such a value)."""
    shapes, opcodes, fusions = {}, {}, []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        shapes[name], opcodes[name] = _elements(shape), opcode
        if opcode == "fusion":
            args = line[m.end():].split(")", 1)[0]
            fusions.append((name, re.findall(r"%[\w.\-]+", args)))
    constants = [n for n, op in opcodes.items()
                 if op == "constant" and shapes[n] >= floor]
    big = [(f, [(a, opcodes[a]) for a in args if shapes.get(a, 0) >= floor])
           for f, args in fusions if shapes[f] >= floor]
    return constants, big


def _compiled_update(opt, layout, sharding=None):
    flat = jax.ShapeDtypeStruct((layout.total,), jnp.float32,
                                sharding=sharding)
    state = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        jax.eval_shape(opt.init, flat))
    return _stepper(opt).lower(flat, state, flat).compile().as_text()


def _masks(layout):
    return {"runs": layout.mask_vector(_not_bn), "none": None}


@EACH
@BOTH
@pytest.mark.parametrize("mask", ["runs", "none"])
def test_update_holds_nothing_T_sized_but_what_it_is_given(opt_name,
                                                           nesterov, mask):
    """The CPU's compiler has no multi-output fusion and cuts the update
    into a few: whatever [T]-sized value one of them reads is an argument
    of the step or another's result, never a constant."""
    _, layout = _layout()
    opt = _make(opt_name, nesterov, _masks(layout)[mask])
    hlo = _compiled_update(opt, layout)
    constants, big = _big_values(hlo, layout.t_compressed)
    assert constants == [] and big
    assert {op for _, args in big for _, op in args} <= {"parameter",
                                                         "fusion"}, big
    # no mask, no iota: the scalar branch is the parent's program
    assert ("iota" in hlo) == (mask == "runs")


@EACH
def test_the_vector_form_is_what_the_reader_finds(opt_name):
    """The reader sees the [P] constants where they are: the same update
    with the mask as a device array, the form before PR 25 (three in
    ``dgc_sgd``'s update, one in ``sgd``'s)."""
    _, layout = _layout()
    vector = jnp.asarray(np.asarray(layout.mask_vector(_not_bn)))
    hlo = _compiled_update(_make(opt_name, True, vector), layout)
    constants, _ = _big_values(hlo, layout.t_compressed)
    assert len(constants) == {"dgc_sgd": 3, "sgd": 1}[opt_name], constants


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e host: the TPU's compiler without a TPU
    (guide: on-chip-measurement §2). Nothing runs on it."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one (the next would warn)."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


@EACH
@BOTH
@pytest.mark.parametrize("mask", ["runs", "none"])
def test_on_the_v5e_the_update_is_one_fusion_over_p_buf_g(
        one_chip, no_compile_cache, opt_name, nesterov, mask):
    """Compiled for the chip the update is ONE multi-output fusion whose
    [T]-sized operands are the three arguments, mask or no mask."""
    _, layout = _layout()
    opt = _make(opt_name, nesterov, _masks(layout)[mask])
    hlo = _compiled_update(opt, layout, sharding=one_chip)
    constants, big = _big_values(hlo, layout.t_compressed)
    assert constants == []
    (_, args), = big
    assert [op for _, op in args] == ["parameter"] * 3, big


#: the compressed block, the flat buffer and one worker's payload of the
#: two benchmark configurations (benchmark/configs/*.json; PERF.md §4)
_VGG_T, _VGG_P, _VGG_PAYLOAD = 139_028_480, 139_051_008, 138_360
_R50_T, _R50_P, _R50_PAYLOAD = 27_068_416, 27_123_712, 25_583
_GEOMETRY = {"vgg16_bn": (_VGG_T, _VGG_P, _VGG_PAYLOAD),
             "resnet50": (_R50_T, _R50_P, _R50_PAYLOAD)}


def _apply_pass_args(one_chip, W, T=_VGG_T, payload=_VGG_PAYLOAD):
    from dgc_tpu.ops import kernels

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    n = W * payload
    return (arg((n,), jnp.float32), arg((n,), jnp.int32), arg((n,), bool),
            arg((kernels.num_sent_words(T),), jnp.int32))


@pytest.mark.parametrize("model, opt_name, nesterov, masked, W", [
    ("vgg16_bn", "dgc_sgd", False, False, 1),  # the benchmark's one-chip cell
    ("vgg16_bn", "dgc_sgd", True, True, 4),
    ("vgg16_bn", "sgd", True, True, 4),
    ("resnet50", "dgc_sgd", True, True, 1),    # resnet50.steady since PR 41
    ("resnet50", "dgc_sgd", True, True, 4)])
def test_on_the_v5e_the_update_pass_compiles_at_the_cells_widths(
        one_chip, no_compile_cache, monkeypatch, model, opt_name, nesterov,
        masked, W):
    """``kernels.payload_update_bits`` with the optimizers' own rule
    through the TPU's compiler at VGG-16-BN's T and at ResNet-50's (the
    geometry rule sends both there when the step offers its rule), for
    one worker's pairs and four, under
    ``jax.default_matmul_precision("highest")`` (what a configuration
    with a model reference states; PERF.md §7.0): block shapes, scalar
    memory, VMEM and the rule's operations are all Mosaic's to refuse.
    The state moves in place: the program holds no [T]-sized
    temporary."""
    from dgc_tpu.compression.flat import FlatDGCEngine
    from dgc_tpu.ops import kernels
    from dgc_tpu.optim.sgd import SGDState
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    T, P, payload = _GEOMETRY[model]
    assert FlatDGCEngine._apply_streams(T, W * payload, offered=True)
    mask = None
    if masked:
        mask = LayoutMask(P, jnp.int32,
                          [(0, 1_000_000, True), (1_000_000, 1_000_512,
                                                  False),
                           (1_000_512, T, True), (T, P, False)])
        assert mask.form == "runs" and len(mask.runs) == 2
    rule = OPTIMIZERS[opt_name](
        lambda c: 0.1 / (1.0 + c), momentum=0.9, weight_decay=5e-5,
        nesterov=nesterov, weight_decay_mask=mask).rule

    def fused(v, i, f, bits, p, buf, count):
        state = SGDState(count, buf)
        return kernels.payload_update_bits(
            v, i, f, T, rule.blocks(state, p), rule.step,
            rule.scalars(state), bits_donor=bits, max_dup=W)

    flat = jax.ShapeDtypeStruct((P,), jnp.float32, sharding=one_chip)
    count = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(fused, donate_argnums=(3, 4, 5)).lower(
            *_apply_pass_args(one_chip, W, T, payload), flat, flat,
            count).compile()
    assert "payload_update_bits" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * T // 16


def test_on_the_v5e_the_apply_pass_compiles_under_highest(
        one_chip, no_compile_cache, monkeypatch):
    """PERF.md §7.0: ``kernels._dot_nt`` states its precision, so the
    streamed apply compiles whatever matmul precision the step states."""
    from dgc_tpu.ops import kernels
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)

    def apply(v, i, f, bits):
        return kernels.payload_apply_bits(v, i, f, _VGG_T, bits_donor=bits,
                                          out_total=_VGG_P, max_dup=1)

    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(apply, donate_argnums=(3,)).lower(
            *_apply_pass_args(one_chip, 1)).compile()
    assert "payload_apply_bits" in compiled.as_text()


# --------------------------------------------------------------------- #
# numerics                                                               #
# --------------------------------------------------------------------- #

@EACH
@BOTH
@pytest.mark.parametrize("jit", [False, True], ids=["op_by_op", "jit"])
def test_three_steps_equal_to_the_vector_mask(opt_name, nesterov, jit):
    """Same formula, same mask on every real coordinate: evaluated op by
    op the parameters and the momentum buffer are BITWISE those of the [P]
    vector (the form before PR 25). Under XLA:CPU's jit LLVM contracts
    ``a * b + c`` into one rounding wherever a fusion holds both, and the
    two forms fuse differently there: an ulp, not a bit."""
    params, layout = _layout()
    mask = layout.mask_vector(_not_bn)
    flat_p = layout.flatten(params)
    flat_g = layout.flatten(jax.tree.map(lambda p: jnp.cos(3.0 * p), params))
    with jax.disable_jit(not jit):
        got = _three_steps(_make(opt_name, nesterov, mask), flat_p, flat_g)
        want = _three_steps(
            _make(opt_name, nesterov, jnp.asarray(np.asarray(mask))),
            flat_p, flat_g)
    for a, b in zip(got, want):
        if jit:
            np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
        else:
            np.testing.assert_array_equal(a.view(np.uint32),
                                          b.view(np.uint32))
    # the structural zeros stay zero
    real = np.asarray(layout.flatten(jax.tree.map(jnp.ones_like, params)))
    assert (got[0][real == 0] == 0).all() and (got[1][real == 0] == 0).all()
    assert np.abs(got[1]).sum() > 0


@EACH
def test_over_the_run_bound_the_vector_takes_over(opt_name, rec):
    params = _many_runs_params()
    layout = ParamLayout(params, [])
    pred = lambda n: "bn" not in n
    mask = layout.mask_vector(pred)
    assert len(mask.runs) == LayoutMask.MAX_RUNS + 4
    assert mask.form == "vector"
    tree_mask = jax.tree_util.tree_map_with_path(
        lambda path, _: pred("/".join(str(getattr(k, "key", k))
                                      for k in path)), params)
    grads = jax.tree.map(lambda p: jnp.cos(3.0 * p), params)
    opt_tree = _make(opt_name, True, tree_mask)
    opt_flat = _make(opt_name, True, mask)
    p_flat, buf_flat = _three_steps(opt_flat, layout.flatten(params),
                                    layout.flatten(grads))
    step, state, p = _stepper(opt_tree), opt_tree.init(params), params
    for i in range(3):
        p, state = step(p, state, jax.tree.map(lambda g: g * (1.0 + i),
                                               grads))
    for flat, tree in ((p_flat, p), (buf_flat, state.momentum_buffer)):
        named = named_flatten(tree)[0]
        for n, piece in layout.unflatten_named(jnp.asarray(flat)).items():
            np.testing.assert_allclose(np.asarray(piece),
                                       np.asarray(named[n]),
                                       rtol=1e-6, atol=1e-7)
    counts = [r for r in rec.records() if r["name"] == "optimizer.wd_mask"]
    assert [c["args"] for c in counts] == [
        {"form": "vector", "runs": LayoutMask.MAX_RUNS + 4}]


def test_one_run_under_the_bound_still_builds_from_the_iota():
    layout = ParamLayout(_many_runs_params(LayoutMask.MAX_RUNS), [])
    mask = layout.mask_vector(lambda n: "bn" not in n)
    assert len(mask.runs) == LayoutMask.MAX_RUNS and mask.form == "runs"
    built = np.asarray(mask(jnp.zeros((layout.total,))))
    np.testing.assert_array_equal(built[:layout.p_data_end],
                                  np.asarray(mask)[:layout.p_data_end] == 1)


# --------------------------------------------------------------------- #
# the recorder                                                           #
# --------------------------------------------------------------------- #

def _masked_step(mesh):
    """The analysis suite's tiny flat step, its optimizer given the
    layout's BatchNorm mask as ``train.py`` and ``benchmark/build.py`` do."""
    from flax import linen as nn

    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return nn.Dense(10)(nn.relu(x).mean(axis=(1, 2)))

    model = M()
    v = dict(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3))))

    def apply_fn(variables, x, train=True, mutable=None, rngs=None):
        if mutable:
            return model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
        return model.apply(variables, x, train=train)

    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
    named, _ = named_flatten(v["params"])
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(v["params"], comp)
    mask = layout.mask_vector(lambda n: "BatchNorm" not in n)
    world = mesh.devices.size
    dist = DistributedOptimizer(
        dgc_sgd(0.1, momentum=0.9, weight_decay=1e-4, nesterov=True,
                weight_decay_mask=mask), comp, world_size=world)
    setup = make_flat_setup(v, dist)
    state = shard_state(make_flat_state(v, dist, setup, world), mesh,
                        dist_opt=dist)
    step = build_train_step(apply_fn, dist, mesh, flat=setup, donate=False)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(world * 4, 16, 16, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, world * 4), jnp.int32)
    return mask, state, step, (images, labels, jax.random.PRNGKey(1))


def test_recorder_counts_the_mask_once_per_traced_step(rec, mesh8):
    mask, state, step, inputs = _masked_step(mesh8)
    assert rec.records() == []          # building the mask traces nothing
    step.lower(state, *inputs)
    _, _, step2, _ = _masked_step(mesh8)
    step2.lower(state, *inputs)
    records = rec.records()
    traces = [r for r in records if r["name"] == "step.trace"]
    counts = [r for r in records if r["name"] == "optimizer.wd_mask"]
    assert len(traces) == 2
    assert [c["parent"] for c in counts] == [t["id"] for t in traces]
    assert all(c["kind"] == "count" and c["value"] == 1
               and c["args"] == {"form": "runs", "runs": len(mask.runs)}
               for c in counts)
    assert 1 <= len(mask.runs) <= 3

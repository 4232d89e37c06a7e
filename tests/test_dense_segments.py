"""The dense exchange reduces per layout segment (``FlatDenseExchange``,
``ParamLayout.pieces``): the segments tile the flat buffer, the segmented
exchange equals one ``psum`` bitwise on the 8-device CPU mesh, and the
traced dense step holds one ``psum`` per segment, none over ``[P]``."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dgc_tpu import (Compression, DGCCompressor, DGCSGDMemory,
                     DistributedOptimizer, dgc_sgd)
from dgc_tpu.analysis import jaxpr as jx
from dgc_tpu.compression.flat import FlatDenseExchange, ParamLayout
from dgc_tpu.parallel import make_two_tier_mesh
from dgc_tpu.training import (build_train_step, make_flat_setup,
                              make_flat_state, shard_state)
from dgc_tpu.utils.compat import shard_map
from dgc_tpu.utils.pytree import named_flatten

W = 8
#: slots below which a piece rides with others — the engine's own constant
#: is sized for real models and would leave every fixture in one segment
SMALL = 256


def _tree(**shapes):
    rng = np.random.RandomState(0)
    return {k: jnp.asarray(rng.randn(*s), jnp.float32)
            for k, s in shapes.items()}


def _layout(kind):
    if kind == "multi-bucket":
        tree = _tree(a=(64, 64), b=(48, 32), c=(8, 8), bias=(64,), bn=(8,))
        return ParamLayout(tree, ["a", "b", "c"])
    if kind == "single-bucket":
        return ParamLayout(_tree(a=(32, 32), bias=(32,)), ["a"])
    if kind == "no-compressed":
        return ParamLayout(_tree(a=(32, 32), b=(700,), c=(5,), d=(300,)))
    if kind == "gap-less":
        # the dense tail ends on the alignment: no padding piece
        lay = ParamLayout(_tree(a=(1024,), b=(1024,)))
        assert lay.total == lay.p_data_end
        return lay
    raise ValueError(kind)


LAYOUTS = ["multi-bucket", "single-bucket", "no-compressed", "gap-less"]


def _engine(layout, compressor=None):
    engine = FlatDenseExchange(compressor or Compression.none(), layout)
    engine.MIN_SEGMENT = SMALL
    return engine


# --------------------------------------------------------------------- #
# (a) geometry                                                           #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("kind", LAYOUTS)
def test_pieces_tile_the_buffer_once_in_storage_order(kind):
    lay = _layout(kind)
    pieces = lay.pieces()
    assert pieces[0][0] == 0 and pieces[-1][1] == lay.total
    assert all(a[1] == b[0] for a, b in zip(pieces, pieces[1:]))
    assert all(hi > lo for lo, hi, _ in pieces)
    # every tensor lies inside the one piece that names it
    seen = [n for _, _, names in pieces for n in names]
    assert sorted(seen) == sorted(lay.names)
    for lo, hi, names in pieces:
        for n in names:
            assert lo <= lay.offsets[n]
            assert lay.offsets[n] + lay.sizes[n] <= hi


@pytest.mark.parametrize("kind", LAYOUTS)
@pytest.mark.parametrize("order", ["storage", "backward"])
def test_segments_tile_the_buffer_once(kind, order):
    lay = _layout(kind)
    engine = _engine(lay)
    ready = None
    if order == "backward":
        # the reverse of the storage order, two tensors to a position
        ready = {n: (len(lay.names) - i) // 2
                 for i, n in enumerate(lay.names)}
    segments = engine.segments(ready)
    runs = sorted(r for seg in segments for r in seg)
    assert runs[0][0] == 0 and runs[-1][1] == lay.total
    assert all(a[1] == b[0] for a, b in zip(runs, runs[1:]))
    assert sorted(runs) == sorted((lo, hi) for lo, hi, _ in lay.pieces())
    # a piece of MIN_SEGMENT slots goes alone; no other segment but the
    # last may hold less
    for seg in segments:
        if any(hi - lo >= SMALL for lo, hi in seg):
            assert len(seg) == 1
    sizes = [sum(hi - lo for lo, hi in seg) for seg in segments]
    assert all(s >= SMALL for s in sizes[:-1])
    if ready is None:
        for starts in ([seg[0][0] for seg in segments
                        if seg[0][1] - seg[0][0] >= SMALL],
                       [lo for seg in segments for lo, hi in seg
                        if hi - lo < SMALL]):
            assert starts == sorted(starts)


def test_segments_follow_the_backward_pass():
    lay = ParamLayout(_tree(a=(300,), b=(2000,), c=(40,), d=(1000,),
                            e=(500,), f=(100,), g=(200,)))
    engine = _engine(lay)
    ready = {"g": 0, "e": 1, "d": 2, "c": 3, "f": 4, "b": 7, "a": 9}
    at, size = lay.offsets, lay.sizes
    run = lambda n: (at[n], at[n] + size[n])
    # g, c and f are small: they ride together and leave when they hold
    # MIN_SEGMENT slots, which f's 100 make up; the buffer's padding
    # waits for nothing and goes last
    pad = (lay.p_data_end, lay.total)
    assert engine.segments(ready) == [
        [run("e")], [run("d")], [run("g"), run("c"), run("f")],
        [run("b")], [run("a")], [pad]]


# --------------------------------------------------------------------- #
# (b) the same sums as one psum                                          #
# --------------------------------------------------------------------- #

def _run(engine, grads, mesh, axes, over, ready=None, **kwargs):
    """``engine.exchange`` of each worker's row of ``grads`` over the mesh;
    ``over`` is (axis the psum runs over, workers on it)."""
    def worker(fg, key):
        out, _ = engine.exchange(fg[0], {}, key, *over, grad_ready=ready,
                                 **kwargs)
        return out[None]

    f = jax.jit(shard_map(worker, mesh=mesh, in_specs=(P(axes), P()),
                          out_specs=P(axes), check_vma=False))
    return np.asarray(f(jnp.asarray(grads), jax.random.PRNGKey(0)))


@pytest.mark.parametrize("case", ["average", "sum", "fp16", "backward"])
def test_segmented_exchange_equals_one_psum(mesh8, case):
    lay = _layout("no-compressed")
    comp = Compression.fp16() if case == "fp16" else Compression.none()
    op = "sum" if case == "sum" else "average"
    ready = ({n: i for i, n in enumerate(reversed(lay.names))}
             if case == "backward" else None)
    rng = np.random.RandomState(1)
    grads = rng.randn(W, lay.total).astype(np.float32)
    cut = _engine(lay, comp)
    assert len(cut.segments(ready)) > 2
    whole = FlatDenseExchange(comp, lay)          # one segment: one psum
    assert len(whole.segments()) == 1
    got = _run(cut, grads, mesh8, "data", ("data", W), ready=ready, op=op)
    want = _run(whole, grads, mesh8, "data", ("data", W), op=op)
    assert got.tobytes() == want.tobytes()
    wire = grads.astype(np.float16) if case == "fp16" else grads
    ref = wire.astype(np.float32).sum(0) / (1 if op == "sum" else W)
    tol = 4e-3 if case == "fp16" else 1e-5     # the sum itself is fp16
    np.testing.assert_allclose(got[0], ref, rtol=tol, atol=tol)


def test_segmented_two_tier_exchange_equals_one_psum():
    mesh = make_two_tier_mesh(2, 4)
    lay = _layout("no-compressed")
    rng = np.random.RandomState(2)
    grads = rng.randn(W, lay.total).astype(np.float32)
    args = (grads, mesh, ("hosts", "local"), ("hosts", 2))
    tiers = dict(local_axis="local", local_size=4)
    got = _run(_engine(lay, Compression.fp16()), *args, **tiers)
    want = _run(FlatDenseExchange(Compression.fp16(), lay), *args, **tiers)
    assert got.tobytes() == want.tobytes()


def test_one_worker_keeps_the_single_psum():
    lay = _layout("no-compressed")
    engine = _engine(lay)
    closed = jax.make_jaxpr(shard_map(
        lambda fg, key: engine.exchange(fg[0], {}, key, "data", 1)[0][None],
        mesh=jax.sharding.Mesh(np.array(jax.devices()[:1]), ("data",)),
        in_specs=(P("data"), P()), out_specs=P("data"), check_vma=False))(
            jnp.zeros((1, lay.total)), jax.random.PRNGKey(0))
    prog = jx.flatten(closed)
    psums = [e for e in prog.eqns if e.prim == "psum"]
    assert len(psums) == 1
    assert [prog.avals[v].shape for v in psums[0].invars] == [(lay.total,)]
    assert not [e for e in prog.eqns if e.prim == "optimization_barrier"]


# --------------------------------------------------------------------- #
# (c) the traced dense step                                              #
# --------------------------------------------------------------------- #

class _Net(nn.Module):
    @nn.compact
    def __call__(self, x, train=True):
        x = nn.Conv(8, (3, 3), name="first")(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        x = nn.relu(x)
        x = nn.Conv(16, (3, 3), name="second")(x)
        x = nn.relu(x).mean(axis=(1, 2))
        return nn.Dense(10, name="last")(x)


def _dense_step(mesh, compressor="none", nbps=1, min_segment=128):
    model = _Net()
    v = dict(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 8, 3))))

    def apply_fn(variables, x, train=True, mutable=None, rngs=None):
        if mutable:
            return model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
        return model.apply(variables, x, train=train)

    if compressor == "none":
        comp = Compression.none()
    else:
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
        named, _ = named_flatten(v["params"])
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=W)
    setup = make_flat_setup(v, dist)
    if compressor == "none":
        setup.engine.MIN_SEGMENT = min_segment
    state = shard_state(make_flat_state(v, dist, setup, W), mesh)
    step = build_train_step(apply_fn, dist, mesh, donate=False, flat=setup,
                            num_batches_per_step=nbps)
    images = jnp.zeros((W * 2 * nbps, 8, 8, 3))
    labels = jnp.zeros((W * 2 * nbps,), jnp.int32)
    return setup, state, step, (images, labels, jax.random.PRNGKey(1))


def _psum_shapes(step, state, inputs, total):
    prog = jx.flatten(jax.make_jaxpr(step)(state, *inputs))
    out = []
    for e in prog.eqns:
        if e.prim == "psum":
            shapes = [prog.avals[v].shape for v in e.invars]
            if any(len(s) == 1 and s[0] > 1 for s in shapes):
                out.append(shapes)
    assert all(s != (total,) for shapes in out for s in shapes)
    return out


def test_traced_dense_step_holds_one_psum_per_segment(mesh8, rec):
    setup, state, step, inputs = _dense_step(mesh8)
    lay = setup.layout
    psums = _psum_shapes(step, state, inputs, lay.total)
    counts = [r for r in rec.records()
              if r["name"] == "exchange.collective"]
    assert len(psums) == len(counts) > 2
    assert [c["args"]["segment"] for c in counts] == list(range(len(counts)))
    assert sum(c["value"] for c in counts) == lay.total * 4
    # issued as the backward pass makes them final: the last layer's
    # kernel before the first layer's
    issued = [s[0] for shapes in psums for s in shapes]
    assert (issued.index(lay.sizes["last/kernel"])
            < issued.index(lay.sizes["first/kernel"]))


def test_micro_batched_dense_step_cuts_in_storage_order(mesh8):
    """Gradients of a micro-batch loop are final together, at its end: the
    step hands the engine no order, and the segments keep storage order."""
    setup, state, step, inputs = _dense_step(mesh8, nbps=2)
    lay = setup.layout
    psums = _psum_shapes(step, state, inputs, lay.total)
    want = [sum(hi - lo for lo, hi in seg)
            for seg in setup.engine.segments()]
    assert [shapes[0][0] for shapes in psums] == want


def test_dgc_step_is_not_handed_an_order(mesh8):
    """The DGC engine does not take ``grad_ready``: its step is traced as
    before (``grad_fn`` unwrapped), and its exchange is called without."""
    setup, state, step, inputs = _dense_step(mesh8, compressor="dgc")
    assert not getattr(setup.engine, "takes_grad_ready", False)
    step.lower(state, *inputs)        # would raise on an unknown keyword


def test_step_losses_equal_the_single_psum_steps(mesh8):
    """Three steps of the segmented dense step and of the same step with
    the engine left at one segment: bitwise-equal losses and parameters."""
    outs = []
    for min_segment in (128, 1 << 30):
        setup, state, step, _ = _dense_step(mesh8, min_segment=min_segment)
        assert (len(setup.engine.segments()) == 1) == (min_segment > 128)
        rng = np.random.RandomState(3)
        images = jnp.asarray(rng.randn(W * 2, 8, 8, 3), jnp.float32)
        labels = jnp.asarray(rng.randint(0, 10, (W * 2,)), jnp.int32)
        losses = []
        for i in range(3):
            state, m = step(state, images, labels, jax.random.PRNGKey(i))
            losses.append(np.asarray(m["loss"]))
        outs.append((np.stack(losses), np.asarray(state.params)))
    assert outs[0][0].tobytes() == outs[1][0].tobytes()
    assert outs[0][1].tobytes() == outs[1][1].tobytes()


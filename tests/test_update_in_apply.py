"""The optimizer's rule inside the apply pass (``FlatDGCEngine._apply``
form ``update``; ``kernels.payload_update_bits``; ``DistributedOptimizer.
step_flat``), at the level of the flat DGC step on the fake CPU mesh.

The chip's route is made here by hand: ``kernels.use_pallas`` says yes
(the kernels still run interpreted) and the geometry rule
``_apply_streams`` is forced true on a layout of a few thousand
coordinates. Both arms of a comparison run under the same two patches;
what differs is whether the optimizer offers its rule.

* three steps with the rule offered are bitwise three steps of today's
  path (streamed apply, then the XLA optimizer): parameters, momentum
  buffer, DGC momentum, velocity, transmit bits;
* a step whose optimizer offers no rule, a step whose geometry keeps
  the scatter, a guarded step and a step that keeps its state (no
  donation) lower to the HLO they lowered to before the offer existed;
* count ``exchange.apply`` says ``path=update`` exactly where the rule
  engaged.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dgc_tpu import (
    DGCCompressor,
    DGCSGDMemory,
    DistributedOptimizer,
    dgc_sgd,
    sgd,
)
from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
from dgc_tpu.ops import kernels
from dgc_tpu.utils.pytree import named_flatten

OPTIMIZERS = {"dgc_sgd": dgc_sgd, "sgd": sgd}
#: the rule's constants as powers of two (and the schedule above them):
#: every product by one is exact, so the comparisons below do not depend
#: on which multiply XLA:CPU's LLVM contracts into an FMA. It may choose
#: differently in two programs that hold the same operations (``m * buf
#: + wd * p`` came out as fma(wd, p, m * buf) in the interpreted kernel
#: and as fma(m, buf, wd * p) in the optimizer's fusion: a last-bit
#: difference in a quarter of the buffer). The TPU's vector unit has no
#: f32 FMA to choose: on the chip the two forms were bitwise at VGG's T
#: with the benchmark's constants (PERF.md §6, PR 35, step 0).
EXACT = dict(momentum=0.5, weight_decay=2.0 ** -7)


def _unruled(opt):
    """The same transformation, offering nothing."""
    return optax.GradientTransformation(opt.init, opt.update)


@pytest.fixture
def chip_route(monkeypatch):
    """The engine takes the chip's forms; its kernels stay interpreted."""
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    monkeypatch.setattr(FlatDGCEngine, "_apply_streams",
                        classmethod(lambda cls, T, pairs: True))


def _step(mesh, opt_name="dgc_sgd", offer=True, masked=True, nesterov=True,
          guards=None, donate=True, lr=None):
    """A tiny flat DGC step (a conv, a BatchNorm, a dense layer: a
    compressed block, a gap, a dense tail) and its first state."""
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

    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=1.0)
    named, _ = named_flatten(v["params"])
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(v["params"], comp)
    mask = (layout.mask_vector(lambda n: "BatchNorm" not in n)
            if masked else None)
    if lr is None:
        def lr(count):                        # a schedule: lr_t is traced
            return 0.125 * 0.5 ** count.astype(jnp.float32)
    opt = OPTIMIZERS[opt_name](lr, nesterov=nesterov,
                               weight_decay_mask=mask, **EXACT)
    assert opt.rule.weight_decay_mask is mask
    world = mesh.devices.size
    dist = DistributedOptimizer(opt if offer else _unruled(opt), comp,
                                world_size=world)
    setup = make_flat_setup(v, dist)
    kw = {}
    if guards is not None:
        kw["guards"] = guards
    state = shard_state(make_flat_state(v, dist, setup, world, **kw), mesh,
                        dist_opt=dist)
    step = build_train_step(apply_fn, dist, mesh, flat=setup, donate=donate,
                            **kw)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(world * 4, 16, 16, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 10, world * 4), jnp.int32)
    return setup, state, step, (images, labels)


def _run(step, state, batch, steps=3):
    out = []
    for i in range(steps):
        state, metrics = step(state, *batch, jax.random.PRNGKey(10 + i))
        # the next step takes the state's buffers: keep copies
        out.append((jax.tree.map(np.asarray, state), float(metrics["loss"])))
    return out


@pytest.mark.parametrize("opt_name, masked, nesterov", [
    ("dgc_sgd", True, True),
    ("dgc_sgd", False, False),
    ("sgd", True, False),
])
def test_three_steps_equal_todays_path_bitwise(chip_route, mesh8, opt_name,
                                               masked, nesterov):
    """Same work, same numbers: every array of the state after each of
    three steps (the first has ``first`` true, the schedule moves
    ``lr_t``), and the loss."""
    runs = {}
    for offer in (True, False):
        setup, state, step, batch = _step(mesh8, opt_name, offer, masked,
                                          nesterov)
        runs[offer] = _run(step, state, batch)
    assert setup.layout.total > setup.engine.T > 0    # a tail to keep
    for (got, loss_g), (want, loss_w) in zip(runs[True], runs[False]):
        assert loss_g == loss_w
        flat_g, tree = jax.tree.flatten(got)
        flat_w, tree_w = jax.tree.flatten(want)
        assert tree == tree_w
        for a, b in zip(flat_g, flat_w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the steps did something: parameters and the record moved
    first, last = runs[True][0][0], runs[True][-1][0]
    assert np.asarray(first.memory["sent_bits"]).any()
    assert not np.array_equal(np.asarray(first.params),
                              np.asarray(last.params))


def _lowered(step, state, batch):
    return step.lower(state, *batch, jax.random.PRNGKey(1)).as_text()


def test_offer_not_taken_lowers_to_the_program_without_it(mesh8):
    """Off the chip's route (here: the CPU's scatter; on the chip:
    ResNet-50's geometry) the offer changes nothing: the lowered step is
    the one an optimizer without a rule lowers to."""
    texts = {}
    for offer in (True, False):
        _, state, step, batch = _step(mesh8, offer=offer)
        texts[offer] = _lowered(step, state, batch)
    assert texts[True] == texts[False]


def test_resnet50_geometry_keeps_the_scatter(monkeypatch, mesh8):
    """With the chip's kernels on and the REAL geometry rule, a layout
    under 128 MiB keeps the scatter form and the XLA optimizer, offer or
    no offer; ResNet-50's T is such a layout."""
    assert not FlatDGCEngine._apply_streams(27_068_416, 4 * 25_583)
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    texts = {}
    for offer in (True, False):
        _, state, step, batch = _step(mesh8, offer=offer)
        texts[offer] = _lowered(step, state, batch)
    assert texts[True] == texts[False]


def test_a_step_that_keeps_its_buffers_makes_no_offer(chip_route, mesh8):
    """The guards' atomic skip selects between the old parameters and
    the new, and a step built without donation leaves its caller the
    state it came with: an update in place would cost either a copy of
    each buffer. On the chip's route both lower to what they lower to
    with an optimizer that offers nothing, where the plain step does
    not."""
    from dgc_tpu.resilience.guard import GuardConfig
    for kw, same in ((dict(guards=GuardConfig()), True),
                     (dict(donate=False), True), ({}, False)):
        texts = {}
        for offer in (True, False):
            _, state, step, batch = _step(mesh8, offer=offer, **kw)
            texts[offer] = _lowered(step, state, batch)
        assert (texts[True] == texts[False]) is same, kw


@pytest.mark.parametrize("offer, streams, path", [
    (True, True, "update"),
    (False, True, "stream"),
    (True, False, "scatter"),
])
def test_apply_count_names_the_form(rec, monkeypatch, mesh8, offer, streams,
                                    path):
    """Count ``exchange.apply`` reports ``path=update`` exactly where
    the rule engaged: once a trace, its value the gathered pairs."""
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(kernels, "_interpret", lambda: True)
    monkeypatch.setattr(FlatDGCEngine, "_apply_streams",
                        classmethod(lambda cls, T, pairs: streams))
    setup, state, step, batch = _step(mesh8, offer=offer)
    step.lower(state, *batch, jax.random.PRNGKey(1))
    count, = [r for r in rec.records() if r["name"] == "exchange.apply"]
    assert count["kind"] == "count"
    assert count["value"] == 8 * setup.engine.payload_size
    assert count["args"] == {"path": path}

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
* with the REAL rule and a layout that lies where ResNet-50's T lies
  between the rule's two bounds, the offer decides: without it the
  scatter's program, with it one ``payload_update_bits`` and no
  [T]-shaped scatter, bitwise the scatter's numbers over three steps
  of a 7-bucket layout;
* count ``exchange.apply`` says ``path=update`` exactly where the rule
  engaged.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from flax import linen as nn

from dgc_tpu import (
    DGCCompressor,
    DGCSGDMemory,
    DistributedOptimizer,
    dgc_sgd,
    sgd,
)
from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
from dgc_tpu.ops import kernels
from dgc_tpu.resilience.guard import GuardConfig
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
def kernels_on(monkeypatch):
    """The chip's kernels, interpreted; the geometry rule as it is."""
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(kernels, "_interpret", lambda: True)


@pytest.fixture
def chip_route(kernels_on, monkeypatch):
    """The engine takes the chip's forms; its kernels stay interpreted."""
    monkeypatch.setattr(FlatDGCEngine, "_apply_streams", classmethod(
        lambda cls, T, pairs, offered=False: True))


#: ResNet-50's compressed block and one worker's payload (PERF.md §4)
_R50_T, _R50_PAYLOAD = 27_068_416, 25_583


def _at_resnet50s_place(monkeypatch, T):
    """The REAL rule on a test layout: both of its bounds scaled by
    ``T`` over ResNet-50's T, so the layout lies where ResNet-50 lies,
    over the offered case's bound and under the other's. Patch before
    the step is traced."""
    for bound in ("APPLY_UPDATE_MIN_BYTES", "APPLY_STREAM_MIN_BYTES"):
        monkeypatch.setattr(FlatDGCEngine, bound,
                            getattr(FlatDGCEngine, bound) * T // _R50_T)
    assert FlatDGCEngine._apply_streams(T, 1, offered=True)
    assert not FlatDGCEngine._apply_streams(T, 1, offered=False)


class _Small(nn.Module):
    """A conv, a BatchNorm, a dense layer: a compressed block, a gap, a
    dense tail."""

    @nn.compact
    def __call__(self, x, train=True):
        x = nn.Conv(8, (3, 3))(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        return nn.Dense(10)(nn.relu(x).mean(axis=(1, 2)))


class _SevenSizes(nn.Module):
    """Seven kernels of seven sizes over one input: with
    ``ParamLayout.FLOOR_SLOTS`` at 0 a bucket each, 290,816 coordinates
    (two apply chunks). At ratio 0.01 six of them select over 128 a row
    (the approximate top-k's route, as six of ResNet-50's seven buckets)
    and the smallest 41 (the exact one's)."""
    widths = (1536, 1024, 768, 512, 384, 256, 64)

    @nn.compact
    def __call__(self, x, train=True):
        x = nn.BatchNorm(use_running_average=not train)(
            x.reshape(x.shape[0], -1)[:, :64])
        return sum(nn.relu(nn.Dense(w)(x)).reshape(x.shape[0], -1, 8).mean(1)
                   for w in self.widths)


def _step(mesh, opt_name="dgc_sgd", offer=True, masked=True, nesterov=True,
          guards=None, donate=True, lr=None, model=None, ratio=0.05,
          decayed=lambda n: "BatchNorm" not in n):
    """A tiny flat DGC step (``model``: :class:`_Small`) and its first
    state."""
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)

    model = model or _Small()
    v = dict(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3))))

    def apply_fn(variables, x, train=True, mutable=None, rngs=None):
        if mutable:
            return model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
        return model.apply(variables, x, train=train)

    comp = DGCCompressor(ratio, memory=DGCSGDMemory(momentum=0.9),
                         sample_ratio=1.0)
    named, _ = named_flatten(v["params"])
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(v["params"], comp)
    mask = layout.mask_vector(decayed) if masked else None
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
    labels = jnp.asarray(rng.randint(0, 8, world * 4), jnp.int32)
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


def _jaxpr(step, state, batch):
    return str(step.trace(state, *batch, jax.random.PRNGKey(1)).jaxpr)


def _t_shaped_scatter(text, T):
    return re.search(rf"f32\[{T}\] = scatter", text)


@pytest.mark.parametrize("kw", [
    dict(offer=False), dict(guards=GuardConfig()), dict(donate=False)],
    ids=["no_rule", "guards", "no_donation"])
def test_resnet50_geometry_without_an_offer_keeps_the_scatter(
        kernels_on, monkeypatch, mesh8, kw):
    """With the chip's kernels on and the REAL geometry rule, a layout
    at ResNet-50's place that makes no offer (an optimizer without a
    rule, a guarded step, a step that keeps its state) lowers to the
    text it lowers to under both bounds (the parent's program): PR 31's
    reading stands where a [T] gradient is written."""
    assert not FlatDGCEngine._apply_streams(_R50_T, 4 * _R50_PAYLOAD)
    setup, state, step, batch = _step(mesh8, **kw)
    want = _lowered(step, state, batch)
    assert _t_shaped_scatter(_jaxpr(step, state, batch), setup.engine.T)
    _at_resnet50s_place(monkeypatch, setup.engine.T)
    _, state, step, batch = _step(mesh8, **kw)
    assert _lowered(step, state, batch) == want


def test_resnet50_geometry_with_the_offer_updates_in_the_pass(
        rec, kernels_on, monkeypatch, mesh8):
    """The same layout WITH the offer: the count says ``path=update``,
    and the step holds ``payload_update_bits``, no other apply kernel
    and no [T]-shaped scatter (neither the values' nor, word-shaped,
    the bits')."""
    assert FlatDGCEngine._apply_streams(_R50_T, 4 * _R50_PAYLOAD,
                                        offered=True)
    setup, state, step, batch = _step(mesh8)
    _at_resnet50s_place(monkeypatch, setup.engine.T)
    text = _jaxpr(step, state, batch)
    count, = [r for r in rec.records() if r["name"] == "exchange.apply"]
    assert count["args"] == {"path": "update"}
    assert count["value"] == 8 * setup.engine.payload_size
    assert "name=payload_update_bits" in text
    assert "name=payload_apply_bits" not in text
    assert not _t_shaped_scatter(text, setup.engine.T)
    words = kernels.num_sent_words(setup.engine.T)
    assert not re.search(rf"i32\[{words}\] = scatter", text)


def test_seven_buckets_three_steps_equal_the_scatter_bitwise(
        kernels_on, monkeypatch, mesh8):
    """ResNet-50's bucket mix, small: seven buckets, six on the
    approximate top-k's route and one on the exact one's, eight workers
    whose payloads share coordinates, nesterov, a weight-decay mask that
    cuts the compressed block inside a chunk. Three steps of the pass
    that takes the rule (the real geometry rule at ResNet-50's place)
    are bitwise three steps of the scatter, the bit scatter and the
    XLA optimizer (the rule under both bounds)."""
    monkeypatch.setattr(ParamLayout, "FLOOR_SLOTS", 0)
    kw = dict(model=_SevenSizes(), ratio=0.01,
              decayed=lambda n: "BatchNorm" not in n and "Dense_3" not in n)
    setup, state, step, batch = _step(mesh8, **kw)
    engine = setup.engine
    assert [b.cols for b in engine.buckets] == [
        64 * w for w in _SevenSizes.widths]
    assert [b.max_sel > 128 for b in engine.buckets] == [True] * 6 + [False]
    assert engine.T > kernels._APPLY_CHUNK          # a chunk boundary
    runs = setup.layout.mask_vector(kw["decayed"]).runs
    assert any(0 < edge < engine.T for run in runs for edge in run), runs
    assert _t_shaped_scatter(_jaxpr(step, state, batch), engine.T)
    want = _run(step, state, batch)
    _at_resnet50s_place(monkeypatch, engine.T)
    _, state, step, batch = _step(mesh8, **kw)
    assert not _t_shaped_scatter(_jaxpr(step, state, batch), engine.T)
    got = _run(step, state, batch)
    for (a, loss_a), (b, loss_b) in zip(got, want):
        assert loss_a == loss_b
        for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    assert np.asarray(got[0][0].memory["sent_bits"]).any()


def test_a_step_that_keeps_its_buffers_makes_no_offer(chip_route, mesh8):
    """The guards' atomic skip selects between the old parameters and
    the new, and a step built without donation leaves its caller the
    state it came with: an update in place would cost either a copy of
    each buffer. On the chip's route both lower to what they lower to
    with an optimizer that offers nothing, where the plain step does
    not."""
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
def test_apply_count_names_the_form(rec, kernels_on, monkeypatch, mesh8,
                                    offer, streams, path):
    """Count ``exchange.apply`` reports ``path=update`` exactly where
    the rule engaged: once a trace, its value the gathered pairs."""
    monkeypatch.setattr(FlatDGCEngine, "_apply_streams", classmethod(
        lambda cls, T, pairs, offered=False: streams))
    setup, state, step, batch = _step(mesh8, offer=offer)
    step.lower(state, *batch, jax.random.PRNGKey(1))
    count, = [r for r in rec.records() if r["name"] == "exchange.apply"]
    assert count["kind"] == "count"
    assert count["value"] == 8 * setup.engine.payload_size
    assert count["args"] == {"path": path}

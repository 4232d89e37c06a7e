"""The gradient pack's placement pass (``kernels.place_rows``,
``ParamLayout.flatten`` / ``placed_names`` / ``pack_bytes``, the step's
``step.pack`` count), interpreted, on small shapes with
``flat.PLACE_MIN_BYTES`` lowered by monkeypatch.

* the placed form is bitwise the concatenation over layouts that hold
  every case: two eligible tensors, a tensor with a row tail, a trailing
  dimension off the lane, rows off the sublane, a dense tail with an
  eligible tensor in it, the gap with the sentinel, the buffer's tail;
* eligibility's truth table, the kernel's and the layout's;
* a layout with no eligible tensor (every dense layout, ResNet-50's at
  the constant as shipped) traces the plain concatenation: the jaxpr of
  the parent's ``flatten``, and lowered for the chip no custom call;
* a dgc step of three dispatches ends in bitwise the concatenate form's
  state;
* count ``step.pack`` gives both paths' bytes.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import test_update_in_apply as uia
from dgc_tpu.compression import flat as flat_mod
from dgc_tpu.compression.flat import ParamLayout
from dgc_tpu.ops import kernels
from dgc_tpu.utils.pytree import named_flatten

NEVER = 1 << 60


def _concat_flatten(layout, tree):
    """``ParamLayout.flatten`` as it stood before the pass: one
    concatenation of everything."""
    named, _ = named_flatten(tree)
    parts = []
    for g in layout.buckets:
        for n in g.names:
            parts.append(jnp.ravel(named[n]))
            if g.cols > layout.sizes[n]:
                parts.append(jnp.zeros((g.cols - layout.sizes[n],),
                                       layout.dtype))
    if layout.t_compressed > layout.t_data:
        parts.append(jnp.zeros((layout.t_compressed - layout.t_data,),
                               layout.dtype))
    parts += [jnp.ravel(named[n]) for n in layout.dense_names]
    if layout.total > layout.p_data_end:
        parts.append(jnp.zeros((layout.total - layout.p_data_end,),
                               layout.dtype))
    return jnp.concatenate(parts)


def _tree(shapes, seed=0):
    rng = np.random.RandomState(seed)
    return {name: {"kernel": jnp.asarray(rng.randn(*shape), jnp.float32)}
            for name, shape in shapes.items()}


#: name -> (shapes, compressed names, FLOOR_SLOTS, the tensors placed at
#: a constant of 32 KiB). ``buckets``: a bucket a size, so every large
#: tensor is a row without a tail; ``tails``: one bucket, every row but
#: the first has a tail; ``dense_tail``: an eligible tensor behind the
#: gap; ``none``: nothing of the size.
LAYOUTS = {
    "buckets": (
        dict(a=(64, 256), b=(3, 3, 16, 128), c=(40, 100), d=(64, 130),
             e=(77,), f=(50, 100), g=(36, 256)),
        ("a", "b", "c", "d", "f", "g"), 0, ("b/kernel", "a/kernel")),
    "tails": (
        dict(a=(64, 256), b=(3, 3, 16, 128), c=(40, 100), e=(77,)),
        ("a", "b", "c"), 300_000, ("b/kernel",)),
    "dense_tail": (
        dict(a=(64, 256), e=(77,), z=(32, 384)),
        ("a",), 300_000, ("a/kernel",)),
    "none": (
        dict(c=(40, 100), d=(64, 130), e=(77,)),
        ("c", "d"), 300_000, ()),
}


def _layout(monkeypatch, name):
    shapes, compressed, floor, placed = LAYOUTS[name]
    monkeypatch.setattr(ParamLayout, "FLOOR_SLOTS", floor)
    tree = _tree(shapes)
    layout = ParamLayout(tree, [f"{n}/kernel" for n in compressed])
    return layout, tree, placed


@pytest.mark.parametrize("name", sorted(LAYOUTS))
def test_placed_flatten_is_the_concatenation_bitwise(monkeypatch, name):
    layout, tree, placed = _layout(monkeypatch, name)
    want = np.asarray(_concat_flatten(layout, tree))
    monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", 32 << 10)
    assert layout.placed_names() == placed
    flatten = functools.partial(layout.flatten, place=True)
    # a caller that does not say ``place`` concatenates
    assert (str(jax.make_jaxpr(layout.flatten)(tree))
            == str(jax.make_jaxpr(lambda t: _concat_flatten(layout, t))(tree)))
    for run in (flatten, jax.jit(flatten)):
        got = np.asarray(run(tree))
        assert got.shape == (layout.total,)
        np.testing.assert_array_equal(got, want)     # NaN would differ
    # every structural zero is an exact zero: row tails, the gap with
    # the sentinel, the buffer's tail
    real = np.zeros(layout.total, bool)
    for n in layout.names:
        real[layout.offsets[n]:layout.offsets[n] + layout.sizes[n]] = True
    assert not got[~real].any() and got[layout.sentinel] == 0
    assert (~real).sum() == layout.total - layout.num_params > 0
    # and back
    back = layout.unflatten(jnp.asarray(got))
    for n, a in named_flatten(tree)[0].items():
        np.testing.assert_array_equal(np.asarray(named_flatten(back)[0][n]),
                                      np.asarray(a))


def test_dense_tail_tensor_is_placed_where_its_slot_is_aligned(monkeypatch):
    """Geometry alone: a tensor of the dense tail whose slot starts on a
    tile is placed too, and one tile further it is not."""
    monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", 32 << 10)
    for lead, placed in ((2048, True), (2048 + 128, False)):
        tree = {"a": {"bias": jnp.ones((lead,))},
                "b": {"kernel": jnp.full((32, 384), 2.0)}}
        layout = ParamLayout(tree, [])
        assert layout.offsets["b/kernel"] == lead
        assert (layout.placed_names() == ("b/kernel",)) is placed
        np.testing.assert_array_equal(
            np.asarray(layout.flatten(tree, place=True)),
            np.asarray(_concat_flatten(layout, tree)))


@pytest.mark.parametrize("total, base, rows, cols, ok", [
    (1 << 20, 0, 8, 128, True),
    (1 << 20, 1024, 64, 4096, True),
    (1 << 20, 1024 * 3, 24, 384, True),          # rows, cols no power of 2
    (1 << 20, 512, 8, 128, False),               # base off the tile
    (1 << 20, 128, 8, 128, False),
    (1 << 20, 0, 4, 256, False),                 # rows off the sublane
    (1 << 20, 0, 12, 128, False),
    (1 << 20, 0, 8, 100, False),                 # cols off the lane
    (1 << 20, 0, 8, 192, False),
    ((1 << 20) + 128, 0, 8, 128, False),         # total off the tile
    (1 << 20, (1 << 20) - 1024, 16, 128, False),  # past the end
    (1 << 20, (1 << 20) - 1024, 8, 128, True),   # the last tile
    (1 << 20, 0, 0, 128, False),
    (1 << 24, 0, 8, 65536, True),                # eight rows fill a block
    (1 << 24, 0, 8, 65536 + 128, False),         # ... and no more
])
def test_place_rows_eligibility(total, base, rows, cols, ok):
    assert kernels.place_rows_eligible(total, base, rows, cols) is ok


@pytest.mark.parametrize("rows, cols, base, into", [
    (8, 128, 0, False), (64, 512, 2048, False), (24, 384, 1024, True),
    (16, 4096, 3072, True)])
def test_place_rows_is_a_copy_into_the_slot(rows, cols, base, into):
    """The kernel alone: the slot holds the tensor row-major; with
    ``into`` everything else is as it came."""
    total = base + rows * cols + 4096
    x = jnp.asarray(np.random.RandomState(rows).randn(rows, cols),
                    jnp.float32)
    before = jnp.arange(total, dtype=jnp.float32)
    got = np.asarray(jax.jit(
        lambda x, b: kernels.place_rows(x, base, total, b if into else None)
    )(x, before))
    np.testing.assert_array_equal(got[base:base + rows * cols],
                                  np.asarray(x).reshape(-1))
    if into:
        want = np.asarray(before)
        outside = np.ones(total, bool)
        outside[base:base + rows * cols] = False
        np.testing.assert_array_equal(got[outside], want[outside])


@pytest.mark.parametrize("case", [
    "size_under_the_constant", "size_at_the_constant", "row_tail",
    "one_dimension", "bfloat16", "slot_off_the_tile"])
def test_layout_eligibility(monkeypatch, case):
    """``placed_names``' own conditions over the kernel's: the constant,
    no row tail, a 2-D view, four-byte elements."""
    monkeypatch.setattr(ParamLayout, "FLOOR_SLOTS", 0)
    monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", 4 * 16384)
    dtype, shapes, placed = jnp.float32, dict(a=(64, 256)), ("a/kernel",)
    if case == "size_under_the_constant":
        shapes, placed = dict(a=(56, 256)), ()
    elif case == "row_tail":
        # one bucket: the smaller tensor's row is the larger's width
        monkeypatch.setattr(ParamLayout, "FLOOR_SLOTS", 300_000)
        shapes, placed = dict(a=(64, 256), b=(72, 256)), ("b/kernel",)
    elif case == "one_dimension":
        shapes, placed = dict(a=(16384,)), ()
    elif case == "bfloat16":
        dtype, placed = jnp.bfloat16, ()
    elif case == "slot_off_the_tile":
        # the larger tensor's bucket ends at 16640 = 16.25 tiles
        shapes, placed = dict(a=(128, 130), b=(64, 256)), ()
    tree = {n: {"kernel": jnp.ones(s, dtype)} for n, s in shapes.items()}
    layout = ParamLayout(tree, [f"{n}/kernel" for n in shapes])
    assert layout.placed_names() == placed
    nbytes = layout.pack_bytes()
    assert nbytes["place"] == 4 * sum(layout.sizes[n] for n in placed)
    assert sum(nbytes.values()) == layout.total * layout.dtype.itemsize


def _lowered_for_tpu(fn, *args):
    return jax.jit(fn).trace(*args).lower(
        lowering_platforms=("tpu",)).as_text()


def _resnet50_layout():
    from dgc_tpu import DGCCompressor, DGCSGDMemory
    from dgc_tpu.models import resnet50
    model = resnet50()
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 224, 224, 3)), train=True))
    named, _ = named_flatten(shapes["params"])
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    return shapes["params"], comp


@pytest.mark.parametrize("which", ["resnet50_dgc", "resnet50_dense",
                                   "small_dense"])
def test_a_layout_without_an_eligible_tensor_traces_the_concatenation(
        monkeypatch, which):
    """At the constant as shipped ResNet-50's DGC layout has no eligible
    tensor, and a dense layout (``ParamLayout(tree, [])``: tree order,
    nothing aligned) has none either: ``flatten`` traces the parent's
    jaxpr, and lowered for the chip it holds no custom call."""
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    if which == "small_dense":
        # tree order behind a 77-element bias: every slot off the tile
        shapes = {"_": {"bias": jnp.ones((77,))},
                  **_tree(LAYOUTS["buckets"][0])}
        layout = ParamLayout(shapes, [])
        monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", 32 << 10)
    else:
        shapes, comp = _resnet50_layout()
        layout = (ParamLayout.for_compressor(shapes, comp)
                  if which == "resnet50_dgc" else ParamLayout(shapes, []))
    assert layout.placed_names() == ()
    assert layout.pack_bytes() == {"place": 0, "concat": 4 * layout.total}
    flatten = functools.partial(layout.flatten, place=True)
    got = jax.make_jaxpr(flatten)(shapes)
    want = jax.make_jaxpr(lambda t: _concat_flatten(layout, t))(shapes)
    assert str(got) == str(want)
    assert "custom_call" not in _lowered_for_tpu(flatten, shapes)


def test_the_placed_form_lowers_to_one_custom_call_a_tensor(monkeypatch):
    """Lowered for the chip: a Mosaic call a placed tensor, the second
    aliased onto the first's buffer, and one ``dynamic_update_slice`` a
    run of the rest."""
    monkeypatch.setattr(kernels, "use_pallas", lambda: True)
    monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", 32 << 10)
    layout, tree, placed = _layout(monkeypatch, "buckets")
    assert len(placed) == 2
    text = _lowered_for_tpu(
        functools.partial(layout.flatten, place=True), tree)
    assert text.count("stablehlo.custom_call @tpu_custom_call") == 2
    assert text.count("kernel_name = \"place_rows\"") == 2
    assert text.count("output_operand_aliases") == 1
    # b at 0, a right behind it: ONE run of the rest, behind both
    assert text.count("stablehlo.dynamic_update_slice") == 1
    assert text.count("stablehlo.concatenate") == 1


@pytest.mark.parametrize("floor, placed", [(0, 6), (300_000, 1)],
                         ids=["a_bucket_a_size", "one_bucket_with_tails"])
def test_three_dgc_steps_end_in_the_concatenate_forms_state(
        monkeypatch, rec, mesh8, floor, placed):
    """A flat DGC step on seven dense kernels: three dispatches with the
    pack placing (six tensors, each a bucket; or the first row of one
    bucket whose other rows have tails) end in bitwise the state of
    three with the pack concatenating; the count gives the bytes of
    each path under ``step.trace``."""
    monkeypatch.setattr(ParamLayout, "FLOOR_SLOTS", floor)
    runs, counts = {}, {}
    for bound in (4 * 16384, NEVER):
        monkeypatch.setattr(flat_mod, "PLACE_MIN_BYTES", bound)
        setup, state, step, batch = uia._step(
            mesh8, model=uia._SevenSizes(), ratio=0.01)
        before = len(rec.records())
        runs[bound] = uia._run(step, state, batch)
        new = rec.records()[before:]
        spans = {r["id"] for r in new
                 if r["kind"] == "span" and r["name"] == "step.trace"}
        counts[bound] = {r["args"]["path"]: r["value"] for r in new
                         if r["name"] == "step.pack"
                         and r["parent"] in spans}
        layout = setup.layout
        assert len(layout.placed_names()) == (placed if bound != NEVER
                                              else 0)
        assert counts[bound] == layout.pack_bytes()
    assert counts[NEVER] == {"place": 0, "concat": 4 * layout.total}
    assert 0 < counts[4 * 16384]["place"] < 4 * layout.total
    for (got, loss_g), (want, loss_w) in zip(runs[4 * 16384], runs[NEVER]):
        assert loss_g == loss_w
        flat_g, tree_g = jax.tree.flatten(got)
        flat_w, tree_w = jax.tree.flatten(want)
        assert tree_g == tree_w
        for a, b in zip(flat_g, flat_w):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    first, last = runs[NEVER][0][0], runs[NEVER][-1][0]
    assert not np.array_equal(np.asarray(first.params),
                              np.asarray(last.params))


def test_the_model_dtype_step_has_no_pack_and_counts_none(rec, mesh8):
    """A dense step counts its one path; the count is the step's, once
    a path a trace."""
    from dgc_tpu.analysis.suite import build_fixture
    state, step, setup, inputs = build_fixture(mesh8, compressor="none")
    step(state, *inputs)
    packs = [r for r in rec.records() if r["name"] == "step.pack"]
    assert sorted(r["args"]["path"] for r in packs) == ["concat", "place"]
    assert {r["args"]["path"]: r["value"] for r in packs} == {
        "place": 0, "concat": 4 * setup.layout.total}

"""The SambaY decoder (``dgc_tpu/models/sambay.py``: Phi-4-mini-flash-
reasoning's layers) against its plain reference (``tests/
sambay_reference.py``) at small widths on the CPU, its pieces against what
they stand for, its parameter counts at the published widths, and the
model through the DGC train step and ``train.py``."""

import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import sambay_reference as reference
from dgc_tpu.models import sambay
from dgc_tpu.models.sambay import SambaY, phi4_mini_flash, published_layers
from dgc_tpu.utils.pytree import named_flatten

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CUT = (("swa", 15), ("ssm", 16), ("full", 17), ("gmu", 18))
SMALL = dict(hidden=64, mlp=96, heads=4, kv_heads=2, head_dim=16, window=8,
             ssm_inner=128, ssm_state=16, ssm_conv=4, ssm_rank=4,
             scan_chunk=8, attn_block=8)
HEADS = dict(heads=4, kv_heads=2, window=8)
SEQ, VOCAB = 32, 128


def _model(layers=CUT, vocab_size=VOCAB, **changes):
    return SambaY(vocab_size=vocab_size, layers=layers,
                  **{**SMALL, **changes})


def _batch(seed=0, rows=2, vocab_size=VOCAB):
    rng = np.random.RandomState(seed)
    tokens = rng.randint(0, vocab_size, (rows, SEQ + 1)).astype(np.int32)
    return jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:]).reshape(-1)


def _params(model, seed=0, spread=0.05):
    """The model's init, every tensor moved off its zeros and ones so that
    every parameter matters to the loss (one program: an eager op a leaf
    would compile some sixty)."""
    @jax.jit
    def make(key):
        params = model.init(key, _batch()[0])["params"]
        leaves, treedef = jax.tree.flatten(params)
        keys = jax.random.split(jax.random.fold_in(key, 1), len(leaves))
        return treedef.unflatten([
            leaf + spread * jax.random.normal(k, leaf.shape)
            for leaf, k in zip(leaves, keys)])

    return make(jax.random.PRNGKey(seed))


def _loss_and_grads(model, params, tokens, labels):
    def loss(p):
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()

    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("layers", [
    CUT,
    (("ssm", 16),),
    (("swa", 15),),
    (("full", 17),),
    (("ssm", 16), ("gmu", 18)),
    (("full", 17), ("cross", 19)),
], ids=["cut", "ssm", "swa", "full", "gmu", "cross"])
def test_model_agrees_with_the_plain_reference(layers):
    """Loss and EVERY gradient tensor, for the cut's four layers and for
    each mixer alone (a ``gmu`` behind the ``ssm`` whose memory it reads,
    a ``cross`` behind the ``full`` whose keys it reads)."""
    model = _model(layers)
    params = _params(model)
    tokens, labels = _batch()
    with jax.default_matmul_precision("highest"):
        loss, grads = _loss_and_grads(model, params, tokens, labels)
        want_loss, want = jax.jit(
            lambda p: reference.loss_and_grads(
                p, tokens, labels, layers=layers, **HEADS))(params)
    np.testing.assert_allclose(loss, want_loss, rtol=2e-6)
    got, want = named_flatten(grads)[0], named_flatten(want)[0]
    assert set(got) == set(want)
    for name in want:
        err = float(jnp.linalg.norm(got[name] - want[name])
                    / jnp.linalg.norm(want[name]))
        assert err < 1e-4, (name, err)


def _scan_inputs(seed, batch, seq, inner=24, state=5, delta_max=0.5):
    rng = np.random.RandomState(seed)
    delta = jnp.asarray(rng.uniform(1e-3, delta_max, (batch, seq, inner)),
                        jnp.float32)
    u, b_in, c_out, weights = (
        jnp.asarray(rng.randn(batch, seq, width), jnp.float32)
        for width in (inner, state, state, inner))
    a = -jnp.asarray(rng.uniform(0.5, 8.0, (state, inner)), jnp.float32)
    return (delta, u, b_in, c_out, a), weights


def _sequential_scan(delta, u, b_in, c_out, a):
    """The plain ``lax.scan`` over t, autodiff's backward."""
    def step(h, at_t):
        d_t, u_t, b_t, c_t = at_t
        h = (jnp.exp(d_t[:, None, :] * a) * h
             + (d_t * u_t)[:, None, :] * b_t[:, :, None])
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    _, y = jax.lax.scan(
        step, jnp.zeros((u.shape[0],) + a.shape),
        tuple(jnp.moveaxis(t, 1, 0) for t in (delta, u, b_in, c_out)))
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("chunk, batch, seq, delta_max", [
    (1, 2, SEQ, 0.5),
    (7, 2, SEQ, 0.5),           # does not divide S: three padded steps
    (16, 2, SEQ, 0.5),
    (SEQ, 2, SEQ, 0.5),         # one chunk
    (40, 1, SEQ, 0.5),          # a chunk longer than S, batch 1
    (8, 1, 1, 0.5),             # S = 1
    (12, 2, SEQ, 0.5),          # neither a multiple of the unroll nor of S
    (5, 1, 19, 0.5),
    (16, 2, SEQ, 200.0),        # exp(delta_t * A) underflows to 0
], ids=["1", "7", "16", "one_chunk", "longer_than_s", "s_1",
        "off_the_unroll", "odd", "decay_underflows"])
def test_chunked_scan_is_the_sequential_recurrence(chunk, batch, seq,
                                                   delta_max):
    """Outputs and the gradients of EVERY input (``a``'s sums over chunks
    and the batch) against the plain ``lax.scan`` over t, under a
    cotangent that is non-zero on every real step (the padded steps'
    is zero: they are cut off)."""
    args, weights = _scan_inputs(chunk, batch, seq, delta_max=delta_max)
    if delta_max > 1:
        decays = np.exp(np.asarray(args[0])[:, :, None, :]
                        * np.asarray(args[4]))
        assert (decays == 0).any() and (decays > 0).any()

    def chunked(*xs):
        return sambay.selective_scan(*xs, chunk=chunk)

    want_y = _sequential_scan(*args)
    np.testing.assert_allclose(chunked(*args), want_y, rtol=1e-5, atol=1e-6)
    got, want = (jax.grad(lambda *xs: jnp.sum(fn(*xs) * weights),
                          argnums=range(5))(*args)
                 for fn in (chunked, _sequential_scan))
    for g, w in zip(got, want):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


def _avals(jaxpr):
    """Every variable's aval in a jaxpr and in the jaxprs its equations
    carry (scan and while bodies, calls, custom rules)."""
    jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    for var in jaxpr.invars + jaxpr.constvars:
        yield var.aval
    for eqn in jaxpr.eqns:
        for var in eqn.outvars:
            yield var.aval
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                if hasattr(getattr(sub, "jaxpr", sub), "eqns"):
                    yield from _avals(sub)


def test_the_scans_backward_is_the_hand_written_one():
    """The forward holds the chunk's ``custom_vjp`` call and no array over
    (steps, N, E) at all; the gradient holds none but whole chunks'
    ([B, L, N, E]: the recomputed states, the stored dh and what the
    chunk-wide reductions make of them), the single states written into
    them ([B, 1, N, E]) and the state a chunk the outer scan keeps: no
    halves and quarters of a log-depth sweep, no [S, N, E] history."""
    chunk, batch, state, inner = 8, 2, 5, 24
    args, weights = _scan_inputs(0, batch, SEQ, inner, state)

    def loss(*xs):
        return jnp.sum(sambay.selective_scan(*xs, chunk=chunk) * weights)

    def wide(fn):
        return {aval.shape for aval in _avals(jax.make_jaxpr(fn)(*args))
                if len(aval.shape) >= 4 and aval.shape[-2:] == (state, inner)
                and aval.shape[:2] != (1, 1)}       # ``a`` beside [B, L, N, E]

    assert "custom_vjp_call" in str(jax.make_jaxpr(loss)(*args))
    assert wide(loss) == set()
    assert wide(jax.grad(loss, argnums=range(5))) == {
        (batch, chunk, state, inner), (batch, 1, state, inner),
        (SEQ // chunk, batch, state, inner)}


def _dense_attention(q, k, v, window):
    """softmax over the whole [S, S] mask: no blocks, no extents."""
    seq, head_dim = q.shape[-2:]
    t, j = jnp.arange(seq)[:, None], jnp.arange(seq)[None, :]
    mask = j <= t if window is None else (j <= t) & (j > t - window)
    scores = jnp.einsum("...kgtd,...kjd->...kgtj", q, k) / np.sqrt(head_dim)
    probs = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return jnp.einsum("...bkgtj,bkjd->...bkgtd", probs, v)


@pytest.mark.parametrize("seq, window, block", [
    (32, None, 8),              # the cell's ratios: S = 4 blocks ...
    (32, 8, 8),                 # ... and the window a block
    (64, None, 8),
    (64, 16, 8),                # S = 8 blocks, the window two
    (29, None, 8),              # S no multiple of the block: padded behind
    (29, 8, 8),
    (12, None, 16),             # one block, shorter than ``block``
    (12, 5, 16),
    (20, 32, 8),                # a window past the row: the causal mask
    (32, 3, 8),                 # a window inside a block
    (40, 11, 8),                # a window that is no multiple of the block
    (23, 7, 4),
    (20, 18, 8),                # a window that reaches back past key 0
    (24, None, 8),              # three blocks in two runs
], ids=lambda value: str(value))
def test_blocked_attention_is_the_dense_masked_softmax(seq, window, block):
    """The value and the three gradients against a softmax over the dense
    [S, S] mask, written here; the keys and values are drawn apart from
    the queries, as a ``cross`` layer's come from another layer (a causal
    case is a ``cross``-shaped call)."""
    _attention_agrees(seq, window, block)


@pytest.mark.parametrize("window", [None, 11])
@pytest.mark.parametrize("runs, part", [(1, 1), (3, 1), (4, 4)])
def test_the_extents_constants_are_free(monkeypatch, runs, part, window):
    """One run and a block as wide as its window (every key to every
    block: the form before the extents), and more runs and smaller window
    blocks than ship: the same numbers."""
    monkeypatch.setattr(sambay, "CAUSAL_RUNS", runs)
    monkeypatch.setattr(sambay, "WINDOW_BLOCKS", part)
    _attention_agrees(40, window, 8)


def _attention_agrees(seq, window, block):
    keys = jax.random.split(jax.random.PRNGKey(seq * 31 + block), 4)
    q = jax.random.normal(keys[0], (2, 2, 2, 3, seq, 8))
    k = 2.0 * jax.random.normal(keys[1], (2, 2, 2, seq, 8))
    v = jax.random.normal(keys[2], (2, 2, seq, 16))
    weights = jax.random.normal(keys[3], (2, 2, 2, 3, seq, 16))

    def blocked(*xs):
        return sambay.masked_attention(*xs, window, block)

    def dense(*xs):
        return _dense_attention(*xs, window)

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(blocked(q, k, v), dense(q, k, v),
                                   rtol=1e-5, atol=1e-6)
        got, want = (jax.grad(lambda *xs: jnp.sum(fn(*xs) * weights),
                              argnums=range(3))(q, k, v)
                     for fn in (blocked, dense))
    for g, w in zip(got, want):
        assert np.all(np.isfinite(g))
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("seq, window, block, given", [
    # S = 4 blocks: two runs of two, keys to each run's end
    (2048, None, 512, (512, ((0, 2, 1024), (2, 2, 2048)))),
    # a window's block is half of it; it reaches two blocks back
    (2048, 512, 512, (256, ((0, 8, 768),))),
    (2048, 512, 128, (128, ((0, 16, 640),))),
    (100, None, 512, (100, ((0, 1, 100),))),        # one block: no runs
    (24, None, 8, (8, ((0, 2, 16), (2, 1, 24)))),
    (100, 2000, 8, (8, ((0, 7, 56), (7, 6, 104)))),   # past the row: causal
    (100, 90, 40, (40, ((0, 3, 120),))),    # no further back than key 0
])
def test_the_keys_a_block_is_given(seq, window, block, given):
    """``attention_extents``: the block and the runs ``(first block,
    blocks, keys each is given)``, from the shapes alone."""
    assert sambay.attention_extents(seq, window, block)[1:] == given


@pytest.mark.parametrize("kind, reaches", [("swa", False), ("full", True)])
def test_a_token_eight_back_reaches_full_attention_only(kind, reaches):
    """Window 8 counts the query itself: position t reads keys t-7 .. t. A
    change of token 10 moves a ``full`` layer's output at 18 and beyond
    and a ``swa`` layer's at 10 .. 17 only (one layer: nothing relays)."""
    model = _model(((kind, 15),))
    params = _params(model)
    tokens, _ = _batch(rows=1)
    other = tokens.at[0, 10].set((tokens[0, 10] + 1) % VOCAB)
    a, b = (model.apply({"params": params}, t).reshape(SEQ, VOCAB)
            for t in (tokens, other))
    moved = np.asarray(jnp.max(jnp.abs(a - b), axis=-1))
    assert np.all(moved[:10] == 0) and np.all(moved[10:18] > 0)
    if reaches:
        assert np.all(moved[18:] > 0)
    else:
        assert np.all(moved[18:] == 0)


def test_a_vocabulary_slice_gives_the_whole_models_logits_on_the_slice():
    """The tied table holds rows 0 .. 63 of the whole model's 128: for ids
    of the slice the logits are the whole model's, on the slice."""
    whole, part = _model(), _model(vocab_size=64)
    params = _params(whole)
    sliced = dict(params, embedding=params["embedding"][:64])
    tokens, _ = _batch(vocab_size=64)
    want = whole.apply({"params": params}, tokens)[:, :64]
    got = part.apply({"params": sliced}, tokens)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_the_memorys_gradient_reaches_the_ssm_layer_from_the_gmu():
    """Layer 16's parameters get a gradient from two layers: its own
    output and the memory layer 18 reads. With the ``ssm`` layer's own
    output cut off (a zero out-projection) the memory is its only way to
    the loss, and still moves its parameters."""
    model = _model((("ssm", 16), ("gmu", 18)))
    params = _params(model)
    params["layer_16"]["mixer"]["out_proj"] = jnp.zeros_like(
        params["layer_16"]["mixer"]["out_proj"])
    _, grads = _loss_and_grads(model, params, *_batch())
    mixer = grads["layer_16"]["mixer"]
    for name in ("in_proj", "conv_kernel", "x_proj", "dt_proj", "A_log", "D"):
        assert float(jnp.linalg.norm(mixer[name])) > 0, name
    # without a reader the same layer's memory goes nowhere
    alone = _model((("ssm", 16),))
    lone = {k: params[k] for k in ("embedding", "layer_16", "norm_f")}
    _, grads = _loss_and_grads(alone, lone, *_batch())
    assert float(jnp.linalg.norm(grads["layer_16"]["mixer"]["A_log"])) == 0


def test_a_reader_without_its_source_is_refused():
    for layers in ((("gmu", 18),), (("cross", 19),), (("conv", 3),)):
        with pytest.raises(ValueError, match="layer"):
            jax.eval_shape(_model(layers).init, jax.random.PRNGKey(0),
                           _batch()[0])


def _shapes(model, seq=8):
    return jax.eval_shape(model.init, jax.random.PRNGKey(0),
                          jnp.zeros((1, seq), jnp.int32))["params"]


@pytest.mark.parametrize("layers, vocab_size, count", [
    (CUT, 25008, 485_433_088),
    # ISSUE 45 counted 3,852,557,824: the 32 layers and the tied table
    # without the final LayerNorm's 5,120
    (None, 200064, 3_852_562_944),
], ids=["cut", "published"])
def test_parameter_counts_at_the_published_widths(layers, vocab_size, count):
    named, _ = named_flatten(_shapes(phi4_mini_flash(vocab_size, layers)))
    assert sum(int(np.prod(s.shape)) for s in named.values()) == count
    # no tensor of the flat buffer has a minor dimension under 128 but a
    # differential attention's four lambdas (64, 1-D: the dense tail)
    small = {n: s.shape for n, s in named.items() if s.shape[-1] < 128}
    assert all(len(shape) == 1 and "lambda" in n
               for n, shape in small.items()), small


def test_the_published_layer_kinds():
    kinds = [kind for kind, _ in published_layers()]
    assert [kinds.count(k) for k in ("ssm", "swa", "full", "gmu", "cross")
            ] == [9, 8, 1, 7, 7]
    assert published_layers()[15:19] == CUT
    # a reader stands behind the layer whose memory or keys it reads
    source = {"gmu": (("ssm", 0),), "cross": (("full", 0),)}
    per_layer = {}
    for kind, i in (("ssm", 16), ("swa", 15), ("full", 17), ("gmu", 18),
                    ("cross", 19)):
        layer = _shapes(phi4_mini_flash(
            8, source.get(kind, ()) + ((kind, i),)))[f"layer_{i}"]
        per_layer[kind] = sum(int(np.prod(s.shape))
                              for s in jax.tree.leaves(layer))
    assert per_layer == {"ssm": 119_895_040, "swa": 98_322_304,
                         "full": 98_322_304, "gmu": 104_867_840,
                         "cross": 91_766_144}


def test_the_flat_layout_at_the_published_widths():
    """The cut's DGC layout: every tensor of two or more dimensions is
    compressed, the 1-D ones are the dense tail, and the buffer holds the
    485M parameters once."""
    from dgc_tpu.compression import DGCCompressor, DGCSGDMemory
    from dgc_tpu.compression.flat import ParamLayout
    shapes = _shapes(phi4_mini_flash(25008, CUT))
    named, _ = named_flatten(shapes)
    comp = DGCCompressor(0.001, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(shapes, comp)
    assert layout.num_params == 485_433_088
    assert set(layout.dense_names) == {n for n, p in named.items()
                                       if p.ndim == 1}
    assert all(layout.shapes[n][-1] >= 128 for n in layout.compressed_names)


def _arm(mesh, dgc, model=None, apply_fn=None):
    """One arm's first state and its train step over the flat engine
    (``apply_fn``: what the step calls instead of ``model.apply``)."""
    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         dgc_sgd)
    from dgc_tpu.compression import Compression
    from dgc_tpu.optim import sgd
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    model = model or _model()
    variables = dict(model.init(jax.random.PRNGKey(0), _batch()[0]))
    if dgc:
        comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
        named, _ = named_flatten(variables["params"])
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        opt = dgc_sgd(1e-2, momentum=0.9, weight_decay=1e-4)
    else:
        comp = Compression.none()
        opt = sgd(1e-2, momentum=0.9, weight_decay=1e-4)
    world = mesh.devices.size
    dist = DistributedOptimizer(opt, comp, world_size=world)
    setup = make_flat_setup(variables, dist)
    state = shard_state(make_flat_state(variables, dist, setup, world), mesh,
                        dist_opt=dist)
    return state, build_train_step(apply_fn or model.apply, dist, mesh,
                                   flat=setup)


def test_two_steps_of_each_arm_through_the_flat_engine(mesh8):
    """``build_train_step`` with the flat engine, token-major labels: both
    arms finite over two steps and the same loss at step 0."""
    tokens, labels = _batch(rows=8)
    losses = {}
    for dgc in (True, False):
        state, step = _arm(mesh8, dgc)
        losses[dgc] = []
        for i in range(2):
            state, metrics = step(state, tokens, labels,
                                  jax.random.PRNGKey(i))
            losses[dgc].append(float(metrics["loss"]))
        assert np.all(np.isfinite(state.params))
    assert np.all(np.isfinite(losses[True] + losses[False]))
    np.testing.assert_allclose(losses[True][0], losses[False][0], rtol=1e-6)
    assert losses[False][1] < losses[False][0]


def test_labels_keep_their_trailing_axes_through_the_micro_batch_cut(mesh8):
    """``training/step.py``'s cut of the labels into micro-batches keeps
    what follows the batch axis: [B, S] labels against [B, S, V] logits
    (PERF.md section 7.5a)."""
    model = _model(())          # the tied table and the final norm alone
    tokens, labels = _batch(rows=8)

    def by_row(variables, x, train=True, rngs=None):
        return model.apply(variables, x).reshape(x.shape + (VOCAB,))

    out = []
    for fn, lbls in ((None, labels), (by_row, labels.reshape(tokens.shape))):
        state, step = _arm(mesh8, False, model, fn)
        state, metrics = step(state, tokens, lbls, jax.random.PRNGKey(0))
        out.append((float(metrics["loss"]), np.asarray(state.params)))
    assert out[0][0] == out[1][0]
    np.testing.assert_array_equal(out[0][1], out[1][1])


def test_the_token_split():
    from dgc_tpu.data import SyntheticTokens
    data = SyntheticTokens(seq_len=SEQ, vocab_size=VOCAB, synthetic_size=12,
                           seed=3)
    assert len(data["train"]) == 12 and len(data["test"]) == 3
    tokens, labels = data["train"].get_batch(np.array([4, 1]))
    assert tokens.shape == (2, SEQ) and tokens.dtype == np.int32
    assert labels.shape == (2 * SEQ,) and labels.dtype == np.int32
    # next-token labels, token-major
    np.testing.assert_array_equal(labels.reshape(2, SEQ)[:, :-1],
                                  tokens[:, 1:])
    assert tokens.min() >= 0 and tokens.max() < VOCAB
    again = SyntheticTokens(seq_len=SEQ, vocab_size=VOCAB, synthetic_size=12,
                            seed=3)["train"].get_batch(np.array([4, 1]))
    np.testing.assert_array_equal(again[0], tokens)


def test_the_counts_and_scopes_a_traced_build_leaves(rec):
    """Once a trace: a ``model.layers`` count a layer with its kind and
    published index, the micro-batch's tokens, the scan's chunks, the
    score entries an attention layer computes and keeps; and the five
    device parts in the lowered step's op names."""
    model = _model()
    tokens, _ = _batch()
    shapes = _shapes(model, SEQ)
    with rec.span("step.trace"):
        lowered = jax.jit(lambda p, t: model.apply({"params": p}, t)).lower(
            shapes, tokens)
    # the counts of that trace: what the shapes' trace left has no parent
    by_name = {}
    for r in rec.records():
        if r["kind"] == "count" and r["parent"] is not None:
            by_name.setdefault(r["name"], []).append(r)
    assert [(r["args"]["kind"], r["args"]["index"])
            for r in by_name["model.layers"]] == list(CUT)
    assert [r["value"] for r in by_name["model.tokens"]] == [2 * SEQ]
    assert [(r["value"], r["args"]["chunk"], r["args"]["unroll"])
            for r in by_name["model.scan_chunks"]] == [
                (4, 8, min(8, sambay.SCAN_UNROLL))]
    # S = 4 blocks of 8, window 8: blocks of 4 given 12 keys, and two
    # runs of two blocks given 16 and 32 keys
    scores = [(r["args"]["kind"], r["args"]["index"], r["args"]["block"],
               r["value"], r["args"]["kept"])
              for r in by_name["model.attn_scores"]]
    assert scores == [("swa", 15, 4, 8 * 4 * 12, 36 + 24 * 8),
                      ("full", 17, 8, 2 * 8 * 16 + 2 * 8 * 32, 32 * 33 // 2)]
    # the extents engage: under every key to every block (SEQ a row) and
    # a block's own and a window's worth to a block of 8 (16 a row)
    for (*_, value, kept), every_key in zip(scores, (SEQ * 16, SEQ * SEQ)):
        assert kept <= value < every_key
    text = lowered.as_text(debug_info=True)
    for part in ("ssm", "attn", "gmu", "mlp", "head"):
        assert f"dgcph.fwd_bwd.{part}" in text, part


def test_cli_trains_on_the_token_split():
    """``train.py`` on the token split: the sample comes from the
    dataset's kind, a model without batch statistics checkpoints."""
    suffix = f".lmtest{os.getpid()}"
    run = os.path.join(REPO, "runs", f"lm.phi4_mini_flash+dgc.wm0{suffix}.np1")
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    small = [arg for key, value in SMALL.items()
             for arg in (f"--model.{key}", str(value))]
    cmd = [sys.executable, "train.py", "--configs",
           "configs/lm/phi4_mini_flash.py", "configs/dgc/wm0.py",
           "--cpu_mesh", "1", "--suffix", suffix,
           "--dataset.seq_len", str(SEQ), "--dataset.vocab_size", str(VOCAB),
           "--model.vocab_size", str(VOCAB), "--dataset.synthetic_size", "4",
           "--model.layers", repr([list(l) for l in CUT[:2]]),
           "--train.num_epochs", "1", *small]
    try:
        r = subprocess.run(cmd, cwd=REPO, env=env, capture_output=True,
                           text=True, timeout=600)
        assert r.returncode == 0, r.stderr[-2000:]
        assert "[loss]" in r.stdout and "acc/test_top1" in r.stdout
        assert os.path.isdir(os.path.join(run, "checkpoints", "e0"))
    finally:
        shutil.rmtree(run, ignore_errors=True)


def test_the_benchmarks_copy_is_the_tests_reference():
    """``benchmark/references/phi4_mini_flash.py`` is self-contained; on a
    seed it gives ``tests/sambay_reference.py``'s loss and gradients, and
    its defaults are the cell's cut."""
    path = os.path.join(REPO, "benchmark", "references", "phi4_mini_flash.py")
    spec = importlib.util.spec_from_file_location("phi4_reference", path)
    copy = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(copy)
    assert copy.LAYERS == CUT == reference.LAYERS and copy.ROW_BLOCK == 1
    for limit in ("LOSS_RTOL", "GRAD_RTOL", "UPDATE_RTOL", "CONSERVED_RTOL"):
        assert 0 < getattr(copy, limit) < 1e-3
    assert "dgc_tpu" not in open(path).read().split('"""', 2)[2]
    model = _model()
    params = _params(model, seed=5)
    tokens, labels = _batch(seed=5)
    got, want = (jax.jit(lambda p, module=module: module.loss_and_grads(
        p, tokens, labels, **HEADS))(params) for module in (copy, reference))
    assert float(got[0]) == float(want[0])
    jax.tree.map(np.testing.assert_array_equal, got[1], want[1])

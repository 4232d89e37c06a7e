"""The recorder's spans and counts where the layers make them, and the
device scopes and kernel names in the lowered step (docs/TELEMETRY.md
§Tracing; the readers are benchmark/layer_metrics/*):

* input — ``input.get_batch`` in the splits, ``input.queue_wait`` +
  ``input.queue_depth`` in ``Prefetcher``, ``input.stage`` in
  ``stage_ahead``; one batch's ``seq`` links them across the producer
  thread;
* step — ``step.trace`` owns every count made while the step is traced;
  ``step.trace_model`` and ``exchange.trace`` split its seconds and own
  none;
* exchange — ``exchange.collective`` bytes are the traced operands' bytes
  on the 8-device CPU mesh, for both engines;
* scopes — the parts, ``params_view``, ``plumbing`` and the dense
  engine's ``dense`` reach the compiled text when on; no op the step
  lowers from ``compression/``, ``optim/`` or ``ops/`` is without one;
* kernels — every ``pl.pallas_call`` site passes a unique ``name=`` that
  reaches the lowered text.
"""

import ast
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from dgc_tpu import (Compression, DGCCompressor, DGCSGDMemory,
                     DistributedOptimizer, dgc_sgd, sgd)
from dgc_tpu.analysis import jaxpr as jx
from dgc_tpu.data import ArraySplit, Prefetcher, SyntheticSplit, stage_ahead
from dgc_tpu.ops import kernels
from dgc_tpu.telemetry import trace as trace_mod
from dgc_tpu.utils.compat import shard_map
from dgc_tpu.utils.pytree import named_flatten

W = 8
MEAN = np.zeros(3, np.float32)
STD = np.ones(3, np.float32)


def _named(records, name, kind="span"):
    return [r for r in records if r["kind"] == kind and r["name"] == name]


# --------------------------------------------------------------------- #
# input                                                                  #
# --------------------------------------------------------------------- #

def _array_split(n=32, delay=0.0):
    rng = np.random.RandomState(0)
    split = ArraySplit(rng.randint(0, 256, (n, 8, 8, 3)).astype(np.uint8),
                       rng.randint(0, 10, n), MEAN, STD, train=True,
                       augment=False)
    if delay:
        fast = split.get_batch

        def slow(indices):
            time.sleep(delay)
            return fast(indices)
        split.get_batch = slow
    return split


@pytest.mark.fast
@pytest.mark.parametrize("make", [
    _array_split,
    lambda: SyntheticSplit(32, 8, 10, MEAN, STD),
], ids=["ArraySplit", "SyntheticSplit"])
def test_get_batch_span_records_images_on_the_callers_thread(rec, make):
    split = make()
    images, labels = split.get_batch(np.arange(6))
    assert images.shape == (6, 8, 8, 3) and labels.shape == (6,)
    span, = rec.records()
    assert span["name"] == "input.get_batch"
    assert span["args"] == {"images": 6} and span["seq"] is None
    assert span["thread"] == threading.get_ident()
    assert span["t1_ns"] > span["t0_ns"]


@pytest.mark.fast
def test_seq_links_one_batch_from_get_batch_through_queue_to_staging(rec):
    batches = Prefetcher(_array_split(), iter(np.arange(32).reshape(4, 8)))
    staged_bytes = []

    def stage(batch):
        staged_bytes.append(sum(a.nbytes for a in batch))
        return batch

    try:
        with rec.span("epoch", epoch=0) as epoch:
            out = [b for b in stage_ahead(batches, stage)]
    finally:
        batches.close()
    assert len(out) == 4 and all(len(b) == 2 for b in out)
    records = rec.records()
    made = _named(records, "input.get_batch")
    waits = [w for w in _named(records, "input.queue_wait")
             if w["seq"] is not None]            # the last get finds "end"
    stages = _named(records, "input.stage")
    seqs = [g["seq"] for g in made]
    assert len(set(seqs)) == 4 and None not in seqs
    assert [w["seq"] for w in waits] == seqs == [s["seq"] for s in stages]
    for g, w, s, nbytes in zip(made, waits, stages, staged_bytes):
        # produced, then handed over, then staged
        assert g["t1_ns"] <= w["t1_ns"] <= s["t0_ns"]
        assert s["args"] == {"bytes": nbytes} and g["args"] == {"images": 8}
    # spans nest per thread: the producer's have no parent on the
    # consumer's stack, the consumer's hang under the span open there
    me = threading.get_ident()
    assert {g["thread"] for g in made} != {me} and len(
        {g["thread"] for g in made}) == 1
    assert all(g["parent"] is None for g in made)
    assert all(r["thread"] == me and r["parent"] == epoch.id
               for r in waits + stages)


@pytest.mark.fast
@pytest.mark.parametrize("slow", ["producer", "consumer"])
def test_queue_depth_says_who_outran_whom(rec, slow):
    split = _array_split(delay=0.03 if slow == "producer" else 0.0)
    batches = Prefetcher(split, iter(np.arange(32).reshape(8, 4)), depth=2)
    try:
        for _ in batches:
            if slow == "consumer":
                time.sleep(0.03)
    finally:
        batches.close()
    records = rec.records()
    depths = _named(records, "input.queue_depth", "count")
    waits = {w["id"] for w in _named(records, "input.queue_wait")}
    assert len(depths) == 9                      # eight batches and "end"
    assert all(d["parent"] in waits for d in depths)
    values = [d["value"] for d in depths]
    if slow == "producer":
        # the consumer always waits ("end" follows the last batch at once)
        assert values[:8] == [0] * 8
    else:
        assert max(values[1:]) >= 1              # batches were ready


# --------------------------------------------------------------------- #
# step and exchange                                                      #
# --------------------------------------------------------------------- #

def _params(fc=(32, 10)):
    rng = np.random.RandomState(0)
    return {
        "conv1": {"kernel": jnp.asarray(rng.randn(3, 3, 4, 8), jnp.float32)},
        "dense": {"kernel": jnp.asarray(rng.randn(*fc), jnp.float32),
                  "bias": jnp.asarray(rng.randn(fc[1]), jnp.float32)},
        "bn": {"scale": jnp.asarray(rng.randn(8), jnp.float32)},
    }


def _engine(kind, ratio=0.05, fc=(32, 10)):
    params = _params(fc)
    if kind == "dgc":
        comp = DGCCompressor(ratio, memory=DGCSGDMemory(momentum=0.9))
        named, _ = named_flatten(params)
        comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                    world_size=W)
    else:
        dist = DistributedOptimizer(sgd(0.1), Compression.none(),
                                    world_size=W)
    layout, engine = dist.make_flat(params)
    return layout, engine


def _trace_exchange(layout, engine, mesh):
    """Trace (never run) one exchange under the mesh; returns the flat
    jaxpr program."""
    def worker(fg, mem, key):
        mem = jax.tree.map(lambda x: x[0], mem)
        out, mem = engine.exchange(fg[0], mem, key, "data", W)
        return out[None], jax.tree.map(lambda x: x[None], mem)

    f = shard_map(worker, mesh=mesh, in_specs=(P("data"), P("data"), P()),
                  out_specs=(P("data"), P("data")), check_vma=False)
    mem = jax.tree.map(lambda x: jnp.broadcast_to(x, (W,) + x.shape),
                       engine.init_memory())
    with trace_mod.span("step.trace", flat=True):
        closed = jax.make_jaxpr(f)(jnp.zeros((W, layout.total)), mem,
                                   jax.random.PRNGKey(0))
    return jx.flatten(closed)


def _operand_bytes(prog):
    """(primitive, bytes of its operands) of every collective equation."""
    out = []
    for e in prog.eqns:
        if e.prim in ("psum", "all_gather"):
            avals = [prog.avals[v] for v in e.invars]
            out.append((e.prim, sum(int(np.prod(a.shape)) * a.dtype.itemsize
                                    for a in avals)))
    return sorted(out)


@pytest.mark.parametrize("kind, engine_class", [
    ("dgc", "FlatDGCEngine"), ("dense", "FlatDenseExchange")])
def test_collective_bytes_are_the_traced_operands_bytes(rec, mesh8, kind,
                                                        engine_class):
    layout, engine = _engine(kind)
    if kind == "dense":
        # the engine's own floor is sized for real models: at the
        # fixture's size everything would ride one collective
        engine.MIN_SEGMENT = 64
    prog = _trace_exchange(layout, engine, mesh8)
    counts = _named(rec.records(), "exchange.collective", "count")
    trace, = _named(rec.records(), "step.trace")
    assert all(c["parent"] == trace["id"] for c in counts)
    assert {c["args"]["engine"] for c in counts} == {engine_class}
    assert {c["args"]["axis"] for c in counts} == {"data"}
    got = sorted((c["args"]["kind"], c["value"]) for c in counts)
    assert got == _operand_bytes(prog)
    item = 4
    if kind == "dgc":
        # values and indices of the payload, and the dense tail's psum
        assert got == [("all_gather", engine.payload_size * item)] * 2 + [
            ("psum", (layout.total - engine.T) * item)]
    else:
        # one psum per segment (PR 28), each count naming its segment;
        # together still the whole flat buffer
        segments = engine.segments()
        assert len(segments) > 1
        assert [(c["args"]["segment"], c["value"]) for c in counts] == [
            (i, sum(hi - lo for lo, hi in seg) * item)
            for i, seg in enumerate(segments)]
        assert sum(c["value"] for c in counts) == layout.total * item


@pytest.mark.parametrize("flag, path", [(False, "scatter"),
                                        (True, "stream")])
def test_apply_path_is_counted_once_a_trace(rec, mesh8, flag, path):
    """``exchange.apply``: one count a trace, under ``step.trace``
    beside the collectives, its value the gathered pairs and ``path``
    what `_apply` chose (off the chip: the XLA scatters unless a flag
    forces the kernel)."""
    layout, engine = _engine("dgc")
    engine.c.fused_apply = flag
    _trace_exchange(layout, engine, mesh8)
    count, = _named(rec.records(), "exchange.apply", "count")
    trace, = _named(rec.records(), "step.trace")
    assert count["parent"] == trace["id"]
    assert count["value"] == W * engine.payload_size
    assert count["args"] == {"path": path}
    assert (engine._use_fused_apply(engine._mem, False, jnp.float32)
            is flag)


def test_dgc_wire_is_some_hundred_times_smaller_at_ratio_0001(rec, mesh8):
    """PERF.md §1's "~1000x": 0.001 of the coordinates, each sent as a
    value AND an index, so ~500x on a model whose 1-D tail is small."""
    fc = (1024, 1024)
    sums = {}
    for kind in ("dgc", "dense"):
        layout, engine = _engine(kind, ratio=0.001, fc=fc)
        _trace_exchange(layout, engine, mesh8)
    for c in _named(rec.records(), "exchange.collective", "count"):
        sums[c["args"]["engine"]] = sums.get(c["args"]["engine"], 0) + c[
            "value"]
    ratio = sums["FlatDenseExchange"] / sums["FlatDGCEngine"]
    assert 100 < ratio < 1000, (sums, ratio)


def test_counts_hang_under_step_trace_and_a_second_trace_does_not_double(
        rec, mesh8):
    from dgc_tpu.analysis.suite import build_fixture
    state, step, setup, inputs = build_fixture(mesh8, donate=False)
    step.lower(state, *inputs)
    # a second build of the same step traces again (a new function object)
    _, step2, _, _ = build_fixture(mesh8, donate=False)
    step2.lower(state, *inputs)
    records = rec.records()
    traces = _named(records, "step.trace")
    assert len(traces) == 2
    assert all(t["args"] == {"compressor": "DGCCompressor", "flat": True}
               for t in traces)
    counts = _named(records, "exchange.collective", "count")
    per_trace = [sorted((c["args"]["kind"], c["value"]) for c in counts
                        if c["parent"] == t["id"]) for t in traces]
    assert per_trace[0] == per_trace[1] and len(per_trace[0]) == 3
    assert len(counts) == 6                  # every count has its trace
    # what a reader takes: the LAST trace's counts, once
    last = sum(v for _, v in per_trace[-1])
    engine, layout = setup.engine, setup.layout
    assert last == (2 * engine.payload_size
                    + layout.total - engine.T) * 4


@pytest.mark.parametrize("compressor, engine_class, counted", [
    ("dgc", "FlatDGCEngine", ["exchange.apply"] + ["exchange.collective"] * 3),
    ("none", "FlatDenseExchange", ["exchange.collective"]),
])
def test_child_spans_split_step_trace_and_own_no_count(rec, mesh8, compressor,
                                                       engine_class, counted):
    """``step.trace_model`` (round the scan) and ``exchange.trace`` (round
    ``engine.exchange``) are children of ``step.trace``, once a trace, and
    together no longer than it; the counts made inside ``engine.exchange``
    keep ``step.trace`` as their parent, as the benchmark's readers (and
    ``program_records.collective_bytes``) key on."""
    from dgc_tpu.analysis.suite import build_fixture
    state, step, _, inputs = build_fixture(mesh8, compressor=compressor,
                                           donate=False)
    step.lower(state, *inputs)
    _, step2, _, _ = build_fixture(mesh8, compressor=compressor,
                                   donate=False)
    step2.lower(state, *inputs)
    records = rec.records()
    traces = _named(records, "step.trace")
    models = _named(records, "step.trace_model")
    exchanges = _named(records, "exchange.trace")
    assert len(traces) == len(models) == len(exchanges) == 2
    for trace, model, exchange in zip(traces, models, exchanges):
        assert model["parent"] == exchange["parent"] == trace["id"]
        assert model["args"] == {"nbps": 1}
        assert exchange["args"] == {"engine": engine_class}
        # they nest in time: the model first, then the exchange
        assert (trace["t0_ns"] <= model["t0_ns"] <= model["t1_ns"]
                <= exchange["t0_ns"] <= exchange["t1_ns"] <= trace["t1_ns"])
        # every count of the trace was made while ``exchange.trace`` was
        # open, and hangs under ``step.trace`` all the same
        inside = [r for r in records if r["kind"] == "count"
                  and exchange["t0_ns"] <= r["t_ns"] <= exchange["t1_ns"]]
        assert sorted(c["name"] for c in inside) == counted
        assert all(c["parent"] == trace["id"] for c in inside)
    ids = {t["id"] for t in traces}
    assert all(c["parent"] in ids for c in records if c["kind"] == "count")


@pytest.mark.fast
def test_a_span_that_owns_no_counts_nests_and_leaves_them_to_its_parent(rec):
    with rec.span("outer") as outer:
        with rec.span("timing", owns_counts=False) as timing:
            rec.count("made.inside", 1)
            with rec.span("inner") as inner:
                rec.count("made.deeper", 2)
        rec.count("made.after", 3)
    with rec.span("alone", owns_counts=False):
        rec.count("made.under.none", 4)
    records = rec.records()
    spans = {r["name"]: r for r in records if r["kind"] == "span"}
    counts = {r["name"]: r["parent"] for r in records if r["kind"] == "count"}
    assert spans["timing"]["parent"] == outer.id
    assert spans["inner"]["parent"] == timing.id         # it nests
    assert "owns_counts" not in spans["timing"]["args"]
    assert counts == {"made.inside": outer.id, "made.deeper": inner.id,
                      "made.after": outer.id, "made.under.none": None}


# --------------------------------------------------------------------- #
# device scopes                                                          #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("compressor, tokens, absent", [
    ("dgc", ["dgcph.update.exchange", "dgcph.update.optimizer",
             "dgcph.params_view", "dgcph.plumbing", "dgcph.fwd_bwd",
             "dgcph.compensate", "dgcph.dense"], []),
    ("none", ["dgcph.update.exchange", "dgcph.update.optimizer",
              "dgcph.params_view", "dgcph.dense"], ["dgcph.compensate"]),
])
def test_new_scopes_reach_the_compiled_text_when_on(rec, mesh8, compressor,
                                                    tokens, absent):
    from dgc_tpu.analysis.suite import build_fixture
    state, step, _, inputs = build_fixture(mesh8, compressor=compressor,
                                           donate=False)
    text = step.lower(state, *inputs).compile().as_text()
    for token in tokens:
        assert token in text, token
    for token in absent:
        assert token not in text, token
    # the all-reduce DGC replaces sits under the dense engine's own phase
    if compressor == "none":
        reduces = [line for line in text.splitlines()
                   if " all-reduce(" in line and "f32[" in line
                   and "dgcph.update.exchange" in line]
        assert reduces and all("dgcph.dense" in line for line in reduces)


PARTS = ["dgcph.apply.sort", "dgcph.apply.stage", "dgcph.fwd_bwd.pack"]


@pytest.mark.parametrize("compressor, on, present", [
    ("dgc", True, PARTS),
    ("none", True, PARTS[2:]),      # the dense arm packs its gradients too
    ("dgc", False, []),
    ("none", False, []),
])
def test_scope_parts_reach_the_compiled_text_when_on_and_only_then(
        mesh8, compressor, on, present):
    """The parts of ``apply`` and ``fwd_bwd``: off the chip the apply pass
    is the opt-in kernel's (interpreted), whose staging is the step's own
    on the chip (``kernels._sorted_pairs``)."""
    from dgc_tpu.analysis.suite import build_fixture
    prev = trace_mod.enable(on)
    try:
        state, step, _, inputs = build_fixture(
            mesh8, compressor=compressor, donate=False,
            compressor_kwargs=({"fused_apply": True} if compressor == "dgc"
                               else None))
        text = step.lower(state, *inputs).compile().as_text()
    finally:
        trace_mod.enable(prev)
    for token in PARTS:
        assert (token in text) == (token in present), token
    if not on:
        assert "dgcph." not in text


_ALIAS = re.compile(r"^(#loc\d*) = loc\((.*)\)$", re.M)
_CALLSITE = re.compile(r"callsite\((#loc\d+) at (#loc\d+)\)$")
_NAMED = re.compile(r'"(.*)"\((#loc\d+)\)$')
_FILE = re.compile(r'"(/[^"]+)":\d+')
_FUNC = re.compile(r"^\s*func\.func (?:public |private )?@([\w.$-]+)\(")
_CALL = re.compile(r"\bcall @([\w.$-]+)\(")
_OP_LOC = re.compile(r"loc\((#loc\d+)\)$")


def _unscoped_ops(text, under):
    """Ops of a module lowered with ``debug_info=True`` whose innermost
    source frame lies in one of the directories ``under`` and which carry
    no ``dgcph.`` scope: (function, name stack, file) of each. An op of an
    outlined function (an inner ``jit``, a ``closed_call``) carries a
    name stack relative to its call: it is scoped where every call of its
    function is."""
    locs = dict(_ALIAS.findall(text))

    def resolve(alias):
        names, frames = [], False
        while alias in locs:
            value = locs[alias]
            site = _CALLSITE.match(value)
            named = _NAMED.match(value)
            if site:
                alias, frames = site.group(1), True     # the callee's frame
            elif named:
                if not frames:
                    names.append(named.group(1))
                alias = named.group(2)
            else:
                found = _FILE.match(value)
                return "/".join(names), found and found.group(1)
        return "/".join(names), None

    ops, calls, func = [], {}, None
    for line in text.splitlines():
        opened = _FUNC.match(line)
        if opened:
            func = opened.group(1)
            continue
        at = _OP_LOC.search(line)
        if func is None or at is None or line.lstrip().startswith("^"):
            continue
        name, file = resolve(at.group(1))
        called = _CALL.search(line)
        if called:
            calls.setdefault(called.group(1), []).append((func, name))
        ops.append((func, name, file))

    scoped = {}

    def is_scoped(fn):
        if fn not in scoped:
            scoped[fn] = False                  # a cycle is not a scope
            sites = calls.get(fn, [])
            scoped[fn] = bool(sites) and all(
                "dgcph." in name or is_scoped(caller)
                for caller, name in sites)
        return scoped[fn]

    return [(fn, name, file) for fn, name, file in ops
            if file and any(f"/dgc_tpu/{d}/" in file for d in under)
            and "dgcph." not in name and not is_scoped(fn)]


@pytest.mark.parametrize("kwargs", [
    {}, {"compressor_kwargs": {"fused_apply": True}},
    {"compressor": "none"}], ids=["dgc", "dgc-stream", "dense"])
def test_no_op_of_the_engine_the_optimizer_or_the_kernels_is_unscoped(
        rec, mesh8, kwargs):
    """As far as the CPU lowering goes: every op the step lowers from
    ``compression/``, ``optim/`` or ``ops/`` carries a ``dgcph`` token, so
    a device profile files its time under a phase and not under
    ``unattributed``."""
    from dgc_tpu.analysis.suite import build_fixture
    state, step, _, inputs = build_fixture(mesh8, donate=False, **kwargs)
    text = step.lower(state, *inputs).as_text(debug_info=True)
    # the parser sees the scoped ops too: those of the sort, where there
    # is one
    assert ("dgcph.apply.sort/sort" in text) == (
        "compressor_kwargs" in kwargs)
    assert os.sep + os.path.join("dgc_tpu", "compression", "flat.py") in text
    assert _unscoped_ops(text, ("compression", "optim", "ops")) == []
    # and it finds what has none: the model's ops are under ``fwd_bwd``
    # only through the scan's call
    assert _unscoped_ops(text.replace("dgcph.", "dgcpx."),
                         ("compression", "optim", "ops"))


# --------------------------------------------------------------------- #
# kernel names                                                           #
# --------------------------------------------------------------------- #

def _kernel_calls(attr):
    with open(kernels.__file__) as fh:
        tree = ast.parse(fh.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and getattr(
                node.func, "attr", getattr(node.func, "id", None)) == attr:
            yield node


@pytest.mark.fast
def test_every_pallas_call_site_passes_a_unique_name():
    # name= of every pl.pallas_call(...): a literal, or the one variable
    names = []
    for call in _kernel_calls("pallas_call"):
        value = {k.arg: k.value for k in call.keywords}.get("name")
        names.append(value.value if isinstance(value, ast.Constant)
                     else getattr(value, "id", None))
    assert len(names) == 15 and None not in names, names
    # the one shared site takes its name from its two callers
    assert names.count("name") == 1
    shared = [call.args[0].value
              for call in _kernel_calls("_payload_apply_call")]
    assert sorted(shared) == ["dgc_apply_rows", "payload_apply_bits"]
    every = [n for n in names if n != "name"] + shared
    assert len(set(every)) == len(every) == 16
    assert "payload_update_bits" in every and "place_rows" in every
    # a kernel carries the name of the jitted function that launches it
    for name in every:
        assert callable(getattr(kernels, name, None)) or callable(
            getattr(kernels, "_" + name, None)), name


@pytest.mark.parametrize("name, call", [
    ("opaque_view", lambda: kernels.opaque_view(jnp.ones((16, 128)))),
    ("opaque_view_from",
     lambda: kernels.opaque_view_from(jnp.ones((8192,)), 1024, 2048)),
    ("place_rows",
     lambda: kernels.place_rows(jnp.ones((8, 256)), 1024, 8192)),
    ("fused_compensate_bits_cands", None),
    ("topk_rows", lambda: kernels.topk_rows(jnp.ones((8, 256)), 4)),
])
def test_kernel_name_reaches_the_lowered_text(name, call):
    """Interpret mode inlines the kernel body; the ``name=`` scope still
    wraps it, so the name is in the lowered module's locations whatever
    the backend (on the chip it is the Mosaic call's ``kernel_name`` and
    the device event's name)."""
    if call is None:
        n = 256 * 128 * 2              # two whole candidate segments
        z = jnp.zeros((n,), jnp.float32)
        bits = jnp.zeros((kernels.num_sent_words(n),), jnp.int32)
        call = lambda: kernels.fused_compensate_bits_cands(
            z, z, z, bits, 0.9, False, True)
    text = jax.jit(call).lower().as_text(debug_info=True)
    assert f"{name}/" in text or f'"{name}"' in text, name

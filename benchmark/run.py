"""One run of one cell: set up, warm up, measure, check, print.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

A fresh process per run. It refuses a backend that is not a TPU, builds the
cell's arms from the repo's config tree (``benchmark/build.py``), warms up
exactly the programs the window uses, and measures for ``--seconds``
seconds in rounds: ``round_steps`` donated per-dispatch
steps of one arm, dispatched back to back and ended by one
``block_until_ready``, then the same for the other arm, the order of the
arms alternating from round to round. Where the traffic file states
``residency: one`` (a cell whose arms do not fit the chip together), the
arms come one after the other, dgc first: each is built, driven through its
first steps, warmed up and measured for its half of the window, and leaves
the chip before the next is built. Nothing may compile inside the
window, or inside either half; a run in which something does exits
non-zero. Once the window has
closed, the peak has been read and the arms' states are freed, the two
checks run on the device the arms have left: the exchange engine, bare and
as the timed step drives it (the step's own gradient pack, then
``step_flat`` with the optimizer's offer), against its plain reference
(``benchmark/check.py``), and the configuration's plain
reference of the model over the first steps that set-up drove through the
window's own call (``benchmark/model_check.py``). So a cell needs of the
chip what its arms need, and no check needs more than that.

``--trace 1`` builds the steps with the ``dgcph.*`` markers on (their
executables have cache entries of their own), runs the same window, then
profiles ``trace_steps`` steps of each arm in one profiler session and
reports the cell's per-layer metrics instead of its end-to-end ones.

With ``DGC_BENCH_KEEP_TRACE=<dir>`` in the environment the profiler's
directory is copied there before it is reduced (to look at a trace a reader
finds nothing in).

The last line of standard output is the result, one JSON object, whole or
not printed: a run that lacks a metric its cell owes (untraced, an
end-to-end one; traced, a per-layer one, and a per-layer metric without a
``workloads`` list is owed by every cell) exits non-zero and names it
(``refuse_a_short_line``). Earlier
lines (JSON objects with an ``event`` key) carry the set-up split, the
check's numbers, the per-round rows with quartiles, and the phase tables.
"""

import time

_T0 = time.perf_counter()        # process start, give or take the interpreter

import argparse
import gc
import json
import math
import os
import shutil
import statistics
import sys
import tempfile
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells, program_records, rounds
from benchmark.spans import Spans

#: both arms see the same weights and the same first batch, so their
#: step-0 losses differ only by how two XLA programs fuse and order the
#: same float32 sums: up to 3.7e-6 relative on the chip (ResNet-50, four
#: seeds, PR 22). Computing in bfloat16 would show as some 1e-3.
STEP0_LOSS_RTOL = 1e-4
#: dispatches each arm runs alone, after its first (compiling) call, before
#: its memory peak is read; the model check follows the first
#: ``model_check.FOLLOWED`` of them all, and a follower keeps nothing of a
#: later one
SOLO_WARMUP_STEPS = 2
KEEP_TRACE_ENV = "DGC_BENCH_KEEP_TRACE"


def log(event, **fields):
    print(json.dumps({"event": event, **fields}), flush=True)


class CompileCounter:
    """Programs JAX built: compiled, or fetched from its persistent cache
    (the duration event fires for both)."""

    def __init__(self):
        import jax.monitoring
        self.programs = self.from_cache = 0
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.from_cache += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1

    def snapshot(self):
        return {"programs": self.programs, "from_cache": self.from_cache}


class ArmRun:
    """One arm while it runs: the built arm, its state, its losses."""

    def __init__(self, arm, state, seed):
        import jax
        self.arm, self.name, self.state = arm, arm.name, state
        self.key = jax.random.PRNGKey(seed)
        self.steps = 0
        self.losses = []
        # what the state is, for a lowering after the state has gone
        self.state_shapes = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype,
                                           sharding=x.sharding), state)

    def lowered(self, images, labels):
        """The program ``dispatch`` calls, lowered for the arguments it is
        called with: JAX keeps a call's trace and lowering, so this
        traces nothing again."""
        program = (self.arm.k_loop if self.arm.k_loop is not None
                   else self.arm.step)
        return program.lower(self.state_shapes, images, labels, self.key)

    def dispatch(self, images, labels):
        import jax
        key = jax.random.fold_in(self.key, self.steps)
        if self.arm.k_loop is not None:
            self.state, losses = self.arm.k_loop(self.state, images, labels,
                                                 key)
        else:
            self.state, metrics = self.arm.step(self.state, images, labels,
                                                key)
            losses = metrics["loss"]
        self.steps += losses.size
        self.losses.append(losses)
        return losses


def run_round(run, feed, spans, dispatches):
    """``dispatches`` back-to-back dispatches of one arm and one wait;
    returns the round's wall seconds and the steps it ran."""
    import jax
    before = run.steps
    t0 = time.perf_counter()
    for _ in range(dispatches):
        with spans.span(run.name, "input.next"):
            images, labels = next(feed)
        with spans.span(run.name, "dispatch"):
            run.dispatch(images, labels)
    with spans.span(run.name, "wait"):
        jax.block_until_ready(run.state)
    return time.perf_counter() - t0, run.steps - before


def hbm_peak_bytes(devices):
    """Peak device memory on the fullest of ``devices``, as the runtime
    reports it: the peak of live arrays plus the peak it reserved for
    programs' scratch (their activations), which ``peak_bytes_in_use``
    alone leaves out (5.3 GB of 7.2 at ResNet-50, PERF.md)."""
    def peak(d):
        stats = d.memory_stats() or {}
        return (stats.get("peak_bytes_in_use", 0)
                + stats.get("peak_bytes_reserved", 0))
    return max((peak(d) for d in devices), default=0)


def engine_info(arm):
    """What the readers may know of the dgc arm's engine: its sizes."""
    setup, memory = arm.setup, arm.dist.compressor.memory
    item = int(setup.layout.dtype.itemsize)
    state_dtype = getattr(memory, "dtype", None)
    return {"T": int(getattr(setup.engine, "T", 0)),
            "total": int(setup.layout.total),
            "payload_size": int(getattr(setup.engine, "payload_size", 0)),
            "grad_itemsize": item,
            "state_itemsize": (int(state_dtype.itemsize)
                               if state_dtype is not None else item)}


def measure(cell, seed, seconds, trace, devices=None, client_s=0.0):
    """Everything between "backend up" and "result": returns the result's
    parts as a dict. ``devices`` is for the rehearsals (virtual CPU
    devices); on the chip it stays None. Every program of the cell is
    traced and called at the matmul precision its configuration states.
    ``client_s``: the seconds the runtime took to create its TPU client,
    which are the machine's and not set-up's (``run_cell``)."""
    from benchmark import build
    with build.matmul_precision(cell):
        return _measure(cell, seed, seconds, trace, devices, client_s)


def _measure(cell, seed, seconds, trace, devices, client_s):
    import jax
    import numpy as np

    from benchmark import build, inputs
    from benchmark.check import exchange_check, mosaic_kernels
    from benchmark import model_check

    split = {}
    mark = time.perf_counter()

    def lap(name=None):
        """The seconds since the last lap go to ``name`` of the set-up's
        split (to nothing without a name: a window's own); returns now."""
        nonlocal mark
        now = time.perf_counter()
        if name is not None:
            split[name] = split.get(name, 0.0) + now - mark
        mark = now
        return now

    counter = CompileCounter()
    traffic = cell.traffic
    if trace:
        # before any step is traced: the markers bake in at trace time,
        # and this also keys the compile cache on op metadata (PR 21)
        from dgc_tpu.telemetry import trace as dgc_trace
        dgc_trace.enable(True)
    mesh = build.make_mesh(cell, devices)
    cell_devices = list(mesh.devices.flat)
    spans = Spans()
    scan = traffic["loop"] == "scan"
    names = traffic["arms"]
    # the arms that are on the chip together and share a window, round by
    # round: all of them, or (``residency: one``) one after the other,
    # each with its share of the window and gone before the next is built
    groups = ([names] if traffic["residency"] == "both"
              else [[name] for name in names])
    dispatches = traffic["round_steps"]
    runs, feed, first_batch = {}, None, None
    first_loss, dgc_peak, engine = {}, None, None
    check = {"ok": True, "skipped": "no dgc arm in this traffic"}
    followers = {}
    rows, steps_per_round, losses, window_spans, traced = [], None, {}, {}, []
    setup_s = window_s = 0.0
    # where the set-up now running began: process start, less the
    # runtime's own client creation
    since = _T0 + client_s
    lap("backend_and_mesh")

    try:
        for group in groups:
            for name in group:
                arm = build.build_arm(cell, name, mesh)
                lap("build_" + name)
                if feed is None:
                    gb = arm.world * traffic["per_chip_batch"]
                    if traffic["input"] == "pipeline":
                        feed = inputs.pipeline_feed(
                            seed, gb, traffic["pool_batches"], arm.dataset,
                            mesh)
                    else:
                        n = traffic["pool_batches" if scan
                                    else "round_steps"]
                        resident = inputs.resident_batches(
                            seed, gb, n, arm.dataset, traffic, mesh, spans)
                        feed = (inputs.scan_feed(resident, mesh) if scan
                                else inputs.resident_feed(resident))
                    first_batch = next(feed)
                    jax.block_until_ready(first_batch)
                    lap("data")
                run = runs[name] = ArmRun(arm, build.init_state(arm, seed),
                                          seed)
                jax.block_until_ready(run.state)
                lap("init_" + name)
                # where the configuration has a reference of its model:
                # what its follow reads of the state round each of these
                # dispatches, in files
                follow = followers[name] = model_check.Follower(cell, arm)
                follow.snapshot(run)
                # the first call compiles (or loads) the one program this
                # arm uses; same weights, same batch, same key for every arm
                loss = run.dispatch(*first_batch)
                first_loss[name] = float(np.ravel(jax.device_get(loss))[0])
                follow.snapshot(run)
                lap("first_step_" + name)
                for _ in range(SOLO_WARMUP_STEPS):
                    run.dispatch(*first_batch)
                    follow.snapshot(run)
                jax.block_until_ready(run.state)
                lap("warmup")
                if name == "dgc":
                    # the DGC job's own peak: before another arm's state
                    # exists on the device
                    dgc_peak = hbm_peak_bytes(cell_devices)
                    engine = engine_info(arm)
            # one whole interleaved round, discarded (bench.py: the first
            # round after compile runs slow); it also fills the pipeline
            for name in group:
                run_round(runs[name], feed, spans, dispatches)
            lap("warmup")

            # ---- the window, or this group's share of it ---------------- #
            for name in group:
                runs[name].losses.clear()
            span_mark = spans.mark()
            before = counter.snapshot()
            share = seconds * len(group) / len(names)
            first_row = len(rows)
            gc.collect()
            gc.disable()
            # the collector's pass is set-up's, and has a lap like the
            # rest of it: the laps add up to ``setup_s``
            t_start = lap("collect")
            setup_s += t_start - since
            while True:
                row = {}
                for name in rounds.arm_order(group, len(rows) - first_row):
                    row[name], steps_per_round = run_round(
                        runs[name], feed, spans, dispatches)
                rows.append(row)
                if (time.perf_counter() - t_start >= share
                        and (len(rows) - first_row) % len(group) == 0):
                    break
            window_s += time.perf_counter() - t_start
            gc.enable()
            after = counter.snapshot()
            if after != before:
                raise SystemExit(
                    f"benchmark: something compiled inside the measured "
                    f"window ({before} -> {after}); the run is void")
            for name in group:
                losses[name] = np.concatenate([
                    np.ravel(x) for x in jax.device_get(runs[name].losses)])
            for name, by_span in spans.seconds(span_mark).items():
                for key, secs in by_span.items():
                    window_spans.setdefault(name, {}).setdefault(
                        key, []).extend(secs)
            if trace:
                traced.append(_profile(
                    cell, {name: runs[name] for name in group}, feed, spans,
                    steps_per_round // dispatches))
            since = lap()
            if group is not groups[-1]:
                # the chip is the next group's: nothing of this one stays
                # but its programs and the cell's resident batches
                for name in group:
                    runs[name].state = None
                    runs[name].losses.clear()
                gc.collect()

        attempted = int(sum(len(v) for v in losses.values()))
        failed = int(sum(int(np.sum(~np.isfinite(v)))
                         for v in losses.values()))

        # the program's peak, then its state freed, then the two checks
        # on the device the arms have left: neither is in the peak or in
        # setup_s
        memory_peak = int(hbm_peak_bytes(cell_devices))
        for run in runs.values():
            run.state = None
        if "dgc" in runs:
            # the Mosaic kernels of the step the window timed, read off
            # the lowering its first call left: the check has to lower
            # every one of them (``check.uncovered_kernels``)
            t0 = time.perf_counter()
            timed = mosaic_kernels(runs["dgc"].lowered(*first_batch))
            kernels_s = time.perf_counter() - t0
            check = exchange_check(runs["dgc"].arm, seed, timed)
            check["check_s"] = time.perf_counter() - t0
            if "parts_s" in check:
                check["parts_s"]["timed_kernels"] = kernels_s
        losses_ref = list(first_loss.values())
        step0_gap = max(abs(v - losses_ref[0])
                        / (abs(losses_ref[0]) or 1.0) for v in losses_ref)
        step0_ok = bool(step0_gap <= STEP0_LOSS_RTOL)
        log("check", exchange=check, step0_loss=first_loss,
            step0_loss_rtol=STEP0_LOSS_RTOL, step0_ok=step0_ok)
        t0 = time.perf_counter()
        model = model_check.compare(cell, followers, first_batch)
        model["check_s"] = time.perf_counter() - t0
        model["followers_bytes"] = sum(follow.written_bytes()
                                       for follow in followers.values())
        log("model_check", **model)
    finally:
        if feed is not None:
            feed.close()
        for follow in followers.values():
            follow.close()

    return {
        "residency": traffic["residency"],
        "memory_peak_bytes": memory_peak,
        "setup_s": setup_s, "split": split, "compiles": counter.snapshot(),
        "rows": rows, "steps_per_round": steps_per_round,
        "window_s": window_s, "attempted": attempted, "failed": failed,
        "check": check, "model_check": model, "step0_ok": step0_ok,
        "step0_gap": step0_gap,
        "dgc_peak_bytes": dgc_peak,
        "window_spans": window_spans, "traced": traced, "engine": engine,
        "setup_spans": spans.seconds().get("setup", {}),
    }


def _profile(cell, runs, feed, spans, steps_per_dispatch):
    """``trace_steps`` steps of each arm in one profiler session; returns
    the loaded events and the steps each arm ran in it."""
    import jax

    from benchmark import trace_reduce

    dispatches = max(1, cell.traffic["trace_steps"] // steps_per_dispatch)
    logdir = tempfile.mkdtemp(prefix="dgc_bench_trace_")
    options = jax.profiler.ProfileOptions()
    # with the python tracer off, 16 ResNet-50 steps export 44,899 events
    # (985,190 for 8 steps with it on, PR 21). The harness's annotations
    # are host-tracer events, so that tracer keeps its default level; what
    # it records of host-to-device copies overflows the export where every
    # step stages a batch (1,000,035 events, input 'pipeline', this PR)
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    steps = {}
    try:
        jax.profiler.start_trace(logdir, profiler_options=options)
        spans.annotate = True
        try:
            for name, run in runs.items():
                with jax.profiler.TraceAnnotation(f"bench:{name}:segment"):
                    _, steps[name] = run_round(run, feed, spans, dispatches)
        finally:
            spans.annotate = False
            jax.profiler.stop_trace()
        keep = os.environ.get(KEEP_TRACE_ENV)
        if keep:
            shutil.copytree(logdir, keep, dirs_exist_ok=True)
        events = trace_reduce.load_events(
            trace_reduce.find_trace_file(logdir))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)
    return {"events": events, "steps": steps}


# ---------------------------------------------------------------------- #
# from a measurement to the result line                                  #
# ---------------------------------------------------------------------- #

def paired_summary(m):
    """Medians and quartiles of the window, per arm and paired. Arms that
    ran one after the other (``residency: one``) have no round in common:
    their difference is that of their medians, and has no quartiles."""
    rows, steps = m["rows"], m["steps_per_round"]
    rounds_of = {}                            # each arm's own rounds
    for row in rows:
        for name in row:
            rounds_of.setdefault(name, []).append(row)
    out = {"rounds": len(rows), "steps_per_round": steps,
           "window_s": m["window_s"], "arms": {}}
    for name, own in rounds_of.items():
        q = rounds.quartiles(rounds.per_step_ms(own, name, steps))
        out["arms"][name] = {"q1": q[0], "median": q[1], "q3": q[2]}
    if "dgc" in rounds_of and "dense" in rounds_of:
        if m["residency"] == "both":
            q = rounds.quartiles(rounds.paired_diff_ms(rows, "dgc", "dense",
                                                       steps))
            out["dgc_minus_dense_ms"] = {"q1": q[0], "median": q[1],
                                         "q3": q[2]}
        else:
            out["dgc_minus_dense_ms"] = {"median": rounds.median_diff_ms(
                rounds_of["dgc"], "dgc", rounds_of["dense"], "dense", steps)}
    return out


def end_to_end_values(m, paired):
    values = {"setup_s": m["setup_s"]}
    if "dgc" in paired["arms"]:
        values["step_ms"] = paired["arms"]["dgc"]["median"]
    if "dense" in paired["arms"]:
        values["dense_step_ms"] = paired["arms"]["dense"]["median"]
    if "dgc_minus_dense_ms" in paired:
        values["dgc_overhead_ms"] = paired["dgc_minus_dense_ms"]["median"]
    if m["dgc_peak_bytes"]:
        values["peak_hbm_gib"] = m["dgc_peak_bytes"] / 2 ** 30
    return values


def host_view(m, paired):
    """What the per-layer readers get as ``trace`` of a run whose trace
    has no device lane (all a rehearsal on the CPU has): the steps each
    arm ran in a profiler session, the window's pairing, the engine's
    sizes and the harness's set-up spans."""
    steps = {}
    for session in m["traced"]:
        steps.update(session["steps"])
    return {"arms": {}, "tables": {}, "peaks": {}, "steps": steps,
            "paired": paired, "engine": m["engine"],
            "setup_spans": m["setup_spans"]}


def trace_view(m, paired, device_kind):
    """What the per-layer readers get as ``trace``."""
    from benchmark import trace_reduce

    # a profiler session for the arms that were on the chip together
    arms = {}
    for session in m["traced"]:
        arms.update(trace_reduce.split_arms(session["events"],
                                            session["steps"]))
    return {
        **host_view(m, paired),
        "arms": arms,
        "tables": {name: trace_reduce.phase_table(a)
                   for name, a in arms.items()},
        "peaks": cells.load_peaks(device_kind),
    }


def per_layer_values(cell, view, spans_seconds):
    """What each reader of the cell's per-layer metrics returns, but for
    None: ``refuse_a_short_line`` names those."""
    values = {}
    for entry in cell.per_layer:
        value = cells.load_reader(entry["name"])(view, spans_seconds, cell)
        if value is not None:
            values[entry["name"]] = float(value)
    return values


#: what the driver reads of ``device`` in every line
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")


def refuse_a_short_line(cell, values, device, traced):
    """The line is whole or it is not printed: every metric the cell owes
    (traced, its per-layer metrics: one without a ``workloads`` list is
    owed by EVERY cell; untraced, its end-to-end ones) has a finite value,
    and ``device`` says what the driver reads of it. Otherwise
    ``SystemExit`` that names the workload and all that is missing, so the
    first to see a short line is the one who made it."""
    owed = cell.per_layer if traced else cell.end_to_end
    missing = [e["name"] for e in owed
               if not math.isfinite(values.get(e["name"], math.nan))]
    faults = []
    if missing:
        faults.append(f"did not produce {missing}")
    lacking = [k for k in DEVICE_KEYS if device.get(k) is None]
    if lacking:
        faults.append(f"its device lacks {lacking}")
    if traced and not 0 < device.get("busy_s", 0) <= device.get(
            "window_s", 0):
        faults.append(f"its trace reads busy_s {device.get('busy_s')!r} of "
                      f"window_s {device.get('window_s')!r}, not 0 < "
                      "busy_s <= window_s")
    if faults:
        kind = "traced" if traced else "untraced"
        raise SystemExit(f"benchmark: workload '{cell.name}', {kind} run, "
                         + "; ".join(faults))


def breakdown(view):
    """At most ten rows each: device seconds by arm and ``dgcph`` phase
    over the traced window, and idle seconds by what the host was doing."""
    from benchmark import trace_reduce
    ops, idle = {}, {}
    for name, arm in view["arms"].items():
        table = view["tables"][name]
        scale = table["steps"] / 1e3            # ms/step -> s in the window
        for phase, ms in table["phases"].items():
            label = phase if phase == "unattributed" else "dgcph." + phase
            ops[f"{name}:{label}"] = ms * scale
        for label, secs in trace_reduce.label_gaps(arm).items():
            idle[f"{name}:{label}"] = secs

    def top(d):
        return [[k, v] for k, v in sorted(d.items(),
                                          key=lambda kv: -kv[1])[:10]]

    return {"device_ops": top(ops), "idle_gaps": top(idle)}


def device_busy(view):
    """(busy_s, window_s): both summed over the arms' windows (first
    device op to last), averaged over the chips."""
    arms = view["arms"].values()
    return (sum(statistics.mean(c.busy_s for c in a.chips) for a in arms),
            sum(statistics.mean(c.window_s for c in a.chips) for a in arms))


def is_correct(m) -> bool:
    """The run's verdict: the exchange and the timed step agree with
    their references, the arms start from the same loss, and every loss
    of the window is finite."""
    return bool(m["check"]["ok"] and m["model_check"]["ok"]
                and m["step0_ok"] and m["failed"] == 0)


def nearness(name: str, number: float, limit: float) -> float:
    """How near ``number`` is to ``limit``, over 1 where it is outside: a
    floor (a name that ends in ``_floor``) is met from above, a count's
    limit is 0, every other limit is met from below. A number that is no
    number is outside every limit."""
    if math.isnan(number):
        return math.inf
    if name.endswith("_floor"):
        return limit / number if number > 0 else math.inf
    if limit == 0:
        return math.inf if number > 0 else 0.0
    return number / limit


def compared(m) -> Dict[str, List[float]]:
    """Every number ``is_correct`` rests on, beside its limit: [number,
    limit], the number nearest its limit first (``nearness``), so one
    outside its limit before all others: a record that keeps only the
    first or the last so many of them keeps the one that decided."""
    out = {"step0_loss_gap": [m["step0_gap"], STEP0_LOSS_RTOL],
           "nonfinite_losses": [m["failed"], 0]}
    check = m["check"]
    if "skipped" not in check:
        from benchmark.check import NEW_COUNTS
        # the timed form's counts first: among counts outside their limit
        # the order is this one, and a fault of the pack or of the offered
        # step leaves the bare exchange's numbers whole
        out["pack.misplaced_coords"] = [check["misplaced_coords"], 0]
        out["check.uncovered_kernels"] = [len(check["uncovered_kernels"]),
                                          0]
        for key in NEW_COUNTS[1:] + (
                "inexact_residual_coords", "unconserved_coords",
                "over_quota_rows", "sent_outside_rows"):
            out["exchange." + key] = [check[key], 0]
        out["exchange.fill_floor"] = [check["fill"], check["fill_floor"]]
        out["exchange.recall_floor"] = [check["recall"],
                                        check["recall_floor"]]
        # the bucket nearest its own floor
        out["exchange.bucket_recall_floor"] = list(min(
            zip(check["recall_per_bucket"],
                check["recall_floor_per_bucket"]),
            key=lambda pair: pair[0] - pair[1]))
    model = m["model_check"]
    for arm, numbers in (model.get("arms") or {}).items():
        for key, limit in model["limits"].items():
            if key in numbers:
                out[f"{arm}.{key}"] = [numbers[key]["max"], limit]
    return dict(sorted(out.items(),
                       key=lambda item: -nearness(item[0], *item[1])))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    try:
        cell = cells.load_cell(args.workload)
    except cells.CellError as e:
        raise SystemExit(f"benchmark: {e}")
    run_cell(cell, args.seed, args.seconds, bool(args.trace))


def run_cell(cell, seed, seconds, trace):
    """One run of ``cell`` on the chip, to its result line."""
    try:
        import jax

        from dgc_tpu.utils import compile_cache
        from dgc_tpu.utils.device import require_tpu
    except ImportError as e:
        raise SystemExit(f"benchmark: the system under test is not in this "
                         f"checkout ({e})")
    cache_dir = compile_cache.enable()
    # every program, however quick to compile, comes from the cache in a
    # warm run (JAX's default keeps only those that took over a second)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the runtime creates its TPU client here (libtpu's start, each chip's
    # two 4 GiB windows, a 4 GiB pinned staging buffer): 6.1 s in a
    # machine's first process, 10.6 in its thirteenth, 15-17 on four chips
    # (PERF.md 6, PR 38). They hold no work of the program or the harness,
    # and no PR can move work into them: logged (``client_s``) and left
    # out of ``setup_s``
    t_client = time.perf_counter()
    require_tpu("benchmark/run.py")
    client_s = time.perf_counter() - t_client
    log("start", workload=cell.name, seed=seed, seconds=seconds,
        trace=int(trace), chips=cell.chips, config=cell.config_name,
        traffic=cell.traffic_name, cache_dir=cache_dir,
        cache_entries=compile_cache.entries(cache_dir),
        import_s=time.perf_counter() - _T0, client_s=client_s)

    m = measure(cell, seed, seconds, trace, client_s=client_s)
    paired = paired_summary(m)
    log("setup", setup_s=m["setup_s"], split=m["split"],
        compiles=m["compiles"],
        cache_entries=compile_cache.entries(cache_dir),
        # whose spans ``input.produce_ms`` reads (a traced run's metric:
        # the program records only then)
        input_produce_source=program_records.batch_seconds(
            m["setup_spans"])[0] if trace else None)
    log("memory", memory_peak_bytes=m["memory_peak_bytes"],
        dgc_peak_bytes=m["dgc_peak_bytes"],
        runtime_stats=jax.devices()[0].memory_stats())
    log("rounds", **paired, rows=m["rows"])
    log("spans", window={arm: {k: {"n": len(v), "median_ms":
                                   statistics.median(v) * 1e3,
                                   "sum_s": sum(v)}
                               for k, v in by.items()}
                         for arm, by in m["window_spans"].items()})

    d0 = jax.devices()[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": jax.device_count(),
              "memory_peak_bytes": m["memory_peak_bytes"]}
    result = {"correct": is_correct(m), "attempted": m["attempted"],
              "failed": m["failed"]}
    if trace:
        view = trace_view(m, paired, device["kind"])
        values = per_layer_values(cell, view, m["window_spans"])
        log("phase_tables", **view["tables"])
        device["busy_s"], device["window_s"] = device_busy(view)
        result["breakdown"] = breakdown(view)
    else:
        values = end_to_end_values(m, paired)
    refuse_a_short_line(cell, values, device, trace)
    result["metrics"] = {
        e["name"]: {"value": values[e["name"]], "unit": e["unit"]}
        for e in (cell.per_layer if trace else cell.end_to_end)}
    result["device"] = device
    # what was compared, each number beside its limit: last in the line,
    # and the last lines of standard error
    result["compared"] = compared(m)
    for name, (number, limit) in result["compared"].items():
        print(f"compared {name} {number!r} limit {limit!r}", file=sys.stderr)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()

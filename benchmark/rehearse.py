"""The three rehearsals that cost no chip time (guide: on-chip-measurement).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [cpu] [mesh] [aot] \
        [<workload> ...]

Workload names restrict ``aot`` to those cells (default: every cell).

``cpu``   tiny shapes on one CPU device through the harness's own
          ``measure`` (an image, a token and a routed token
          configuration; pipeline, resident and scan traffic, untraced
          and traced): wrong paths,
          arguments and control flow show here.
``mesh``  the same on a mesh of four virtual CPU devices: wrong meshes and
          sharding rules show here.
``aot``   both arms of every cell of BENCHMARK.json, and the exchange
          check's programs, compiled at the real size for a described
          ``v5e`` (one chip, or the 2x2 host), with ``memory_analysis()``
          held against the chip's 16 GB by ``memory_law``: what the
          TPU's compiler would refuse shows here, and a Mosaic kernel of
          the timed step that the check's programs do not lower.

No rehearsal prints a time, a rate or any other device metric: nothing
here ran on the device.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import cells

FIXTURE = os.path.join(ROOT, "benchmark", "tests", "fixtures", "rehearsal")
HBM_BYTES = 16e9
#: the host beside one chip and beside the 2x2, and what a process of the
#: harness holds there when the reference's follow begins (17.7e9 at a
#: 504M-parameter token model on one v5e, PR 32; 14.2e9 of it is the TPU
#: runtime's from the moment the client exists: two 4 GiB windows of the
#: chip, a 4 GiB staging buffer, its code; PERF.md section 7.6)
HOST_BYTES = {1: 40 * 2 ** 30, 4: 140 * 2 ** 30}
HOST_BASELINE_BYTES = 17.7e9


def host_follow_bytes(chips: int) -> int:
    """What the follow holds of the host for each byte of the parameters:
    two snapshots' momentum and velocity as each worker's engine gives
    them, the reference's gradient, the parameters on their way to the
    device, dgc_sgd's buffer (read on one chip: 13.7e9 at 2.02e9, PR 32;
    the workers' share is arithmetic, not read)."""
    return 4 * chips + 3


#: free under the chip machine's temporary directory (my probe, PR 32)
TMP_BYTES = 80e9


def fixture_cell(name):
    bench = cells.load_benchmark(os.path.join(FIXTURE, "BENCHMARK.json"))
    return cells.load_cell(name, bench=bench,
                           traffic_dir=os.path.join(FIXTURE, "traffic"))


#: where a reader's number comes from when no op ran on a device: the
#: per-layer metrics of these sources read on the CPU too
HOST_SOURCES = ("program_span", "program_counter", "host_clock")


def _run_tiny(name, trace):
    """One tiny cell through ``measure``; checks what a run must satisfy
    and returns counts only."""
    from dgc_tpu.telemetry import trace as program_trace

    # a run is a process of its own: its recorder starts empty, and the
    # next run's steps carry no marker of this one's
    program_trace.enable(False)
    try:
        return _run_tiny_fresh(name, trace)
    finally:
        program_trace.enable(False)


def _run_tiny_fresh(name, trace):
    import jax

    from benchmark import program_records, run, trace_reduce

    cell = fixture_cell(name)
    m = run.measure(cell, seed=3, seconds=0.5, trace=trace,
                    devices=jax.devices("cpu"))
    paired = run.paired_summary(m)
    assert m["failed"] == 0 and m["attempted"] > 0, m["attempted"]
    assert m["check"]["ok"], m["check"]
    assert m["model_check"]["ok"], m["model_check"]
    assert m["step0_ok"]
    one = cell.traffic["residency"] == "one"
    order = [sorted(row) for row in m["rows"]]
    if one:
        # each arm has its own rounds, all of dgc's before the first of
        # dense's
        n_dgc = order.count(["dgc"])
        assert 0 < n_dgc < len(order)
        assert order == [["dgc"]] * n_dgc + [["dense"]] * (len(order) - n_dgc)
    else:
        assert len(order) % 2 == 0 and order == [["dense", "dgc"]] * len(order)
    assert set(run.end_to_end_values(m, paired)) >= {
        "setup_s", "step_ms", "dense_step_ms", "dgc_overhead_ms"}
    out = {"cell": name, "trace": trace, "rounds": len(m["rows"]),
           "steps": m["attempted"], "check": m["check"],
           "model_check": m["model_check"], "compiles": m["compiles"]}
    if trace:
        # a profiler session for the arms that share the chip
        assert len(m["traced"]) == (2 if one else 1)
        events = [ev for session in m["traced"] for ev in session["events"]]
        names = [n for n, _, _ in trace_reduce.host_annotations(events)]
        for arm in ("dgc", "dense"):
            assert f"{arm}:segment" in names, names
            assert f"{arm}:dispatch" in names, names
        for session in m["traced"]:
            try:
                trace_reduce.split_arms(session["events"], session["steps"])
            except trace_reduce.TraceError as e:
                out["no_device_ops"] = str(e)[:60]  # the CPU has no lane
            else:
                raise AssertionError("a CPU trace yielded device ops")
        # the repo's readers on this cell: each that needs no device lane
        # returns a number (the rest have nothing to read on the CPU)
        values = run.per_layer_values(cell, run.host_view(m, paired),
                                      m["window_spans"])
        assert sorted(values) == sorted(
            e["name"] for e in cell.per_layer
            if e["source"] in HOST_SOURCES), sorted(values)
        out["per_layer_read"] = sorted(values)
        # the check drives ``step_flat`` after the window, under no
        # ``step.trace``: its ``exchange.trace`` span is on the record and
        # is not among the step's, an arm each
        step_spans = program_records.span_seconds("exchange.trace",
                                                  under="step.trace")
        assert len(step_spans) == 2 < len(
            program_records.span_seconds("exchange.trace"))
        assert values["exchange.trace_s"] == sum(step_spans)
        out["input_produce_source"] = program_records.batch_seconds(
            m["setup_spans"])[0]
        out["annotations"] = len(names)
        out["trace_events"] = len(events)
    return out


def rehearse_cpu():
    for name in ("tiny.steady", "tiny.resident", "tiny.scan",
                 "tiny_lm.resident", "tiny_lm.scan", "tiny_lm.one",
                 "tiny_moe.resident", "tiny_moe.one"):
        print(json.dumps(_run_tiny(name, trace=False)), flush=True)
    for name in ("tiny.steady", "tiny_lm.resident", "tiny_lm.one",
                 "tiny_moe.resident"):
        print(json.dumps(_run_tiny(name, trace=True)), flush=True)


def rehearse_mesh():
    for name in ("tiny.steady.x4", "tiny_lm.resident.x4", "tiny_lm.one.x4"):
        print(json.dumps(_run_tiny(name, trace=False)), flush=True)


def memory_law(row):
    """What a cell needs, from the compiler's numbers in ``row`` (bytes a
    chip), and whether it may run. Of one chip, by the ``residency`` its
    traffic file states: ``both``, the arms' states together and the
    larger step's temporaries (both states are resident, the steps run
    one at a time); ``one``, the larger arm's state and temporaries (the
    arms come one after the other). The key is no choice: ``one`` is
    refused where ``both`` fits. The exchange check runs on a device the
    arms have left, so it is held to the chip on its own. Where the
    configuration has a reference of its model, what the followers write
    (``model_check.Follower.kept_bytes``) is held to the temporary
    directory's disk, and the reference's follow, which reads it back a
    piece at a time, to the host's memory: ``host_follow_bytes`` for each
    byte of the parameters on top of the process's baseline. Returns the
    row's ``needs_bytes``, ``host_bytes``, ``disk_bytes``, ``fits`` and
    ``law``, the sentence that says so."""
    arms = [n for n in cells.ARMS if n in row]
    states = [row[n]["argument_bytes"] for n in arms]
    temps = [row[n]["temp_bytes"] for n in arms]
    both = sum(states) + max(temps)
    one = max(s + t for s, t in zip(states, temps))
    residency = row.get("residency", "both")
    needs = both if residency == "both" else one
    check = row.get("check_bytes", 0)
    disk = sum(row[n].get("follower_bytes", 0) for n in arms)
    host = int(HOST_BASELINE_BYTES + (
        host_follow_bytes(row["chips"]) * row["param_bytes"] if disk else 0))
    law = (
        f"{row['cell']}: residency '{residency}' needs {needs} B a chip "
        f"(states {' + '.join(map(str, states))}, step temporaries "
        f"{' | '.join(map(str, temps))}: together {both}, one arm at a time "
        f"{one}) and the exchange check {check} B on its own, of "
        f"{int(HBM_BYTES)}; the followers write {disk} B under the "
        f"temporary directory, of {int(TMP_BYTES)}, and the host holds at "
        f"least {host} B ({int(HOST_BASELINE_BYTES)} + the reference's "
        f"follow), of {HOST_BYTES[row['chips']]}")
    fits = bool(needs < HBM_BYTES and check < HBM_BYTES
                and disk < TMP_BYTES and host < HOST_BYTES[row["chips"]])
    if row.get("uncovered_kernels"):
        fits = False
        law += (f"; the timed step lowers {row['uncovered_kernels']}, "
                "which no program of the exchange check lowers")
    if residency == "one" and both < HBM_BYTES:
        fits = False
        law += ("; 'one' is stated where both arms fit the chip together: "
                "the key is for a cell the law forces it on")
    return {"needs_bytes": needs, "host_bytes": host, "disk_bytes": disk,
            "fits": fits, "law": law}


def aot_row(cell, topo):
    """Both arms of ``cell`` and its exchange check's programs compiled
    for the described chips of ``topo``: the compiler's bytes, what the
    followers would write, and ``memory_law``'s verdict."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import build, check, inputs, model_check

    mesh = build.make_mesh(cell, devices=topo.devices)
    gb = cell.chips * cell.traffic["per_chip_batch"]
    batch = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    row = {"cell": cell.name, "chips": cell.chips,
           "residency": cell.traffic["residency"]}
    for name in cell.traffic["arms"]:
        arm = build.build_arm(cell, name, mesh)
        key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                   sharding=NamedSharding(mesh, P()))
        arm.init.lower(key).compile()
        state = jax.eval_shape(arm.init, jax.random.PRNGKey(0))
        examples, labels = inputs.example_shapes(arm.dataset, gb)
        with build.matmul_precision(cell):
            lowered = arm.step.lower(
                state,
                jax.ShapeDtypeStruct(*examples, sharding=batch),
                jax.ShapeDtypeStruct(*labels, sharding=batch),
                key)
            compiled = lowered.compile()
        program = check.check_program(arm) if name == "dgc" else None
        if program is not None:
            stages = check.stage_bytes(program)
            row["check_stage_bytes"] = stages
            row["check_bytes"] = max(stages.values())
            row["check_bytes_per_T"] = (row["check_bytes"]
                                        / arm.setup.engine.T)
            # every Mosaic kernel the timed step lowers, the check lowers
            # (the model's own views of its parameters apart)
            checked = frozenset().union(*(
                check.mosaic_kernels(stage.fn.lower(*stage.args))
                for stage in program.stages()))
            row["check_kernels"] = sorted(checked)
            row["uncovered_kernels"] = sorted(
                check.mosaic_kernels(lowered) - check.MODEL_VIEW_KERNELS
                - checked)
        mem = compiled.memory_analysis()
        hlo = compiled.as_text()
        row["param_bytes"] = (cell.config["sizes"]["num_parameters"]
                              * state.params.dtype.itemsize)
        row[name] = {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            # a snapshot before the first dispatch and after each
            # followed one
            "follower_bytes": model_check.Follower(cell, arm).kept_bytes(
                state),
            # donated state: the outputs alias the arguments
            "program_bytes_per_chip": check.program_bytes(compiled),
            "mosaic_calls": hlo.count("tpu_custom_call"),
            "collectives": sorted({
                op for op in ("all-reduce", "all-gather", "all-to-all",
                              "collective-permute", "reduce-scatter")
                if f" {op}(" in hlo or f" {op}-start(" in hlo}),
        }
    row.update(memory_law(row))
    return row


def describe_v5e():
    """The described 2x2 of v5e chips, with the process set up to compile
    for it: no device is attached."""
    import jax
    from jax.experimental import topologies

    from dgc_tpu.ops import kernels

    # the engine asks the default backend (the CPU, here) which route to
    # take; the program compiled for the chip takes the Pallas route
    kernels.use_pallas = lambda: True
    # a compile for a described chip is written to the cache but cannot be
    # read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


def rehearse_aot(only=()):
    topo = describe_v5e()
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        if only and w["name"] not in only:
            continue
        row = aot_row(cells.load_cell(w["name"], bench=bench), topo)
        print(json.dumps(row), flush=True)
        if not row["fits"]:
            raise SystemExit("refused: " + row["law"])


def main(argv):
    modes = {"cpu": rehearse_cpu, "mesh": rehearse_mesh, "aot": rehearse_aot}
    which = [a for a in argv if a in modes] or list(modes)
    only = [a for a in argv if a not in modes]
    for name in which:
        modes[name](only) if name == "aot" else modes[name]()
    print(json.dumps({"rehearsed": which, "ok": True}))


if __name__ == "__main__":
    main(sys.argv[1:])

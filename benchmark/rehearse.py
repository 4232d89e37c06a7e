"""The three rehearsals that cost no chip time (guide: on-chip-measurement).

    JAX_PLATFORMS=cpu python3 benchmark/rehearse.py [cpu] [mesh] [aot] \
        [<workload> ...]

Workload names restrict ``aot`` to those cells (default: every cell).

``cpu``   tiny shapes on one CPU device through the harness's own
          ``measure`` (an image and a token configuration; pipeline,
          resident and scan traffic, untraced and traced): wrong paths,
          arguments and control flow show here.
``mesh``  the same on a mesh of four virtual CPU devices: wrong meshes and
          sharding rules show here.
``aot``   both arms of every cell of BENCHMARK.json, and the exchange
          check's programs, compiled at the real size for a described
          ``v5e`` (one chip, or the 2x2 host), with ``memory_analysis()``
          held against the chip's 16 GB by ``memory_law``: what the
          TPU's compiler would refuse shows here.

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
#: harness held there before any state was made (14.2e9 at a 461M-parameter
#: token model, one v5e, PR 27; PERF.md section 7.6)
HOST_BYTES = {1: 40 * 2 ** 30, 4: 140 * 2 ** 30}
HOST_BASELINE_BYTES = 14.2e9


def fixture_cell(name):
    bench = cells.load_benchmark(os.path.join(FIXTURE, "BENCHMARK.json"))
    return cells.load_cell(name, bench=bench,
                           traffic_dir=os.path.join(FIXTURE, "traffic"))


def _run_tiny(name, trace):
    """One tiny cell through ``measure``; checks what a run must satisfy
    and returns counts only."""
    import jax

    from benchmark import run, trace_reduce

    cell = fixture_cell(name)
    m = run.measure(cell, seed=3, seconds=0.5, trace=trace,
                    devices=jax.devices("cpu"))
    paired = run.paired_summary(m)
    assert m["failed"] == 0 and m["attempted"] > 0, m["attempted"]
    assert m["check"]["ok"], m["check"]
    assert m["model_check"]["ok"], m["model_check"]
    assert m["step0_ok"]
    assert len(m["rows"]) % 2 == 0 and set(m["rows"][0]) == {"dgc", "dense"}
    assert set(run.end_to_end_values(m, paired)) >= {
        "setup_s", "step_ms", "dense_step_ms", "dgc_overhead_ms"}
    out = {"cell": name, "trace": trace, "rounds": len(m["rows"]),
           "steps": m["attempted"], "check": m["check"],
           "model_check": m["model_check"], "compiles": m["compiles"]}
    if trace:
        events = m["traced"]["events"]
        names = [n for n, _, _ in trace_reduce.host_annotations(events)]
        for arm in ("dgc", "dense"):
            assert f"{arm}:segment" in names, names
            assert f"{arm}:dispatch" in names, names
        try:
            trace_reduce.split_arms(events, m["traced"]["steps"])
        except trace_reduce.TraceError as e:
            out["no_device_ops"] = str(e)[:60]     # the CPU has no device lane
        else:
            raise AssertionError("a CPU trace yielded device ops")
        values = run.per_layer_values(
            cell, {"paired": paired, "tables": {}, "arms": {},
                   "engine": None, "peaks": {}}, m["window_spans"])
        out["per_layer_read"] = sorted(values)
        out["annotations"] = len(names)
        out["trace_events"] = len(events)
    return out


def rehearse_cpu():
    for name in ("tiny.steady", "tiny.resident", "tiny.scan",
                 "tiny_lm.resident", "tiny_lm.scan"):
        print(json.dumps(_run_tiny(name, trace=False)), flush=True)
    print(json.dumps(_run_tiny("tiny.steady", trace=True)), flush=True)


def rehearse_mesh():
    for name in ("tiny.steady.x4", "tiny_lm.resident.x4"):
        print(json.dumps(_run_tiny(name, trace=False)), flush=True)


def memory_law(row):
    """What a cell needs, from the compiler's numbers in ``row`` (bytes a
    chip). Of one chip: the arms' states together and the larger step's
    temporaries (both states are resident, the steps run one at a time);
    the exchange check runs on a device the arms have left, so it is held
    to the chip on its own. Of the host, where the configuration has a
    reference of its model: ``follower_copies`` copies of a chip's states
    (``model_check.Follower``), which stay until the reference has
    followed, on top of the process's baseline; the other chips' shares of
    what is sharded and the follow's own float64 working copies come on
    top and are not counted, so this refuses what cannot fit and admits
    nothing for certain. Returns (chip bytes, host
    bytes, the sentence that says so)."""
    arms = [n for n in cells.ARMS if n in row]
    states = [row[n]["argument_bytes"] for n in arms]
    temp = max(row[n]["temp_bytes"] for n in arms)
    needs = sum(states) + temp
    check = row.get("check_bytes", 0)
    copies = row.get("follower_copies", 0)
    host = int(HOST_BASELINE_BYTES) + copies * sum(states)
    return needs, host, (
        f"{row['cell']}: needs {needs} B a chip (states "
        f"{' + '.join(map(str, states))} together + the larger step's "
        f"temporaries {temp}) and the exchange check {check} B on its own, "
        f"of {int(HBM_BYTES)}; at least {host} B of the host ({copies} "
        f"copies of the states for the model reference + "
        f"{int(HOST_BASELINE_BYTES)}), of {HOST_BYTES[row['chips']]}")


def rehearse_aot(only=()):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import build, check, inputs, run
    from dgc_tpu.ops import kernels

    # the engine asks the default backend (the CPU, here) which route to
    # take; the program compiled for the chip takes the Pallas route
    kernels.use_pallas = lambda: True
    # a compile for a described chip is written to the cache but cannot be
    # read back without one
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    bench = cells.load_benchmark()
    for w in bench["workloads"]:
        if only and w["name"] not in only:
            continue
        cell = cells.load_cell(w["name"], bench=bench)
        mesh = build.make_mesh(cell, devices=topo.devices)
        gb = cell.chips * cell.traffic["per_chip_batch"]
        batch = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        # a snapshot before the first dispatch and after each followed one
        row = {"cell": cell.name, "chips": cell.chips,
               "follower_copies": (2 + run.SOLO_WARMUP_STEPS
                                   if cell.config["reference"] else 0)}
        for name in cell.traffic["arms"]:
            arm = build.build_arm(cell, name, mesh)
            key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                                       sharding=NamedSharding(mesh, P()))
            arm.init.lower(key).compile()
            state = jax.eval_shape(arm.init, jax.random.PRNGKey(0))
            examples, labels = inputs.example_shapes(arm.dataset, gb)
            with build.matmul_precision(cell):
                compiled = arm.step.lower(
                    state,
                    jax.ShapeDtypeStruct(*examples, sharding=batch),
                    jax.ShapeDtypeStruct(*labels, sharding=batch),
                    key).compile()
            program = check.check_program(arm) if name == "dgc" else None
            if program is not None:
                stages = check.stage_bytes(program)
                row["check_stage_bytes"] = stages
                row["check_bytes"] = max(stages.values())
                row["check_bytes_per_T"] = (row["check_bytes"]
                                            / arm.setup.engine.T)
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            row[name] = {
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                # donated state: the outputs alias the arguments
                "program_bytes_per_chip": check.program_bytes(compiled),
                "mosaic_calls": hlo.count("tpu_custom_call"),
                "collectives": sorted({
                    op for op in ("all-reduce", "all-gather", "all-to-all",
                                  "collective-permute", "reduce-scatter")
                    if f" {op}(" in hlo or f" {op}-start(" in hlo}),
            }
        row["needs_bytes"], row["host_bytes"], row["law"] = memory_law(row)
        row["fits"] = bool(row["needs_bytes"] < HBM_BYTES
                           and row.get("check_bytes", 0) < HBM_BYTES
                           and row["host_bytes"] < HOST_BYTES[cell.chips])
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

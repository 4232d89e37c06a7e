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
``aot``   both arms of every cell of BENCHMARK.json compiled at the real
          size for a described ``v5e`` (one chip, or the 2x2 host), with
          ``memory_analysis()`` of both arms added up against the chip's
          16 GB: what the TPU's compiler would refuse shows here.

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


def rehearse_aot(only=()):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchmark import build, check, inputs
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
        row = {"cell": cell.name, "chips": cell.chips}
        together = 0
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
                mem = program[0].lower(key).compile().memory_analysis()
                row["check_temp_bytes"] = mem.temp_size_in_bytes
            mem = compiled.memory_analysis()
            hlo = compiled.as_text()
            # donated state: the outputs alias the arguments
            per_chip = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
                        + mem.output_size_in_bytes
                        - mem.alias_size_in_bytes)
            together += mem.argument_size_in_bytes
            row[name] = {
                "argument_bytes": mem.argument_size_in_bytes,
                "temp_bytes": mem.temp_size_in_bytes,
                "program_bytes_per_chip": per_chip,
                "mosaic_calls": hlo.count("tpu_custom_call"),
                "collectives": sorted({
                    op for op in ("all-reduce", "all-gather", "all-to-all",
                                  "collective-permute", "reduce-scatter")
                    if f" {op}(" in hlo or f" {op}-start(" in hlo}),
            }
            row["largest_program_bytes"] = max(
                row.get("largest_program_bytes", 0), per_chip)
        # both arms' states are resident at once; the steps run one at a
        # time, so the larger program's temporaries count once
        row["both_states_and_largest_temp"] = together + max(
            row[n]["temp_bytes"] for n in cell.traffic["arms"])
        row["fits_16GB"] = row["both_states_and_largest_temp"] < HBM_BYTES
        assert row["fits_16GB"], row
        print(json.dumps(row), flush=True)


def main(argv):
    modes = {"cpu": rehearse_cpu, "mesh": rehearse_mesh, "aot": rehearse_aot}
    which = [a for a in argv if a in modes] or list(modes)
    only = [a for a in argv if a not in modes]
    for name in which:
        modes[name](only) if name == "aot" else modes[name]()
    print(json.dumps({"rehearsed": which, "ok": True}))


if __name__ == "__main__":
    main(sys.argv[1:])

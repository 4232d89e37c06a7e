"""The exchange check alone, at a cell's own geometry, sound and with each
of ``faults.py``'s faults planted: the controls of the numbers PR 44 added,
and the readings their allowance was set from.

    python3 benchmark/tests/chip_controls.py --workload <cell> \
        --seeds 1,2,3 [--faults all|none|<name>,...] [--fault_seeds 1,2] \
        [--out <file>] [--fixture]

On the machine it is started on (through the chip tool, on the chip). No
window: the dgc arm is built, its timed step LOWERED once for the
described batch (no compile, no state), and the check's programs run after
it as they do after a window. One JSON line a run: the fault, the seed,
``ok``, the counts, the largest distance from the plain rule in float32
spacings, ``first`` (the number ``run.compared`` would list first) and the
seconds it took. Exit code 1 where a sound run is not ``ok``, a faulted
one is, or a fault is first seen by another number than its own.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for path in (ROOT, os.path.dirname(os.path.abspath(__file__))):
    if path not in sys.path:
        sys.path.insert(0, path)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="all")
    ap.add_argument("--fault_seeds", help="default: --seeds")
    ap.add_argument("--out")
    ap.add_argument("--fixture", action="store_true",
                    help="a rehearsal fixture's cell, on whatever backend")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    import faults
    from benchmark import build, cells, check, inputs, run
    from dgc_tpu.utils import compile_cache

    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if args.fixture:
        # imported here alone: the module sets the environment of a
        # process that compiles for a DESCRIBED chip
        from benchmark import rehearse
        cell = rehearse.fixture_cell(args.workload)
    else:
        cell = cells.load_cell(args.workload)
    seeds = {True: args.seeds, False: args.fault_seeds or args.seeds}
    by_name = {f.__name__: f for f in faults.FAULTS}
    planted = ([None] if args.faults != "all" else [None, *faults.FAULTS])
    if args.faults not in ("all", "none"):
        planted += [by_name[name] for name in args.faults.split(",")]
    failed, lines = 0, []
    with build.matmul_precision(cell):
        mesh = build.make_mesh(cell)
        arm = build.build_arm(cell, "dgc", mesh)
        # the timed step's kernels, from its lowering for the cell's batch
        batch = NamedSharding(mesh, P(tuple(mesh.axis_names)))
        examples, labels = inputs.example_shapes(
            arm.dataset, cell.chips * cell.traffic["per_chip_batch"])
        t0 = time.perf_counter()
        timed = check.mosaic_kernels(arm.step.lower(
            jax.eval_shape(arm.init, jax.random.PRNGKey(0)),
            jax.ShapeDtypeStruct(*examples, sharding=batch),
            jax.ShapeDtypeStruct(*labels, sharding=batch),
            jax.ShapeDtypeStruct((2,), jnp.uint32,
                                 sharding=NamedSharding(mesh, P()))))
        print(json.dumps({"workload": cell.name, "timed_kernels":
                          sorted(timed),
                          "lower_s": time.perf_counter() - t0}), flush=True)
        for fault in planted:
            want = faults.FAULTS.get(fault)
            for seed in map(int, seeds[fault is None].split(",")):
                patch = faults.Patches()
                t0 = time.perf_counter()
                try:
                    got = check.exchange_check(
                        arm if fault is None else fault(arm, patch), seed,
                        timed)
                finally:
                    patch.undo()
                compared = run.compared({
                    "step0_gap": 0.0, "failed": 0, "check": got,
                    "model_check": {}})
                first = next(iter(compared))
                outside = [name for name, (number, limit)
                           in compared.items()
                           if run.nearness(name, number, limit) > 1]
                sound = fault is None
                good = (got["ok"] and not outside if sound
                        else not got["ok"] and first == want)
                failed += not good
                line = {
                    "fault": None if sound else fault.__name__,
                    "seed": seed, "ok": got["ok"], "as_wanted": good,
                    "first": first, "outside": outside,
                    **{k: got[k] for k in (
                        *check.NEW_COUNTS, "uncovered_kernels",
                        "update_most_ulps", "buffer_most_ulps",
                        "inexact_residual_coords", "unconserved_coords",
                        "fill", "recall", "parts_s",
                        "excused_by_sum_order")},
                    "kernels_checked": got["kernels"]["checked"],
                    "check_s": time.perf_counter() - t0}
                lines.append(line)
                print(json.dumps(line), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as fh:
            for line in lines:
                fh.write(json.dumps(line) + "\n")
    print(json.dumps({"runs": len(lines), "not_as_wanted": failed}))
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

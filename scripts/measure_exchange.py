"""Measured-vs-modeled exchange validation (ISSUE 2 satellite).

``utils/profiling.exchange_report`` *models* the wire: ring allreduce moves
``2*4*P*(W-1)/W`` bytes, the sparse allgather ``(W-1)*K*8``. This script
MEASURES both collectives over a real 2-process ``jax.distributed``
boundary (gloo over localhost TCP) at the repo's model geometries and
compares the measured sparse/dense time ratio against the modeled byte
ratio. Localhost TCP says nothing absolute about TPU fabric — but the
*ratio* is fabric-independent to first order, so model vs measurement
should agree within a small factor. Results feed docs/RESULTS.md.

Run (parent self-spawns the two workers)::

    python scripts/measure_exchange.py [--iters 5] [--big] \\
        [--fabric-out runs/fabric.json]

``--fabric-out`` additionally writes the measured per-geometry latencies
plus a fitted ``alpha + bytes/bw`` link model as a schema-versioned
``fabric.json`` — the exchange planner's measured-fabric input
(``dgc_tpu.compression.planner.load_fabric``).

``--big`` adds the VGG-16-BN geometry (138M params — ~4.5 GB of host
buffers; off by default).
"""

import argparse
import json
import os
import socket
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

#: (name, num_params P, payload K) — the flat-engine geometries of the
#: repo's three benchmark models at ratio 0.001 (scripts/bench_model.py)
GEOMETRIES = [
    ("resnet20", 272_474, 283),
    ("resnet50", 23_519_754, 25_583),
]
BIG_GEOMETRIES = [
    ("vgg16_bn", 138_365_992, 138_351),
]


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------- #
# worker                                                                  #
# ---------------------------------------------------------------------- #

def worker(args):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    jax.config.update("jax_platforms", "cpu")
    if "jax_cpu_collectives_implementation" in jax.config.values:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    os.environ["JAX_COORDINATOR_ADDRESS"] = args.coord
    os.environ["JAX_NUM_PROCESSES"] = "2"
    os.environ["JAX_PROCESS_ID"] = str(args.proc)
    from dgc_tpu.parallel.multihost import initialize_multihost
    assert initialize_multihost(initialization_timeout=600,
                                heartbeat_timeout_seconds=600,
                                shutdown_timeout_seconds=1200) is True

    import jax.numpy as jnp
    import numpy as np
    from dgc_tpu.utils.compat import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    W = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()), ("data",))
    shard = NamedSharding(mesh, P("data"))

    def time_op(fn, *xs, iters, warmup=2):
        for _ in range(warmup):
            out = fn(*xs)
        jax.block_until_ready(out)
        times = []
        for _ in range(iters):
            t0 = time.perf_counter()
            out = fn(*xs)
            jax.block_until_ready(out)
            times.append((time.perf_counter() - t0) * 1e3)
        return float(np.median(times))

    rows = []
    for name, P_params, K in args.geoms:
        # dense exchange: every worker holds a full [P] gradient, psum —
        # XLA lowers this to the ring/gloo allreduce the model prices
        g = jax.device_put(
            np.random.RandomState(0).randn(W, P_params).astype(np.float32),
            shard)

        @jax.jit
        def dense(x):
            return shard_map(lambda r: jax.lax.psum(r[0], "data"),
                             mesh=mesh, in_specs=P("data"),
                             out_specs=P())(x)

        # sparse exchange: K values + K int32 indices per worker,
        # allgathered (the flat engine's wire form at f32 values)
        vals = jax.device_put(
            np.random.RandomState(1).randn(W, K).astype(np.float32), shard)
        idx = jax.device_put(
            np.random.RandomState(2).randint(
                0, P_params, (W, K)).astype(np.int32), shard)

        @jax.jit
        def sparse(v, i):
            def body(v, i):
                return (jax.lax.all_gather(v[0], "data"),
                        jax.lax.all_gather(i[0], "data"))
            return shard_map(body, mesh=mesh,
                             in_specs=(P("data"), P("data")),
                             out_specs=(P(), P()))(v, i)

        dense_ms = time_op(dense, g, iters=args.iters)
        sparse_ms = time_op(sparse, vals, idx, iters=args.iters)
        dense_bytes = 2 * 4 * P_params * (W - 1) / W
        sparse_bytes = (W - 1) * K * 8
        rows.append({
            "name": name, "P": P_params, "K": K,
            "dense_ms": round(dense_ms, 3),
            "sparse_ms": round(sparse_ms, 3),
            "measured_ratio": round(sparse_ms / dense_ms, 5),
            "modeled_ratio": round(sparse_bytes / dense_bytes, 5),
        })
        del g, vals, idx

    if args.proc == 0:
        print("RESULT:" + json.dumps({"workers": W, "rows": rows}),
              flush=True)
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("measure_done")
    jax.distributed.shutdown()


# ---------------------------------------------------------------------- #
# parent                                                                  #
# ---------------------------------------------------------------------- #

def parent(args):
    coord = f"127.0.0.1:{_free_port()}"
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    cmd = [sys.executable, os.path.abspath(__file__),
           "--iters", str(args.iters)] + (["--big"] if args.big else [])
    procs = [subprocess.Popen(cmd + ["--proc", str(i), "--coord", coord],
                              stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True, env=env)
             for i in range(2)]
    outs = [p.communicate()[0] for p in procs]
    for i, (p, out) in enumerate(zip(procs, outs)):
        if p.returncode != 0:
            print(out[-4000:], file=sys.stderr)
            raise SystemExit(f"worker {i} failed rc={p.returncode}")
    result = None
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RESULT:"):
                result = json.loads(line[len("RESULT:"):])
    assert result, "no RESULT line from workers"

    print(f"# measured vs modeled exchange — {result['workers']} workers "
          f"(2 processes, gloo/localhost)")
    print("| model | P | payload K | dense ms | sparse ms | "
          "measured sparse/dense | modeled (bytes) |")
    print("|---|---|---|---|---|---|---|")
    for r in result["rows"]:
        print(f"| {r['name']} | {r['P']:,} | {r['K']:,} | "
              f"{r['dense_ms']} | {r['sparse_ms']} | "
              f"{r['measured_ratio']} | {r['modeled_ratio']} |")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(result, fh, indent=1)
        print(f"wrote {args.json}", file=sys.stderr)
    if args.fabric_out:
        # schema-versioned fabric model for the exchange planner
        # (dgc_tpu.compression.planner.load_fabric): the per-geometry
        # measured latencies plus a fitted alpha/beta link model over
        # every (bytes, ms) point — dense psums and sparse gathers
        # together, so the intercept captures the per-collective launch
        # latency and the slope the usable bandwidth
        from dgc_tpu.compression.planner import (FABRIC_SCHEMA,
                                                 FABRIC_VERSION,
                                                 fit_link_model)
        Wk = result["workers"]
        pts = []
        for r in result["rows"]:
            pts.append((2 * 4 * r["P"] * (Wk - 1) / Wk, r["dense_ms"]))
            pts.append(((Wk - 1) * r["K"] * 8, r["sparse_ms"]))
        alpha_ms, gbps = fit_link_model(pts)
        beta = 1.0 / (gbps * 1e6)
        residual = (sum((t - (alpha_ms + b * beta)) ** 2
                        for b, t in pts) / len(pts)) ** 0.5
        fabric = {
            "schema": FABRIC_SCHEMA, "version": FABRIC_VERSION,
            "name": f"measured-{Wk}w-gloo",
            "workers": Wk,
            "rows": result["rows"],
            "fit": {"alpha_ms": round(alpha_ms, 6),
                    "gbps": round(gbps, 6)},
            # same stamp shape as the autotuner's runs/fabric.json
            # (compression/autotune.py) so downstream tooling can tell
            # the two producers — and their fit quality — apart
            "provenance": {
                "source": "measure_exchange",
                "geometries": [r["name"] for r in result["rows"]],
                "points": len(pts),
                "distinct_sizes": len({int(b) for b, _ in pts}),
                "geometry_bytes": sorted({int(b) for b, _ in pts}),
                "fit_residual_ms": round(residual, 6),
                "iters": args.iters,
                "written_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
            },
        }
        d = os.path.dirname(os.path.abspath(args.fabric_out))
        os.makedirs(d, exist_ok=True)
        with open(args.fabric_out, "w") as fh:
            json.dump(fabric, fh, indent=1)
        print(f"wrote {args.fabric_out} "
              f"(alpha={fabric['fit']['alpha_ms']} ms, "
              f"gbps={fabric['fit']['gbps']})", file=sys.stderr)
    if args.telemetry_out:
        # the measured table as a telemetry run: one event record per
        # geometry, self-describing header — readable with
        # `python -m dgc_tpu.telemetry.sink <file>` like any other run
        from dgc_tpu.telemetry.sink import TelemetrySink
        with TelemetrySink(args.telemetry_out,
                           static={"experiment": "measure_exchange",
                                   "workers": result["workers"],
                                   "processes": 2,
                                   "fabric": "gloo/localhost"}) as sk:
            for r in result["rows"]:
                sk.write_record(dict(r, event="exchange_measurement"))
        print(f"wrote {args.telemetry_out}", file=sys.stderr)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--big", action="store_true",
                    help="include the 138M-param VGG geometry")
    ap.add_argument("--json", default=None, help="also dump raw JSON")
    ap.add_argument("--fabric-out", default=None,
                    help="write a schema-versioned fabric model (e.g. "
                         "runs/fabric.json) for the exchange planner "
                         "(dgc_tpu.compression.planner); the planner "
                         "falls back to the built-in modeled fabrics "
                         "when absent")
    ap.add_argument("--telemetry-out", default=None,
                    help="also log the measurements through the telemetry "
                         "sink (JSONL)")
    ap.add_argument("--proc", type=int, default=None, help=argparse.SUPPRESS)
    ap.add_argument("--coord", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    args.geoms = GEOMETRIES + (BIG_GEOMETRIES if args.big else [])
    if args.proc is None:
        parent(args)
    else:
        worker(args)


if __name__ == "__main__":
    main()

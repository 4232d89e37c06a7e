"""Device-profile decomposition of the DGC vs dense train step.

Traces K steps of each config with jax.profiler and aggregates per-op
device durations through :mod:`dgc_tpu.telemetry.attrib` (the one trace
parser — this script used to carry its own copy), printing the top ops
per config plus a diff view — the attribution tool behind
docs/RESULTS.md's overhead decomposition. Isolated micro-benches on this
backend are floor-dominated and DCE-prone (see bench.py); the profile
measures the shipped program. Run with ``--trace`` on the train side (or
``scripts/bench_model.py --trace-ab``) for the per-phase/per-bucket view
on top of the per-source one.

Usage: python scripts/profile_step.py [--model resnet50] [--bs 32] [--k 10]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

from dgc_tpu.telemetry import attrib
from dgc_tpu.telemetry import trace as dgc_trace


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--k", type=int, default=10)
    ap.add_argument("--ratio", type=float, default=0.001)
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--out", default="/tmp/dgc_profile")
    ap.add_argument("--mem-dtype", default=None,
                    help="error-feedback state dtype for the dgc arm")
    ap.add_argument("--phases", action="store_true",
                    help="enable dgcph.* markers and print the per-phase "
                         "attribution table alongside the per-source one")
    args = ap.parse_args()

    if args.phases:
        dgc_trace.enable(True)

    import bench
    from dgc_tpu import (Compression, DGCCompressor, DGCSGDMemory,
                         DistributedOptimizer, dgc_sgd, sgd)
    from dgc_tpu import models
    from dgc_tpu.parallel import data_sharding, make_mesh
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.utils import compile_cache
    from dgc_tpu.utils.device import require_tpu
    from dgc_tpu.utils.pytree import named_flatten

    compile_cache.enable()
    require_tpu("profile_step.py")
    model = getattr(models, args.model)()
    size = 32 if args.model.startswith("resnet2") else 224
    ncls = 10 if size == 32 else 1000
    W = len(jax.devices())
    mesh = make_mesh(W)
    npr = np.random.RandomState(0)
    batch_sharding = data_sharding(mesh)
    images = jax.device_put(
        npr.randn(W * args.bs, size, size, 3).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        npr.randint(0, ncls, W * args.bs).astype(np.int32), batch_sharding)
    v = model.init(jax.random.PRNGKey(42), jnp.zeros((1, size, size, 3)),
                   train=True)
    named, _ = named_flatten(v["params"])

    def prepare(dist):
        setup = make_flat_setup(v, dist)
        state = shard_state(make_flat_state(v, dist, setup, W), mesh,
                            dist_opt=dist)
        step = build_train_step(model.apply, dist, mesh, donate=False,
                                use_dropout="vgg" in args.model,
                                flat=setup)
        return bench._make_k_loop(step, images, labels, args.k), state

    comp = DGCCompressor(args.ratio, memory=DGCSGDMemory(
        momentum=0.9, dtype=args.mem_dtype))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    runs = {
        "dgc": prepare(DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9, weight_decay=1e-4), comp,
            world_size=W)),
        "dense": prepare(DistributedOptimizer(
            sgd(0.1, momentum=0.9, weight_decay=1e-4), Compression.none(),
            world_size=W)),
    }

    repo_root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    _ssum = jax.jit(lambda x: jnp.sum(x))
    per_config = {}
    for name, (k_loop, state) in runs.items():
        state, _ = k_loop(state, jax.random.PRNGKey(0))  # compile + warm
        float(_ssum(state.params))
        logdir = os.path.join(args.out, name)
        os.makedirs(logdir, exist_ok=True)
        with jax.profiler.trace(logdir):
            state, _ = k_loop(state, jax.random.PRNGKey(1))
            float(_ssum(state.params))
        events = attrib.device_events(attrib.load_trace_events(logdir),
                                      device="tpu")
        by_source, by_name, leaf_total = attrib.aggregate_by_source(
            events, repo_root)
        per_config[name] = by_source
        print(f"\n=== {name}: leaf device total {leaf_total / args.k:.3f} "
              f"ms/step ===")
        for nm, (ms, meta) in sorted(by_name.items(),
                                     key=lambda kv: -kv[1][0])[:args.top]:
            print(f"  {ms / args.k:8.4f}  {nm:<36s} {meta}")
        if args.phases:
            table = attrib.phase_table(events, steps=args.k)
            print(f"  --- phases ({table['attributed_ms']:.3f} of "
                  f"{table['total_ms']:.3f} ms/step attributed) ---")
            for ph, ms in attrib.phase_rows(table):
                print(f"  {ms:8.4f}  {ph}")

    d, b = per_config["dgc"], per_config["dense"]
    print("\n=== per-source decomposition: DGC minus dense (ms/step) ===")
    keys = sorted(set(d) | set(b),
                  key=lambda k: -(d.get(k, 0.0) - b.get(k, 0.0)))
    tot = 0.0
    for k in keys:
        delta = (d.get(k, 0.0) - b.get(k, 0.0)) / args.k
        tot += delta
        if abs(delta) > 0.02:
            print(f"  {delta:+8.4f}  {k}  (dgc {d.get(k, 0) / args.k:.3f} "
                  f"dense {b.get(k, 0) / args.k:.3f})")
    print(f"  TOTAL leaf delta: {tot:+.3f} ms/step")


if __name__ == "__main__":
    main()

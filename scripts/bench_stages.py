"""Dev micro-bench: per-stage isolation of the flat DGC engine at
ResNet-50 / ratio 0.001 shapes on the real TPU chip.

Same scan-K + one-scalar-readback methodology as bench.py (whose
host-readback measurement it reuses; if the methodology changes there,
update time_scan here to match). Each stage runs K times inside one
jitted lax.scan with a data dependency threaded through, then one forced
readback; the host readback latency is subtracted and the remainder
amortized. Refuses to run off the chip. Every stage calls ENGINE code (not inlined
re-implementations, which go stale); for finer attribution take a device
profile (jax.profiler.trace) and aggregate the XLA-op durations.

Known bias: isolated stages carry a ~1 ms per-scan-iteration floor on
this backend — compare stages to each other, not to the paired full-step
difference (the honest end-to-end number).

Usage: python scripts/bench_stages.py [--model resnet50|resnet20] [--k 30]
       add --attrib to ALSO take a device profile of the full exchange
       with dgcph.* phase markers on and print the per-phase/per-bucket
       attribution (dgc_tpu.telemetry.attrib) — the profile view is free
       of the micro-bench floor bias above
"""

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np
from dgc_tpu.utils.compat import shard_map


_ssum = jax.jit(lambda x: jnp.sum(x))


def time_scan(fn, carry0, k, readback_ms, repeats=5, name=""):
    """fn: carry -> carry (same pytree structure). Returns ms/iter."""
    @jax.jit
    def loop(c):
        def body(c, _):
            return fn(c), 0
        c, _ = jax.lax.scan(body, c, None, length=k)
        return c

    c = loop(carry0)  # compile + warm
    float(_ssum(jax.tree.leaves(c)[0]))
    best = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        c = loop(c)
        float(_ssum(jax.tree.leaves(c)[0]))
        dt = ((time.perf_counter() - t0) * 1e3 - readback_ms) / k
        best = dt if best is None else min(best, dt)
    print(f"{name:<44s}: {best:8.4f} ms", file=sys.stderr)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--k", type=int, default=30)
    ap.add_argument("--ratio", type=float, default=0.001)
    ap.add_argument("--attrib", action="store_true",
                    help="device-profile the full exchange with phase "
                         "markers and print the attrib table")
    ap.add_argument("--out", default="/tmp/dgc_stages",
                    help="profiler logdir for --attrib")
    args = ap.parse_args()

    from dgc_tpu import DGCCompressor, DGCSGDMemory
    from dgc_tpu.compression.flat import FlatDGCEngine, ParamLayout
    from dgc_tpu.models import resnet20, resnet50
    from dgc_tpu.utils import compile_cache
    from dgc_tpu.utils.device import require_tpu
    from dgc_tpu.utils.pytree import named_flatten

    compile_cache.enable()
    require_tpu("bench_stages.py")
    model = resnet50() if args.model == "resnet50" else resnet20()
    shape = (1, 224, 224, 3) if args.model == "resnet50" else (1, 32, 32, 3)
    v = model.init(jax.random.PRNGKey(0), jnp.zeros(shape), train=True)
    named, _ = named_flatten(v["params"])

    comp = DGCCompressor(args.ratio, memory=DGCSGDMemory(momentum=0.9))
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    layout = ParamLayout.for_compressor(v["params"], comp)
    engine = FlatDGCEngine(comp, layout)

    print(f"model={args.model} ratio={args.ratio} "
          f"P={layout.total} T={layout.t_compressed} "
          f"payload={engine.payload_size}", file=sys.stderr)
    for b in engine.buckets:
        sel = "approx" if (comp.approx_recall is not None
                           and b.max_sel > 128) else "exact"
        print(f"  bucket R={b.rows:3d} cols={b.cols:9d} "
              f"max_s={b.max_s:8d} max_k={b.max_k:6d} "
              f"max_sel={b.max_sel:6d} exact={b.exact} sel={sel} "
              f"payload={b.payload}", file=sys.stderr)

    import bench
    readback_ms = bench._measure_readback_ms()
    print(f"host readback latency {readback_ms:.3f} ms", file=sys.stderr)

    rng = np.random.RandomState(0)
    T = layout.t_compressed
    P = layout.total
    g = jax.device_put(jnp.asarray(rng.randn(P), jnp.float32) * 1e-2)
    mem = engine.init_memory()
    key = jax.random.PRNGKey(1)

    # --- full pipeline single-device (no collectives; psum/all_gather on
    #     1 device are local copies) ---
    from jax.sharding import Mesh, PartitionSpec as Pspec

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))

    def full(c):
        grad, m = c
        def worker(fg, mm):
            out, mm = engine.exchange(fg, mm, key, "data", 1)
            return out, mm
        out, m = shard_map(
            worker, mesh=mesh, in_specs=(Pspec(), Pspec()),
            out_specs=(Pspec(), Pspec()), check_vma=False)(grad, m)
        return (out * 0.999, m)

    time_scan(full, (g, mem), args.k, readback_ms,
              name="FULL exchange (1-dev)")

    # --- stage: fused compensate over [T] ---
    gc = g[:T]
    mc, vc = mem["momentums_c"], mem["velocities_c"]

    def comp_stage(c):
        gg, m, vv = c
        out, m2, v2, _ = engine._compensate_acc(m, vv, gg)
        return (gg * 0.999, m2, v2 * 0.5)

    time_scan(comp_stage, (gc, mc, vc), args.k, readback_ms,
              name="compensate [T]")

    # --- stage: sparsify (all buckets) ---
    def spars(c):
        vec, acc = c
        vals, idx = engine.sparsify(vec, key)
        return (vec * 0.999, acc + jnp.sum(vals) + jnp.sum(idx))

    time_scan(spars, (gc, jnp.float32(0)), args.k, readback_ms,
              name="sparsify ALL buckets")

    # --- per-bucket sparsify ---
    saved = engine.buckets
    for bi in range(len(saved)):
        engine.buckets = [saved[bi]]
        time_scan(spars, (gc, jnp.float32(0)), args.k, readback_ms,
                  name=f"sparsify bucket {bi} (R={saved[bi].rows}, "
                       f"cols={saved[bi].cols})")
    engine.buckets = saved

    # (round-1 carried hand-inlined sub-stage benches here; they
    # re-implemented engine internals and went stale the moment the engine
    # changed — per-stage attribution now comes from the device PROFILE
    # via --attrib below (dgc_tpu.telemetry.attrib over a marker-annotated
    # trace), which always measures the shipped code. The remaining
    # stages call engine code directly.)

    if args.attrib:
        from dgc_tpu.telemetry import attrib
        from dgc_tpu.telemetry import trace as dgc_trace
        prev = dgc_trace.enable(True)
        try:
            # fresh jit so the marker-annotated program builds (the scans
            # above traced with markers off)
            loop = jax.jit(lambda c: jax.lax.scan(
                lambda cc, _: (full(cc), 0), c, None, length=args.k)[0])
            c = loop((g, mem))                      # compile + warm
            float(_ssum(jax.tree.leaves(c)[0]))
            os.makedirs(args.out, exist_ok=True)
            with jax.profiler.trace(args.out):
                c = loop(c)
                float(_ssum(jax.tree.leaves(c)[0]))
        finally:
            dgc_trace.enable(prev)
        events = attrib.device_events(attrib.load_trace_events(args.out))
        if not events:
            raise SystemExit(
                "[attrib] the profiler trace holds no device-op events "
                f"under {args.out} — nothing to attribute")
        table = attrib.phase_table(events, steps=args.k)
        print(f"--- profile attribution: {table['attributed_ms']:.3f} "
              f"of {table['total_ms']:.3f} ms/iter attributed ---",
              file=sys.stderr)
        for ph, ms in attrib.phase_rows(table):
            print(f"  {ms:8.4f}  {ph}", file=sys.stderr)
        for b, phases in table["buckets"].items():
            tot = sum(phases.values())
            print(f"  {tot:8.4f}  {b}  " + "  ".join(
                f"{p}={v:.4f}" for p, v in phases.items()),
                file=sys.stderr)

    # --- masking + scatter-add decompress ---
    vals0, idx0 = jax.jit(lambda v, k: engine.sparsify(v, k))(gc, key)

    def sent_stage(c):
        vv, acc = c
        sent = jnp.zeros((T,), jnp.float32).at[idx0].add(1.0)
        return (vv * 0.999, acc + sent[0])

    time_scan(sent_stage, (vc, jnp.float32(0)), args.k, readback_ms,
              name="sent-count scatter (fresh zeros)")

    def scatter_stage(c):
        acc = jnp.zeros((T,), jnp.float32)
        acc = acc.at[idx0].add(vals0 + c[0])
        return (acc[:1] * 0.999,)

    time_scan(scatter_stage, (jnp.zeros((1,)),), args.k, readback_ms,
              name="scatter-add decompress")


if __name__ == "__main__":
    main()

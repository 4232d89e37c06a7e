"""Paired full-step DGC-vs-dense overhead at ImageNet scale on the real
TPU chip (the ResNet-50 / VGG-16-BN rows of docs/RESULTS.md).

Reuses bench.py's scan-K + one-readback + interleaved-rounds methodology
(see bench.py's module docstring). Prints the paired per-round overheads
and their median/IQR. Refuses to run off the chip.

Usage: python scripts/bench_model.py [--model resnet50|vgg16_bn|resnet20]
           [--bs 32] [--k 40] [--repeats 8] [--ratio 0.001]
"""

import argparse
import os
import statistics
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet50")
    ap.add_argument("--bs", type=int, default=32)
    ap.add_argument("--k", type=int, default=40)
    ap.add_argument("--repeats", type=int, default=8)
    ap.add_argument("--ratio", type=float, default=0.001)
    ap.add_argument("--mem-dtype", default=None,
                    help="error-feedback state dtype override, e.g. "
                         "bfloat16 (configs/dgc/bf16mem.py)")
    ap.add_argument("--int8", action="store_true",
                    help="int8-quantized wire values (configs/dgc/int8.py)")
    ap.add_argument("--no-int8-ef", action="store_true",
                    help="with --int8: disable quantization error "
                         "feedback (the round-3 no-feedback form) — "
                         "isolates the feedback path's step-time cost")
    ap.add_argument("--fused-apply", action="store_true",
                    help="fused apply epilogue (DGCCompressor "
                         "fused_apply=True): decompress scatter-add + "
                         "transmit-record pack as one streamed Pallas "
                         "pass (kernels.payload_apply_bits). Run once "
                         "with and once without to A/B paired against "
                         "the identical dense arm.")
    ap.add_argument("--bf16", action="store_true",
                    help="bfloat16 model compute (configs/bf16.py): both "
                         "arms build the model with dtype=bf16 and the "
                         "step casts the flat parameter buffer once "
                         "(build_train_step model_dtype)")
    ap.add_argument("--megakernel-ab", action="store_true",
                    help="pair dgc+megakernel against plain dgc instead "
                         "of dgc vs dense: measures the two-megakernel "
                         "hot path's step-time delta (DGCCompressor "
                         "megakernel=True — kernels.dgc_forward_rows + "
                         "dgc_apply_rows; negative = the fused path "
                         "wins). Gated as overhead_ms_megakernel.")
    ap.add_argument("--megakernel", action="store_true",
                    help="run the DGC arm with megakernel=True in the "
                         "ordinary dgc-vs-dense pairing")
    ap.add_argument("--telemetry-ab", action="store_true",
                    help="pair dgc+telemetry against plain dgc instead of "
                         "dgc vs dense: measures the in-graph telemetry "
                         "taps' overhead (ISSUE 2 gate: <= 1% of step "
                         "time). Both arms consume their metric outputs "
                         "so nothing is dead-code-eliminated.")
    ap.add_argument("--guards-ab", action="store_true",
                    help="pair dgc+guards(+checksum) against plain dgc: "
                         "measures the resilience layer's in-graph cost "
                         "(nonfinite skip + spike breaker + payload "
                         "checksum; docs/RESILIENCE.md). Both arms "
                         "consume their metric outputs so nothing is "
                         "dead-code-eliminated.")
    ap.add_argument("--telemetry-out", default=None,
                    help="write a telemetry JSONL run summary (sink "
                         "schema) for the regression gate: python -m "
                         "dgc_tpu.telemetry.regress BASELINE <path>")
    ap.add_argument("--trace-ab", action="store_true",
                    help="after the paired timing, device-profile both "
                         "arms with dgcph.* phase markers on and write "
                         "the per-bucket per-phase cost table "
                         "(--profile-out) — the exchange planner's input; "
                         "the profiled dgc-minus-dense delta reconciles "
                         "against the paired-timing overhead "
                         "(docs/TELEMETRY.md §Phase attribution)")
    ap.add_argument("--profile-out", default="runs/profile.json",
                    help="profile.json path for --trace-ab")
    ap.add_argument("--profile-dir", default="/tmp/dgc_trace_ab",
                    help="profiler logdir for --trace-ab")
    ap.add_argument("--mode", default="scan", choices=["scan", "dispatch"],
                    help="scan: K steps in one lax.scan dispatch (the "
                         "conservative default — its while-loop carry "
                         "copies the big DGC state each iteration, ~1 "
                         "ms/step counted against DGC). dispatch: K "
                         "DONATED per-dispatch steps queued async + one "
                         "readback — how real training runs; valid only "
                         "while the host's per-call dispatch latency "
                         "stays under the step time (watch the paired "
                         "MAD).")
    args = ap.parse_args()

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
    require_tpu("bench_model.py")
    model = getattr(models, args.model)(
        **({"dtype": jnp.bfloat16} if args.bf16 else {}))
    size = 32 if args.model.startswith("resnet2") else 224
    ncls = 10 if size == 32 else 1000

    devices = jax.devices()
    W = len(devices)
    mesh = make_mesh(W)
    readback_ms = bench._measure_readback_ms()
    print(f"devices {W} x {devices[0].device_kind}, host readback latency "
          f"{readback_ms:.3f} ms", file=sys.stderr)

    npr = np.random.RandomState(0)
    # the global batch lives where the step reads it: one slice per chip
    batch_sharding = data_sharding(mesh)
    images = jax.device_put(
        npr.randn(W * args.bs, size, size, 3).astype(np.float32),
        batch_sharding)
    labels = jax.device_put(
        npr.randint(0, ncls, W * args.bs).astype(np.int32), batch_sharding)
    v = model.init(jax.random.PRNGKey(42), jnp.zeros((1, size, size, 3)),
                   train=True)
    named, _ = named_flatten(v["params"])

    dispatch = args.mode == "dispatch"

    def make_dispatch_loop(step_fn, k):
        def run(state, key):
            keys = jax.random.split(key, k)
            for i in range(k):
                state, m = step_fn(state, images, labels, keys[i])
            return state, m["loss"]
        return run

    def prepare(dist, telemetry=False, consume=False, guards=None):
        setup = make_flat_setup(v, dist)
        state = shard_state(make_flat_state(v, dist, setup, W,
                                            guards=guards), mesh,
                            dist_opt=dist)
        step = build_train_step(model.apply, dist, mesh, donate=dispatch,
                                use_dropout="vgg" in args.model,
                                flat=setup,
                                model_dtype=(jnp.bfloat16 if args.bf16
                                             else None),
                                telemetry=telemetry, guards=guards)
        loop = (make_dispatch_loop(step, args.k) if dispatch
                else bench._make_k_loop(step, images, labels, args.k,
                                        consume_metrics=consume))
        return (loop, state), setup

    def mk_comp(checksum=False, megakernel=None):
        if megakernel is None:
            megakernel = args.megakernel
        c = DGCCompressor(args.ratio, memory=DGCSGDMemory(
            momentum=0.9, dtype=args.mem_dtype), int8_values=args.int8,
            int8_error_feedback=not args.no_int8_ef,
            fused_apply=args.fused_apply, megakernel=megakernel,
            checksum=checksum)
        c.initialize((n, p) for n, p in named.items() if p.ndim > 1)
        return c

    def mk_dgc_dist(checksum=False, megakernel=None):
        return DistributedOptimizer(
            dgc_sgd(0.1, momentum=0.9, weight_decay=1e-4),
            mk_comp(checksum, megakernel=megakernel), world_size=W)

    if args.megakernel_ab:
        a_run, setup = prepare(mk_dgc_dist(megakernel=True))
        b_run, _ = prepare(mk_dgc_dist(megakernel=False))
        label = ("dgc+megakernel", "dgc")
    elif args.telemetry_ab:
        a_run, setup = prepare(mk_dgc_dist(), telemetry=True, consume=True)
        b_run, _ = prepare(mk_dgc_dist(), telemetry=False, consume=True)
        label = ("dgc+telemetry", "dgc")
    elif args.guards_ab:
        from dgc_tpu.resilience import GuardConfig
        a_run, setup = prepare(mk_dgc_dist(checksum=True), consume=True,
                               guards=GuardConfig(spike_window=8))
        b_run, _ = prepare(mk_dgc_dist(), consume=True)
        label = ("dgc+guards", "dgc")
    else:
        a_run, setup = prepare(mk_dgc_dist())
        b_run, _ = prepare(DistributedOptimizer(
            sgd(0.1, momentum=0.9, weight_decay=1e-4), Compression.none(),
            world_size=W))
        label = ("dgc", "dense")
    print(f"model={args.model} P={setup.layout.num_params} "
          f"payload={setup.engine.payload_size}", file=sys.stderr)

    rows = bench._interleaved_step_ms(
        [a_run, b_run], readback_ms, k=args.k, repeats=args.repeats,
        max_repeats=3 * args.repeats)
    a_ms, b_ms = (min(col) for col in zip(*rows))
    diffs = [d - b for d, b in rows]
    med = statistics.median(diffs)
    q1, q3 = (float(x) for x in np.percentile(diffs, [25, 75]))
    print(f"{label[0]} step:   {a_ms:.3f} ms", file=sys.stderr)
    print(f"{label[1]} step: {b_ms:.3f} ms", file=sys.stderr)
    print(f"per-round overheads: {[round(x, 3) for x in diffs]}",
          file=sys.stderr)
    print(f"OVERHEAD ({label[0]} - {label[1]}) median {med:.3f} ms  "
          f"IQR [{q1:.3f}, {q3:.3f}]  "
          f"({100 * med / b_ms:.1f}% of {label[1]} step)")

    if args.trace_ab:
        from dgc_tpu.telemetry import attrib
        from dgc_tpu.telemetry import trace as dgc_trace
        _ssum = jax.jit(lambda x: jnp.sum(x))
        events = {}
        prev = dgc_trace.enable(True)
        try:
            # fresh builds: the markers must be live at trace time (the
            # timing arms above compiled with markers off — the honest
            # paired numbers carry zero annotation cost)
            profiled = {
                "dgc": mk_dgc_dist(),
                "dense": DistributedOptimizer(
                    sgd(0.1, momentum=0.9, weight_decay=1e-4),
                    Compression.none(), world_size=W),
            }
            for name, dist in profiled.items():
                (loop, state), _ = prepare(dist)
                state, _ = loop(state, jax.random.PRNGKey(0))  # warm
                float(_ssum(state.params))
                logdir = os.path.join(args.profile_dir, name)
                os.makedirs(logdir, exist_ok=True)
                with jax.profiler.trace(logdir):
                    state, _ = loop(state, jax.random.PRNGKey(1))
                    float(_ssum(state.params))
                events[name] = attrib.device_events(
                    attrib.load_trace_events(logdir))
        finally:
            dgc_trace.enable(prev)
        if not events["dgc"]:
            raise SystemExit(
                "[trace-ab] the profiler trace holds no device-op events "
                f"under {args.profile_dir}/dgc — an empty phase table is "
                "not a profile; nothing written")
        dgc_table = attrib.phase_table(events["dgc"], steps=args.k)
        dense_table = attrib.phase_table(events["dense"], steps=args.k)
        prof = attrib.profile_json(
            dgc_table, dense_table,
            static={"model": args.model, "bs": args.bs, "k": args.k,
                    "ratio": args.ratio, "world": W, "mode": args.mode,
                    "wire_bytes": setup.engine.wire_bytes_per_worker(),
                    "payload_elems": setup.engine.payload_size},
            measured_overhead_ms=med)
        path = attrib.write_profile(prof, args.profile_out)
        print(f"profile -> {path}", file=sys.stderr)
        print(f"PROFILE delta {prof['delta_ms']:.3f} ms  "
              f"exchange phases {prof['exchange_phase_ms']:.3f} ms  "
              f"vs measured overhead {med:.3f} ms")

    if args.telemetry_out:
        from dgc_tpu.telemetry.sink import TelemetrySink
        with TelemetrySink(args.telemetry_out,
                           static=dict(setup.engine.telemetry_static(),
                                       model=args.model, mode=args.mode,
                                       arms=list(label))) as sk:
            rec = {
                "event": "run_summary",
                "step_time_ms": round(a_ms, 4),
                "baseline_step_ms": round(b_ms, 4),
                "overhead_ms": round(max(med, 0.0), 4),
                "wire_bytes": setup.engine.wire_bytes_per_worker(),
                "payload_elems": setup.engine.payload_size,
            }
            if args.megakernel_ab:
                # signed: a faster megakernel arm must KEEP the gain
                # under the lower-is-better regression gate
                rec["overhead_ms_megakernel"] = round(med, 4)
            sk.write_record(rec)
        print(f"telemetry run written: {args.telemetry_out}",
              file=sys.stderr)


if __name__ == "__main__":
    main()

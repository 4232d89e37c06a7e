"""Worker program for the 2-process fleet-observability drill
(tests/test_multiprocess.py::test_fleet_two_process_straggler).

One 2-process ``jax.distributed`` launch over a 2-host x 4-device mesh:
build the FLEET train step (telemetry=True, fleet=True — the packed
all_gather replaces the telemetry pmean), stamp the real host prep
interval into the clock input each step, and write every record through a
per-host :class:`TelemetrySink` shard (``<run>/telemetry/host<i>/``) —
exactly the layout train.py produces with configs/fleet.py.

The parent arms ``DGC_FAULTS=slow:ms=...`` on process 1 only, so that
process sleeps before every dispatch: its workers' dispatch intervals
stretch and the fleet view must name one of them the straggler. Prints one
``RESULT:`` JSON line per process with the in-graph straggler verdicts.

With ``adaptive`` as a 6th argv (the straggler-adaptive drill,
tests/test_multiprocess.py::test_fleet_two_process_adaptive), the step is
built with ``resilience.adaptive.AdaptiveConfig()`` and the RESULT line
additionally carries the per-step ``w_eff_ratio`` / ``w_sent_ratio``
columns — the parent asserts the straggler's effective send fraction
drops while the healthy workers' stays at 1. A windowed fault
(``slow:ms=M@K-L``) makes it the transient-straggler drill: the policy
must engage inside the window and release after it.
"""

import json
import os
import sys
import time

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax

jax.config.update("jax_platforms", "cpu")
if "jax_cpu_collectives_implementation" in jax.config.values:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

STEPS = 14


def main():
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    coord = sys.argv[3]
    workdir = sys.argv[4]
    adaptive_on = len(sys.argv) > 5 and sys.argv[5] == "adaptive"

    from dgc_tpu.parallel.multihost import (host_local_to_global,
                                            initialize_multihost)

    from dgc_tpu.utils import compile_cache
    compile_cache.enable()

    os.environ["JAX_COORDINATOR_ADDRESS"] = coord
    os.environ["JAX_NUM_PROCESSES"] = str(num_procs)
    os.environ["JAX_PROCESS_ID"] = str(proc_id)
    assert initialize_multihost(initialization_timeout=600,
                                heartbeat_timeout_seconds=600,
                                shutdown_timeout_seconds=1200) is True
    assert jax.process_count() == num_procs

    import jax.numpy as jnp  # noqa: F401  (kept for parity with sibling)
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh

    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         dgc_sgd)
    from dgc_tpu.resilience import faults
    from dgc_tpu.telemetry import fleet
    from dgc_tpu.telemetry.sink import TelemetrySink
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.utils.pytree import named_flatten

    W = len(jax.devices())
    assert W == 2 * 4
    mesh = Mesh(np.array(jax.devices()), ("data",))

    class M(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            x = nn.relu(x)
            return nn.Dense(10)(x.mean(axis=(1, 2)))

    model = M()
    v = dict(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16, 16, 3))))

    def apply_fn(variables, x, train=True, mutable=None, rngs=None):
        if mutable:
            return model.apply(variables, x, train=train, mutable=mutable,
                               rngs=rngs)
        return model.apply(variables, x, train=train)

    comp = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
    named, _ = named_flatten(v["params"])
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.1, momentum=0.9), comp,
                                world_size=W)
    acfg = None
    if adaptive_on:
        from dgc_tpu.resilience.adaptive import AdaptiveConfig
        acfg = AdaptiveConfig()
    setup = make_flat_setup(v, dist)
    state = shard_state(make_flat_state(v, dist, setup, W, adaptive=acfg),
                        mesh, dist_opt=dist)
    step_fn = build_train_step(apply_fn, dist, mesh, donate=False,
                               flat=setup, telemetry=True, fleet=True,
                               adaptive=acfg)

    run_dir = os.path.join(workdir, "fleetrun")
    sink = TelemetrySink(
        os.path.join(run_dir, "telemetry", f"host{proc_id}"),
        static=dict(setup.engine.telemetry_static(), world=W,
                    process_index=proc_id, num_processes=num_procs),
        fleet=True)
    sink.write_record({"event": "fleet_drill_start", "proc": proc_id})

    bs = 4

    def batch(i):
        rng = np.random.RandomState(2000 + i)
        im = rng.randn(W * bs, 16, 16, 3).astype(np.float32)
        lb = rng.randint(0, 10, W * bs).astype(np.int32)
        return (host_local_to_global(im, mesh),
                host_local_to_global(lb, mesh))

    prev = None
    kept = []
    for i in range(STEPS):
        if faults.armed():
            faults.maybe_slow(i)         # the injected straggler drill
                                         # (step-gated for @K-L windows)
        im, lb = batch(i)
        # w_clock lane: host PREP time only — previous dispatch RETURN to
        # this dispatch START. The dispatch call itself is excluded: it
        # can block on the cohort collective, and that wait is the same
        # on every host (equalized), so including it would erase the
        # straggler's signature. Only its own sleep/data work stretch
        # ITS stamps.
        now = time.perf_counter()
        dt_ms = (now - prev) * 1000.0 if prev is not None else 0.0
        state, m = step_fn(state, im, lb, jax.random.PRNGKey(i),
                           fleet.make_clock(dt_ms, mesh, W))
        prev = time.perf_counter()
        sink.write(i, {**m["telemetry"], **m["fleet"], "loss": m["loss"]})
        kept.append(m["fleet"])
    jax.block_until_ready(state)
    sink.close()

    # convert after the loop: one host sync per recorded scalar, all of
    # them long since computed
    stragglers = [int(float(f["straggler"])) for f in kept]
    gaps = [float(f["straggler_gap"]) for f in kept]
    out = {"proc": proc_id,
           "stragglers": stragglers,
           "gaps": [round(g, 3) for g in gaps],
           "sink": sink.path or ""}
    if adaptive_on:
        out["eff"] = [[round(float(x), 4) for x in np.asarray(f["w_eff_ratio"])]
                      for f in kept]
        out["sent"] = [[round(float(x), 5)
                        for x in np.asarray(f["w_sent_ratio"])]
                       for f in kept]
        out["engaged"] = [float(f["adaptive_engaged"]) for f in kept]
    print("RESULT:" + json.dumps(out), flush=True)

    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("fleet_drill_done")
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()

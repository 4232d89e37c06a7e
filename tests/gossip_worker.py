"""Worker program for the 2-process gossip drill
(tests/test_multiprocess.py::test_gossip_two_process_save_resume).

Two phases, each a 2-process ``jax.distributed`` launch over the same
checkpoint directory, both with the SAME ``DGC_FAULTS`` armed (the
``droplink`` injector is traced into the program, so every process must
compile the identical graph):

* ``run`` — build the fleet train step under a ``gossip_ring`` plan
  (``sync_every=4``, ``max_staleness=4``) with
  ``DGC_FAULTS=droplink:peer=3@1-5`` armed: worker 3's contribution is
  suppressed for gossip rounds 1..5, so the staleness bound breaches and
  the engine forces full-sync rounds at exactly clocks 5 and 6 (the
  test_gossip.py step-exact arithmetic, now over a real process
  boundary). Train TOTAL_STEPS steps, write every fleet record —
  including the ``w_staleness`` lane and the forced-sync counter —
  through a per-host :class:`TelemetrySink` shard, and save one
  collective checkpoint after SAVE_STEP steps (mid-drill: the gossip
  clock, ages, forced counter, and in-flight inbox all ride the raw
  memory tree).
* ``resume`` — restore the checkpoint, fingerprint the restored gossip
  round state (must be bitwise the run phase's at the save point), and
  train the remaining steps: the loss trajectory and the final gossip
  fingerprint must match the uninterrupted run exactly.

Prints one RESULT: JSON line per process for the parent to compare.
"""

import hashlib
import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax

jax.config.update("jax_platforms", "cpu")
if "jax_cpu_collectives_implementation" in jax.config.values:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TOTAL_STEPS = 8
SAVE_STEP = 5          # completed steps before the collective save
GOSSIP_KEYS = ("gossip_clock", "gossip_age", "gossip_forced",
               "gossip_inbox")


def main():
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    coord = sys.argv[3]
    workdir = sys.argv[4]
    phase = sys.argv[5]
    assert phase in ("run", "resume"), phase

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

    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh

    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         dgc_sgd)
    from dgc_tpu.compression import planner
    from dgc_tpu.telemetry import fleet
    from dgc_tpu.telemetry.sink import TelemetrySink
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.training.checkpoint import CheckpointManager
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
    # the gossip plan (refit to the real bucket geometry inside
    # make_flat_setup); sync_every == max_staleness == 4 is the step-exact
    # droplink drill from tests/test_gossip.py
    plan = planner.plan_buckets(
        [], fabric="32x25GbE", world=W, candidates=("gossip_ring",),
        gossip_sync_every=4, gossip_max_staleness=4)
    setup = make_flat_setup(v, dist, plan=plan)
    assert setup.engine.plan.gossip is not None
    state = shard_state(make_flat_state(v, dist, setup, W), mesh,
                        dist_opt=dist)
    step_fn = build_train_step(apply_fn, dist, mesh, donate=False,
                               flat=setup, telemetry=True, fleet=True)

    run_dir = os.path.join(workdir, "gossiprun")
    # the resume phase replays steps the run already recorded; one clean
    # shard set keeps the fleet view unambiguous
    sink = None
    if phase == "run":
        sink = TelemetrySink(
            os.path.join(run_dir, "telemetry", f"host{proc_id}"),
            static=dict(setup.engine.telemetry_static(), world=W,
                        process_index=proc_id, num_processes=num_procs),
            fleet=True)

    bs = 4

    def batch(i):
        """Deterministic per-step global batch — identical in both phases,
        so the resumed run sees the uninterrupted run's data."""
        rng = np.random.RandomState(3000 + i)
        im = rng.randn(W * bs, 16, 16, 3).astype(np.float32)
        lb = rng.randint(0, 10, W * bs).astype(np.int32)
        return (host_local_to_global(im, mesh),
                host_local_to_global(lb, mesh))

    def fingerprint(tree):
        """sha256 over this process's addressable shard bytes, in a
        deterministic (path, shard-index) order."""
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        h = hashlib.sha256()
        for path, leaf in sorted(leaves, key=lambda kv: str(kv[0])):
            if not hasattr(leaf, "addressable_shards"):
                h.update(np.asarray(leaf).tobytes())
                continue
            for s in sorted(leaf.addressable_shards,
                            key=lambda s: str(s.index)):
                h.update(np.asarray(s.data).tobytes())
        return h.hexdigest()

    def gossip_print(st):
        return fingerprint({k: st.memory[k] for k in GOSSIP_KEYS})

    def drive(st, lo, hi):
        """Train steps [lo, hi); return (state, losses, fleet columns).
        The clock input is a deterministic stamp, so both phases trace
        the identical fleet lanes."""
        losses, stale_cols, forced, seen = [], [], [], []
        for i in range(lo, hi):
            im, lb = batch(i)
            st, m = step_fn(st, im, lb, jax.random.PRNGKey(i),
                            fleet.make_clock(10.0 + i, mesh, W))
            losses.append(float(m["loss"]))
            flt = m["fleet"]
            stale_cols.append(
                [float(x) for x in np.asarray(flt["w_staleness"])])
            forced.append(float(flt["gossip_forced_syncs"]))
            seen.append(float(flt["max_staleness_seen"]))
            if sink is not None:
                sink.write(i, {**m["telemetry"], **m["fleet"],
                               "loss": m["loss"]})
            jax.block_until_ready(st)
        return st, losses, stale_cols, forced, seen

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt_gossip"), keep=2)
    out = {"proc": proc_id, "phase": phase}

    if phase == "run":
        state, losses, stale, forced, seen = drive(state, 0, SAVE_STEP)
        out["gossip_saved"] = gossip_print(state)
        ckpt.save(0, state, {"gossip_batch": SAVE_STEP - 1})
        state, l2, s2, f2, m2 = drive(state, SAVE_STEP, TOTAL_STEPS)
        losses += l2
        stale += s2
        forced += f2
        seen += m2
        out.update(losses=losses, w_staleness=stale, forced=forced,
                   max_seen=seen, gossip_final=gossip_print(state),
                   mem_final=fingerprint(state.memory))

    else:  # resume
        restored = ckpt.restore(state)
        assert restored is not None, "gossip checkpoint must restore"
        r_state, r_epoch, meters = restored
        assert r_epoch == 0
        start = int(meters["gossip_batch"]) + 1
        out["gossip_restored"] = gossip_print(r_state)
        r_state, losses, stale, forced, seen = drive(
            r_state, start, TOTAL_STEPS)
        out.update(losses=losses, start=start, w_staleness=stale,
                   forced=forced, max_seen=seen,
                   gossip_final=gossip_print(r_state),
                   mem_final=fingerprint(r_state.memory))

    if sink is not None:
        sink.close()
    print("RESULT:" + json.dumps(out), flush=True)

    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(f"gossip_{phase}_done")
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()

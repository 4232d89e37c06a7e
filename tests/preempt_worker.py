"""Worker program for the 2-process kill-and-resume drill
(tests/test_multiprocess.py::test_kill_and_resume_bitwise_memory).

Three phases, each a separate 2-process ``jax.distributed`` launch over the
same checkpoint directory:

* ``baseline`` — train TOTAL_STEPS uninterrupted; record per-step losses
  and a per-process sha256 fingerprint of the compressor memory after
  KILL_STEP steps and at the end.
* ``run`` — train with a :class:`PreemptionHandler` installed; the parent
  arms ``DGC_FAULTS=kill@3`` on process 1 only, so that process SIGTERMs
  itself after step 3. :func:`agree_preempt` spreads the verdict, both
  processes break on the SAME step boundary, write one collective
  emergency checkpoint (atomic tmp+rename) with the batch cursor, and exit
  0 through :func:`clean_shutdown`.
* ``resume`` — restore the emergency checkpoint, fingerprint the restored
  memory (must be bitwise the baseline's at the kill point), and train the
  remaining steps — losses must match the baseline trajectory exactly.

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

TOTAL_STEPS = 6
KILL_STEP = 3          # completed steps before the injected SIGTERM


def main():
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    coord = sys.argv[3]
    workdir = sys.argv[4]
    phase = sys.argv[5]
    assert phase in ("baseline", "run", "resume"), phase

    from dgc_tpu.parallel.multihost import (host_local_to_global,
                                            initialize_multihost)

    # same shared persistent compile cache as multiproc_worker.py (this
    # worker's step function is built identically, so it reuses the entry)
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
    from dgc_tpu.resilience import faults, preempt
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
    setup = make_flat_setup(v, dist)
    state = shard_state(make_flat_state(v, dist, setup, W), mesh,
                        dist_opt=dist)
    step_fn = build_train_step(apply_fn, dist, mesh, donate=False,
                               flat=setup)

    bs = 4

    def batch(i):
        """Deterministic per-step global batch — identical in every phase,
        so an uninterrupted run and a kill+resume run see the same data."""
        rng = np.random.RandomState(1000 + i)
        im = rng.randn(W * bs, 16, 16, 3).astype(np.float32)
        lb = rng.randint(0, 10, W * bs).astype(np.int32)
        return (host_local_to_global(im, mesh),
                host_local_to_global(lb, mesh))

    def fingerprint(tree):
        """sha256 over this process's addressable shard bytes, in a
        deterministic (path, shard-index) order — equal fingerprints mean
        bitwise-equal per-worker state on this process."""
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

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt_preempt"), keep=3)
    out = {"proc": proc_id, "phase": phase}

    if phase == "baseline":
        losses = []
        for i in range(TOTAL_STEPS):
            im, lb = batch(i)
            state, m = step_fn(state, im, lb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            jax.block_until_ready(state)
            if i + 1 == KILL_STEP:
                out["mem_at_kill"] = fingerprint(state.memory)
        out.update(losses=losses, mem_final=fingerprint(state.memory))

    elif phase == "run":
        handler = preempt.PreemptionHandler()
        losses, preempt_at = [], None
        for i in range(TOTAL_STEPS):
            # step-boundary agreement: the killed process's local flag
            # becomes everyone's verdict, so both enter the collective
            # emergency save on the same step
            if preempt.agree_preempt(handler.requested):
                preempt_at = i - 1
                break
            im, lb = batch(i)
            state, m = step_fn(state, im, lb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            jax.block_until_ready(state)
            faults.maybe_kill(i + 1)     # SIGTERM self at the armed step
        assert preempt_at == KILL_STEP - 1, \
            f"expected preemption after step {KILL_STEP}, got {preempt_at}"
        preempt.emergency_save(ckpt, 0, state,
                               {"preempt_batch": preempt_at})
        out.update(losses=losses, preempt_at=preempt_at,
                   mem_saved=fingerprint(state.memory),
                   signum=handler.signum)
        handler.uninstall()

    else:  # resume
        restored = ckpt.restore(state)
        assert restored is not None, "emergency checkpoint must restore"
        r_state, r_epoch, meters = restored
        assert r_epoch == 0
        start = int(meters["preempt_batch"]) + 1
        out["mem_restored"] = fingerprint(r_state.memory)
        losses = []
        for i in range(start, TOTAL_STEPS):
            im, lb = batch(i)
            r_state, m = step_fn(r_state, im, lb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            jax.block_until_ready(r_state)
        out.update(losses=losses, start=start,
                   mem_final=fingerprint(r_state.memory))

    print("RESULT:" + json.dumps(out), flush=True)

    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices(f"preempt_{phase}_done")
    if phase == "run":
        preempt.clean_shutdown()     # the path a preempted trainer takes
    else:
        jax.distributed.shutdown()


if __name__ == "__main__":
    main()

"""Worker program for the elastic-restart drills
(tests/test_multiprocess.py::test_elastic_cross_topology_resume and
tests/test_elastic.py::test_supervisor_relaunch_smoke).

Single-process launches over a configurable fake-device count (the world
size W comes from argv BEFORE jax imports, so each phase can run a
different topology against the same checkpoint directory):

* ``baseline W`` — train TOTAL_STEPS uninterrupted at W workers on a
  learnable synthetic task; record the per-step losses.
* ``save W`` — train SAVE_STEPS at W workers and write a checkpoint with
  the ``_topology`` record.
* ``resume W from_world`` — restore the ``save`` phase's checkpoint at a
  DIFFERENT world size with ``elastic=True``; verify per-parameter
  residual + momentum gradient mass against an independent NumPy oracle
  computed from the RAW old-world state (fold each worker's pending
  transmit record, then sum — exact up to fp addition order); train the
  remaining steps with the SAME global batch.
* ``supervised W`` — one launch of the supervisor smoke child: train
  under a PreemptionHandler with ``DGC_FAULTS=kill@3`` armed by the
  parent; the first launch SIGTERMs itself after step 3, emergency-saves
  (topology stamped), appends a result line, and exits 75 so
  scripts/supervise.py relaunches; the relaunch resumes at step 4 and
  completes.

Each phase prints one ``RESULT:<json>`` line (the ``supervised`` phase
also appends it to ``<workdir>/results.jsonl``, one line per launch).
"""

import json
import os
import sys

NDEV = int(sys.argv[2])
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={NDEV}")

import jax

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

TOTAL_STEPS = 24
SAVE_STEPS = 10
SUP_TOTAL = 6
SUP_KILL = 3
GLOBAL_BS = 16          # fixed across world sizes: same data every phase


def main():
    phase = sys.argv[1]
    workdir = sys.argv[3]
    assert phase in ("baseline", "save", "resume", "supervised"), phase

    from dgc_tpu.utils import compile_cache
    compile_cache.enable()

    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh

    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         dgc_sgd)
    from dgc_tpu.parallel.multihost import host_local_to_global
    from dgc_tpu.resilience import elastic, faults, preempt
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.training.checkpoint import CheckpointManager
    from dgc_tpu.utils.pytree import named_flatten

    W = len(jax.devices())
    assert W == NDEV, (W, NDEV)
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

    comp = DGCCompressor(0.1, memory=DGCSGDMemory(momentum=0.9))
    named, _ = named_flatten(v["params"])
    comp.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist = DistributedOptimizer(dgc_sgd(0.15, momentum=0.9), comp,
                                world_size=W)
    setup = make_flat_setup(v, dist)
    state = shard_state(make_flat_state(v, dist, setup, W), mesh,
                        dist_opt=dist)
    step_fn = build_train_step(apply_fn, dist, mesh, donate=False,
                               flat=setup)

    # learnable task (the tests/test_convergence.py pattern): class
    # prototypes + noise, so the loss trajectory genuinely descends and
    # "resumed training still converges" is a meaningful assertion
    protos = np.random.RandomState(7).randn(10, 16, 16, 3) * 1.5

    def batch(i):
        """Deterministic GLOBAL batch for step i — world-size
        independent, so every topology sees the same data sequence."""
        rng = np.random.RandomState(1000 + i)
        lb = rng.randint(0, 10, GLOBAL_BS).astype(np.int32)
        im = (protos[lb] + 0.2 * rng.randn(GLOBAL_BS, 16, 16, 3)
              ).astype(np.float32)
        return (host_local_to_global(im, mesh),
                host_local_to_global(lb, mesh))

    def train_range(state, lo, hi):
        losses = []
        for i in range(lo, hi):
            im, lb = batch(i)
            state, m = step_fn(state, im, lb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            jax.block_until_ready(state)
        return state, losses

    # ----------------------------------------------------------------- #
    # independent NumPy oracle over the flat engine's memory layout
    # ----------------------------------------------------------------- #

    layout = setup.layout
    T = int(setup.engine.T)

    def oracle_keep(bits, total):
        """Bit-unpack straight from the documented layout (flat position
        p -> word (p // 4096) * 128 + (p % 128), bit (p // 128) % 32),
        written differently from elastic.keep_from_bits_np on purpose."""
        bits = np.asarray(bits).astype(np.uint32)
        p = np.arange(total)
        word = (p // 4096) * 128 + (p % 128)
        bit = (p // 128) % 32
        keep = ((bits[word] >> bit.astype(np.uint32)) & 1) == 0
        return keep

    def masses(mem_workers, momentum_masking=True):
        """Per-parameter momentum/velocity gradient mass summed over
        workers, pending transmit records folded, accumulated in f64."""
        out = {}
        nw = len(mem_workers["momentums_c"])
        folded_m = np.zeros(T, np.float64)
        folded_v = np.zeros(T, np.float64)
        for w in range(nw):
            keep = oracle_keep(mem_workers["sent_bits"][w], T)
            folded_v += np.where(keep,
                                 mem_workers["velocities_c"][w], 0.0)
            mk = keep if momentum_masking else np.ones(T, bool)
            folded_m += np.where(mk, mem_workers["momentums_c"][w], 0.0)
        dense_m = np.asarray(mem_workers["momentums_d"],
                             np.float64).sum(axis=0)
        dense_v = np.asarray(mem_workers["velocities_d"],
                             np.float64).sum(axis=0)
        for n in layout.names:
            off, size = layout.offsets[n], layout.sizes[n]
            if n in layout.compressed_names:
                m, vv = folded_m[off:off + size], folded_v[off:off + size]
            else:
                m = dense_m[off - T:off - T + size]
                vv = dense_v[off - T:off - T + size]
            out[n] = [float(m.sum()), float(vv.sum())]
        return out

    def host_memory(mem):
        return {k: np.asarray(jax.device_get(x)) for k, x in mem.items()}

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt_elastic"), keep=3)
    out = {"phase": phase, "world": W}

    if phase == "baseline":
        state, losses = train_range(state, 0, TOTAL_STEPS)
        out["losses"] = losses

    elif phase == "save":
        state, losses = train_range(state, 0, SAVE_STEPS)
        topo = {"process_count": 1, "world": W, "num_local_workers": 1}
        ckpt.save(0, state, {"saved_steps": SAVE_STEPS}, topology=topo)
        out.update(losses=losses,
                   mass=masses(host_memory(state.memory)))

    elif phase == "resume":
        from_world = int(sys.argv[4])
        topo = {"process_count": 1, "world": W, "num_local_workers": 1}
        # raw restore at the OLD world: the oracle's ground truth
        raw_tmpl = elastic.with_world(state, from_world)
        raw = ckpt.restore(raw_tmpl)
        assert raw is not None, "save-phase checkpoint must restore"
        raw_mass = masses(host_memory(raw[0].memory))
        # the real elastic restore under the NEW topology
        restored = ckpt.restore(state, topology=topo, elastic=True,
                                elastic_opts=comp.elastic_reshard_opts())
        assert restored is not None
        r_state, r_epoch, meters = restored
        assert meters["_elastic"]["from_world"] == from_world
        assert meters["_elastic"]["to_world"] == W
        new_mass = masses(host_memory(r_state.memory))
        # per-parameter gradient mass conserved (exact up to fp addition)
        mass_rel = 0.0
        for n in layout.names:
            for a, b in zip(raw_mass[n], new_mass[n]):
                denom = max(abs(a), abs(b), 1e-6)
                mass_rel = max(mass_rel, abs(a - b) / denom)
        assert mass_rel < 1e-5, f"gradient mass not conserved: {mass_rel}"
        if W > from_world:
            # grow (1:k split): child c%k==0 inherits parent c//k
            # BITWISE (sent_bits included); siblings start zeroed —
            # their residual mass is zero and their keep mask is all-keep
            k = W // from_world
            raw_mem = host_memory(raw[0].memory)
            new_mem = host_memory(r_state.memory)
            for mkey, new_rows in new_mem.items():
                old_rows = raw_mem[mkey]
                for c in range(W):
                    if c % k == 0:
                        np.testing.assert_array_equal(
                            new_rows[c], old_rows[c // k],
                            err_msg=f"{mkey}[{c}] not bitwise-inherited")
                    else:
                        assert not np.any(new_rows[c]), \
                            f"{mkey}[{c}] sibling not zeroed"
            # BN stats: every child copies its parent's row exactly
            for pth, leaf in jax.tree_util.tree_flatten_with_path(
                    raw[0].batch_stats)[0]:
                new_leaf = r_state.batch_stats
                for key in pth:
                    new_leaf = new_leaf[key.key]
                old = np.asarray(jax.device_get(leaf), np.float64)
                new = np.asarray(jax.device_get(new_leaf), np.float64)
                for c in range(W):
                    np.testing.assert_array_equal(new[c], old[c // k])
        else:
            # BN stats: each child row is the mean of its parent group
            k = from_world // W
            for pth, leaf in jax.tree_util.tree_flatten_with_path(
                    raw[0].batch_stats)[0]:
                new_leaf = r_state.batch_stats
                for key in pth:
                    new_leaf = new_leaf[key.key]
                old = np.asarray(jax.device_get(leaf), np.float64)
                new = np.asarray(jax.device_get(new_leaf), np.float64)
                for c in range(W):
                    np.testing.assert_allclose(
                        new[c], old[c * k:(c + 1) * k].mean(axis=0),
                        rtol=1e-5, atol=1e-6)
        r_state = shard_state(jax.tree.map(jnp.asarray, r_state), mesh,
                              dist_opt=dist)
        r_state, losses = train_range(r_state, SAVE_STEPS, TOTAL_STEPS)
        out.update(losses=losses, start=SAVE_STEPS, mass_rel=mass_rel,
                   mass=new_mass)

    else:  # supervised (one launch under scripts/supervise.py)
        results_path = os.path.join(workdir, "results.jsonl")
        topo = {"process_count": 1, "world": W, "num_local_workers": 1}
        sup_ckpt = CheckpointManager(os.path.join(workdir, "ckpt_sup"),
                                     keep=3)
        start = 0
        restored = sup_ckpt.restore(state, topology=topo, elastic=True) \
            if sup_ckpt.latest_epoch() is not None else None
        if restored is not None:
            r_state, _, meters = restored
            state = shard_state(jax.tree.map(jnp.asarray, r_state), mesh,
                                dist_opt=dist)
            start = int(meters["preempt_batch"]) + 1
        handler = preempt.PreemptionHandler()
        losses, preempt_at = [], None
        for i in range(start, SUP_TOTAL):
            if preempt.agree_preempt(handler.requested):
                preempt_at = i - 1
                break
            im, lb = batch(i)
            state, m = step_fn(state, im, lb, jax.random.PRNGKey(i))
            losses.append(float(m["loss"]))
            jax.block_until_ready(state)
            faults.maybe_kill(i + 1)   # global step count: no re-kill
        out.update(losses=losses, start=start)
        if preempt_at is not None:
            preempt.emergency_save(sup_ckpt, 0, state,
                                   {"preempt_batch": preempt_at},
                                   topology=topo)
            out.update(preempt_at=preempt_at, completed=False)
        else:
            out["completed"] = True
        handler.uninstall()
        with open(results_path, "a") as f:
            f.write(json.dumps(out) + "\n")
        print("RESULT:" + json.dumps(out), flush=True)
        sys.exit(75 if preempt_at is not None else 0)

    print("RESULT:" + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

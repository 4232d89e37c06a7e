"""Worker program for the 2-process ``jax.distributed`` CPU test
(tests/test_multiprocess.py) — the multi-host execution path the reference
exercised with 8-256 MPI ranks (/root/reference/train.py:99-100,244-264).

Each process: initialize the process group over gRPC, build a mesh spanning
BOTH processes' fake CPU devices, assemble the global batch from its local
shard (``host_local_to_global``), run flat DGC train steps, save a
checkpoint collectively (orbax distributed write, coordinator-only
bookkeeping), restore it, and verify the restored state matches. Prints
one JSON result line prefixed RESULT: for the parent to parse.
"""

import json
import os
import sys

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"

import jax

jax.config.update("jax_platforms", "cpu")
# pre-0.5 JAX defaults CPU cross-process collectives to "none" ("Multiprocess
# computations aren't implemented on the CPU backend"); newer releases
# default to gloo already
if "jax_cpu_collectives_implementation" in jax.config.values:
    jax.config.update("jax_cpu_collectives_implementation", "gloo")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def main():
    proc_id = int(sys.argv[1])
    num_procs = int(sys.argv[2])
    coord = sys.argv[3]
    workdir = sys.argv[4]

    from dgc_tpu.parallel.multihost import (
        host_local_to_global, initialize_multihost, is_coordinator)

    # persistent compilation cache SHARED by both processes (and across
    # tests — JAX_COMPILATION_CACHE_DIR or <checkout>/.jax_cache, not the
    # per-test dir): on a small/loaded host, cold-compiling the train
    # step in both processes can outlast the coordination service's
    # 300 s shutdown barrier when one process is starved — the cache
    # removes that variance (warm runs: ~30 s total)
    from dgc_tpu.utils import compile_cache
    compile_cache.enable()

    os.environ["JAX_COORDINATOR_ADDRESS"] = coord
    os.environ["JAX_NUM_PROCESSES"] = str(num_procs)
    os.environ["JAX_PROCESS_ID"] = str(proc_id)
    # cold-cache runs compile the train steps from scratch (minutes on a
    # loaded 1-core host) and the two processes' compile times diverge;
    # the default 300 s shutdown barrier / 100 s heartbeat then kill the
    # process that finished first while its peer is still compiling
    assert initialize_multihost(initialization_timeout=600,
                                heartbeat_timeout_seconds=600,
                                shutdown_timeout_seconds=1200) is True
    assert jax.process_count() == num_procs
    assert is_coordinator() == (proc_id == 0)

    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn
    from jax.sharding import Mesh

    from dgc_tpu import (DGCCompressor, DGCSGDMemory, DistributedOptimizer,
                         dgc_sgd)
    from dgc_tpu.training import (build_train_step, make_flat_setup,
                                  make_flat_state, shard_state)
    from dgc_tpu.training.checkpoint import CheckpointManager
    from dgc_tpu.utils.logging import MetricWriter
    from dgc_tpu.utils.pytree import named_flatten

    W = len(jax.devices())          # 8 global (4 per process)
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

    # every process materializes the full host batch; host_local_to_global
    # takes each process's local slice (the DistributedSampler role)
    rng = np.random.RandomState(7)
    bs = 4
    images_h = rng.randn(W * bs, 16, 16, 3).astype(np.float32)
    labels_h = rng.randint(0, 10, W * bs).astype(np.int32)
    images = host_local_to_global(images_h, mesh)
    labels = host_local_to_global(labels_h, mesh)

    # NOTE on the block_until_ready calls below: syncing only the loss
    # scalar leaves the step's exchange collectives in flight on the async
    # CPU runtime; if the host then starts a collective sequence of its own
    # (shard_state / checkpoint device_puts issue assert_equal broadcasts),
    # the two processes can issue gloo ops in different orders on the shared
    # communicator and die with "op.preamble.length <= op.nbytes". Fully
    # draining the device stream before every host-driven collective
    # sequence removes that race.
    losses = []
    for i in range(3):
        state, m = step_fn(state, images, labels, jax.random.PRNGKey(i))
        losses.append(float(m["loss"]))
    jax.block_until_ready(state)

    # metric writer: only the coordinator creates files
    writer = MetricWriter(os.path.join(workdir, "logs"))
    writer.add_scalar("loss", losses[-1], 3)
    writer.close()

    ckpt = CheckpointManager(os.path.join(workdir, "ckpt"), keep=3)
    ckpt.save(0, state, {"top1": 12.5}, best=True)

    # one more step so the live state diverges from the saved one
    state2, _ = step_fn(state, images, labels, jax.random.PRNGKey(99))
    jax.block_until_ready(state2)
    restored = ckpt.restore(state2)
    assert restored is not None
    r_state, r_epoch, meters = restored
    assert r_epoch == 0 and abs(meters["top1"] - 12.5) < 1e-6

    # restored params equal the saved (pre-divergence) params, not state2's
    def gather(x):
        # params are replicated: any local shard holds the full value
        return np.asarray(x.addressable_data(0))

    saved_p = gather(state.params)
    rest_p = gather(r_state.params)
    div_p = gather(state2.params)
    np.testing.assert_allclose(rest_p, saved_p, rtol=1e-6)
    assert not np.allclose(rest_p, div_p)

    # resumed state trains on
    state3, m3 = step_fn(r_state, images, labels, jax.random.PRNGKey(5))
    assert np.isfinite(float(m3["loss"]))
    jax.block_until_ready((state3, m3))

    # --- two-tier hierarchical exchange across the REAL process boundary:
    # each process is one "host" row (its 4 local devices form the dense
    # tier); the sparse DGC gather crosses the gRPC/DCN link only ---
    from dgc_tpu.parallel import make_two_tier_mesh
    mesh_tt = make_two_tier_mesh(num_procs, W // num_procs)
    assert [d.process_index for d in mesh_tt.devices[proc_id]] == \
        [proc_id] * (W // num_procs), "mesh rows must align with processes"
    comp_tt = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
    comp_tt.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist_tt = DistributedOptimizer(
        dgc_sgd(0.1, momentum=0.9), comp_tt, axis_name="hosts",
        world_size=W, local_axis_name="local", local_size=W // num_procs)
    setup_tt = make_flat_setup(v, dist_tt)
    state_tt = shard_state(make_flat_state(v, dist_tt, setup_tt, W),
                           mesh_tt, dist_tt.data_axes, dist_opt=dist_tt)
    step_tt = build_train_step(apply_fn, dist_tt, mesh_tt, donate=False,
                               flat=setup_tt)
    images_tt = host_local_to_global(images_h, mesh_tt)
    labels_tt = host_local_to_global(labels_h, mesh_tt)
    tt_losses = []
    for i in range(2):
        state_tt, m = step_tt(state_tt, images_tt, labels_tt,
                              jax.random.PRNGKey(i))
        tt_losses.append(float(m["loss"]))
    assert all(np.isfinite(tl) for tl in tt_losses)
    jax.block_until_ready(state_tt)

    # --- 4-host x 2-local two-tier mesh (ISSUE 2 satellite): the hosts
    # (sparse) axis now CROSSES the process boundary — rows 0-1 live in
    # proc 0, rows 2-3 in proc 1 — so the dense local tier stays inside a
    # process while the sparse gather spans both intra- and inter-process
    # "hosts". Per-node memory semantics: the local tier psums the gradient
    # before compression, so the two devices of one row must hold bitwise-
    # identical error-feedback memory at every step, including across
    # save/resume. ---
    hosts4, local2 = 4, 2
    mesh_t4 = make_two_tier_mesh(hosts4, local2)
    rows_per_proc = hosts4 // num_procs
    for r in range(hosts4):
        owner = r // rows_per_proc
        assert [d.process_index for d in mesh_t4.devices[r]] == \
            [owner] * local2, "rows must pack per process in order"
    comp_t4 = DGCCompressor(0.05, memory=DGCSGDMemory(momentum=0.9))
    comp_t4.initialize((n, p) for n, p in named.items() if p.ndim > 1)
    dist_t4 = DistributedOptimizer(
        dgc_sgd(0.1, momentum=0.9), comp_t4, axis_name="hosts",
        world_size=W, local_axis_name="local", local_size=local2)
    setup_t4 = make_flat_setup(v, dist_t4)
    state_t4 = shard_state(make_flat_state(v, dist_t4, setup_t4, W),
                           mesh_t4, dist_t4.data_axes, dist_opt=dist_t4)
    # telemetry riding the same program across the real process boundary
    step_t4 = build_train_step(apply_fn, dist_t4, mesh_t4, donate=False,
                               flat=setup_t4, telemetry=True)
    images_t4 = host_local_to_global(images_h, mesh_t4)
    labels_t4 = host_local_to_global(labels_h, mesh_t4)

    def mem_pair_dev(mem):
        """Max |memory(row dev 0) - memory(row dev 1)| over all per-worker
        leaves — 0.0 iff every host row's local pair is bitwise equal."""
        leaves = [l for l in jax.tree.leaves(mem)
                  if hasattr(l, "shape") and l.ndim >= 1
                  and l.shape[0] == W]
        assert leaves, "memory has no per-worker leaves"

        def f(*ls):
            d = jnp.zeros((), jnp.float32)
            for l in ls:
                r = l.reshape(hosts4, local2, -1).astype(jnp.float32)
                d = jnp.maximum(d, jnp.max(jnp.abs(r[:, 0] - r[:, 1])))
            return d
        return float(jax.jit(f)(*leaves))

    t4_losses, t4_mem_dev = [], []
    telem = None
    for i in range(2):
        state_t4, m = step_t4(state_t4, images_t4, labels_t4,
                              jax.random.PRNGKey(i))
        jax.block_until_ready(state_t4)
        t4_losses.append(float(m["loss"]))
        t4_mem_dev.append(mem_pair_dev(state_t4.memory))
        telem = m["telemetry"]
    assert all(np.isfinite(tl) for tl in t4_losses)
    t4_payload = float(np.asarray(telem["payload_elems"]))
    assert np.isfinite(float(np.asarray(telem["grad_norm"])))

    # save/resume preserves the per-node memory pairing across the
    # process boundary: save, diverge one step, restore, verify
    ckpt_t4 = CheckpointManager(os.path.join(workdir, "ckpt_tt"), keep=1)
    ckpt_t4.save(0, state_t4, {"top1": 1.0}, best=False)
    state_t4b, _ = step_t4(state_t4, images_t4, labels_t4,
                           jax.random.PRNGKey(77))
    jax.block_until_ready(state_t4b)
    restored_t4 = ckpt_t4.restore(state_t4b)
    assert restored_t4 is not None
    r_state_t4 = restored_t4[0]

    def mem_max_diff(ma, mb):
        la = [l for l in jax.tree.leaves(ma) if hasattr(l, "shape")]
        lb = [l for l in jax.tree.leaves(mb) if hasattr(l, "shape")]

        def f(*ls):
            n = len(ls) // 2
            return jnp.max(jnp.stack([
                jnp.max(jnp.abs(a.astype(jnp.float32) -
                                b.astype(jnp.float32)))
                for a, b in zip(ls[:n], ls[n:])]))
        return float(jax.jit(f)(*(la + lb)))

    t4_restore_diff = mem_max_diff(r_state_t4.memory, state_t4.memory)
    t4_restored_pair_dev = mem_pair_dev(r_state_t4.memory)
    state_t4c, m4c = step_t4(r_state_t4, images_t4, labels_t4,
                             jax.random.PRNGKey(5))
    jax.block_until_ready((state_t4c, m4c))
    t4_resumed_pair_dev = mem_pair_dev(state_t4c.memory)
    assert np.isfinite(float(m4c["loss"]))

    print("RESULT:" + json.dumps({
        "proc": proc_id,
        "losses": losses,
        "tt_losses": tt_losses,
        "resume_loss": float(m3["loss"]),
        "coordinator": is_coordinator(),
        "t4_losses": t4_losses,
        "t4_mem_pair_dev": t4_mem_dev,
        "t4_payload": t4_payload,
        "t4_restore_diff": t4_restore_diff,
        "t4_restored_pair_dev": t4_restored_pair_dev,
        "t4_resumed_pair_dev": t4_resumed_pair_dev,
    }), flush=True)

    # align exits: the coordinator's extra file bookkeeping must not make
    # the other process hit the jax shutdown barrier alone and time out
    from jax.experimental import multihost_utils
    multihost_utils.sync_global_devices("test_done")
    jax.distributed.shutdown()


if __name__ == "__main__":
    main()

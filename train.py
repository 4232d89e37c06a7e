"""Training harness — parity with the reference CLI
(/root/reference/train.py): composable config modules + dotted overrides,
DGC wiring over only dim>1 parameters, LR scaling + warm-up, per-epoch
eval with Sum-reduced meters, checkpoint save/resume/rotate including the
compression memory, and best-metric tracking.

Usage (mirrors the reference README):
    python train.py --configs configs/cifar/resnet20.py configs/dgc/wm5.py \
        [--train.num_epochs 500] [--suffix .e500] [--cpu_mesh 8]

TPU-native differences by design:
* one process drives the whole mesh (no horovodrun/mpirun; `--cpu_mesh N`
  forces an N-fake-device CPU mesh for machines without TPUs);
* the hot loop is one jitted step (see dgc_tpu.training.step) — a compress-
  ratio change from the warm-up schedule rebuilds it (≤ warmup_epochs + 1
  compiles per run);
* checkpoints are one sharded-state directory per epoch instead of one file
  per rank.
"""

import argparse
import itertools
import os
import sys
import time

import numpy as np


def get_save_path(*config_paths, prefix="runs"):
    """Experiment directory from the config-path set
    (reference train.py:378-403): configs/cifar/resnet20.py + configs/dgc/wm5.py
    → runs/cifar.resnet20+dgc.wm5. Unlike the reference, sibling groups are
    joined WITHOUT surrounding brackets: tensorstore (orbax's storage layer)
    treats ``[...]`` in paths as glob patterns and cannot re-open such
    checkpoints."""
    memo = {}
    for c in config_paths:
        node = memo
        c = c.replace("configs/", "").replace(".py", "").split("/")
        for m in c:
            node = node.setdefault(m, {})

    def fmt(m):
        parts = []
        for k, v in m.items():
            s = k
            if v:
                s += "." + fmt(v)
            parts.append(s)
        return "+".join(parts)

    return os.path.join(prefix, fmt(memo))


def _narrow_model_dtype(model):
    """The model's sub-4-byte compute dtype, if any (configs/bf16.py sets
    ``model.dtype = bfloat16``): the flat train step then makes ONE narrow
    copy of the parameter buffer per micro-batch instead of letting XLA
    materialize per-consumer weight conversions (training/step.py)."""
    import jax.numpy as jnp

    dt = getattr(model, "dtype", None)
    if dt is not None and jnp.dtype(dt).itemsize < 4:
        return dt
    return None


def drain_loss_log(writer, loss_log, on_loss=None):
    """Convert the epoch's collected device losses in one go.

    The train loop appends ``(num_inputs, device_scalar)`` pairs instead
    of calling ``float()`` per logged step — a per-step conversion blocks
    the dispatch pipeline behind every enqueued step. Draining here costs
    one host sync per epoch, after all steps are in flight.

    ``on_loss`` sees each converted value in order (the nonfinite-streak
    breaker taps in here: the drain is the only place losses become
    host floats without adding a sync)."""
    loss = 0.0
    for at, dev_loss in loss_log:
        loss = float(dev_loss)
        if on_loss is not None:
            on_loss(loss)
        writer.add_scalar("loss/train", loss, at)
    loss_log.clear()
    return loss


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--configs", nargs="+", required=True)
    parser.add_argument("--devices", default="tpu")
    parser.add_argument("--cpu_mesh", type=int, default=0,
                        help="force an N-fake-device CPU mesh (testing)")
    parser.add_argument("--evaluate", action="store_true")
    parser.add_argument("--suffix", default="")
    parser.add_argument("--profile", action="store_true",
                        help="write a device trace of 8 training steps "
                             "(after the first, compiling one) to "
                             "<save_path>/profile")
    parser.add_argument("--trace", action="store_true",
                        help="structured tracing: device-side dgcph.* "
                             "scopes + the in-memory recorder of host "
                             "spans and counts (with --profile: dgc:* "
                             "annotations in the profile, one file, one "
                             "clock; docs/TELEMETRY.md §Tracing); same "
                             "as stacking configs/trace.py")
    parser.add_argument("--elastic", action="store_true",
                        help="allow resuming under a different world size: "
                             "reshard the per-worker DGC state "
                             "(docs/RESILIENCE.md §Elastic restart); same "
                             "as stacking configs/elastic.py")
    parser.add_argument("--autotune", action="store_true",
                        help="online exchange replanning: plan per-bucket "
                             "wire regimes, refit the link model from "
                             "measured step/bucket costs at epoch "
                             "boundaries, and rebuild the step only when "
                             "the plan key changes (docs/PLANNER.md "
                             "§Autotuning); same as stacking "
                             "configs/autotune.py")
    parser.add_argument("--adaptive", action="store_true",
                        help="straggler-adaptive exchange: a flagged "
                             "straggler transmits a smaller fraction of "
                             "its per-bucket quota (withheld mass stays in "
                             "the error-feedback residual) so the cohort "
                             "stops paying its full lag "
                             "(docs/RESILIENCE.md §Adaptive exchange); "
                             "needs the fleet taps (configs/fleet.py); "
                             "same as stacking configs/adaptive.py or "
                             "setting DGC_ADAPTIVE=1")
    args, opts = parser.parse_known_args(argv)

    on_cpu = bool(args.cpu_mesh or args.devices == "cpu")
    if on_cpu:
        n = args.cpu_mesh or 1
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_force_host_platform_device_count" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_force_host_platform_device_count={n}").strip()
    import jax
    from dgc_tpu.utils import compile_cache
    compile_cache.enable()
    if on_cpu:
        jax.config.update("jax_platforms", "cpu")
        _multihost = False
    else:
        # multi-host wiring (TPU pods / Slurm; no-op single host) must
        # precede ANY backend use — even a jax.process_index() in a log
        # line initializes the local backend and breaks
        # jax.distributed.initialize
        from dgc_tpu.parallel.multihost import initialize_multihost
        _multihost = initialize_multihost()
        # the CPU backend is a different program (jnp references instead
        # of the Pallas kernels): training on it must be asked for by
        # name, never fallen into because no chip was found
        from dgc_tpu.utils.device import require_tpu
        require_tpu("train.py (without --cpu_mesh N or --devices cpu)")
    import jax.numpy as jnp

    from dgc_tpu.compression.flat import ParamLayout
    from dgc_tpu.optim import DistributedOptimizer
    from dgc_tpu.parallel import make_mesh
    from dgc_tpu.training import (
        build_eval_step,
        build_train_step,
        make_flat_setup,
        make_flat_state,
        make_lr_schedule,
        shard_state,
    )
    from dgc_tpu.training.checkpoint import CheckpointManager
    from dgc_tpu.utils.config import Config, configs
    from dgc_tpu.utils.logging import MetricWriter, printr
    from dgc_tpu.utils.pytree import named_flatten

    ##################
    # Update configs #
    ##################

    printr(f"==> loading configs from {args.configs}")
    Config.update_from_modules(*args.configs)
    Config.update_from_arguments(*opts)

    if _multihost:
        printr(f"[multihost] {jax.process_count()} processes, "
               f"{len(jax.devices())} devices")

    seed = configs.get("seed", 0) or 0
    np.random.seed(seed)
    from dgc_tpu.parallel.multihost import host_local_to_global

    configs.train.num_batches_per_step = configs.train.get(
        "num_batches_per_step", 1)

    # num_local_workers > 1 selects the two-tier hierarchical exchange:
    # dense aggregation over ICI within each group of that many workers,
    # sparse DGC over DCN across groups — the real form of the reference's
    # "#Sparsified Nodes < #GPUs" regime (README.md:126-128,133-134, which
    # it simulates via num_batches_per_step). On a TPU pod set it to the
    # per-host chip count (e.g. --train.num_local_workers 8 on v5e-8 hosts).
    num_local = int(configs.train.get("num_local_workers", 1) or 1)
    if num_local > 1:
        from dgc_tpu.parallel import make_two_tier_mesh
        n_dev = args.cpu_mesh if args.cpu_mesh else len(jax.devices())
        if n_dev % num_local:
            raise SystemExit(
                f"--train.num_local_workers {num_local} must divide the "
                f"device count {n_dev}")
        # the local tier carries the FULL dense gradient psum every step —
        # it must stay on ICI. A value that makes mesh rows span processes
        # would put that psum on DCN (performance-inverted, silently).
        if (jax.process_count() > 1
                and jax.local_device_count() % num_local):
            raise SystemExit(
                f"--train.num_local_workers {num_local} must divide the "
                f"per-process device count {jax.local_device_count()} on "
                "multi-host runs, or the dense tier would cross hosts")
        mesh = make_two_tier_mesh(n_dev // num_local, num_local)
        axis = tuple(mesh.axis_names)
    else:
        mesh = make_mesh(args.cpu_mesh if args.cpu_mesh else None)
        axis = mesh.axis_names[0]
    world = mesh.devices.size

    # elastic restart (configs/elastic.py or --elastic, docs/RESILIENCE.md):
    # a world-size mismatch at restore reshards the per-worker state
    # instead of failing fast, and the batch geometry below compensates
    ecfg = configs.train.get("elastic", None)
    elastic_on = bool(args.elastic or (ecfg and ecfg.get("enabled", False)))
    elastic_preserve = bool(ecfg.get("preserve_global_batch", True)) \
        if ecfg else True

    # two-tier runs get their own experiment dir: the error-feedback memory
    # has per-NODE semantics there — resuming a flat run's per-worker
    # residuals (same shapes!) would silently corrupt momentum correction.
    # Elastic runs drop the per-world suffix: every topology of the run
    # must share one checkpoint lineage or there is nothing to reshard.
    tier_tag = f".tt{num_local}" if num_local > 1 else ""
    world_tag = ".npE" if elastic_on else f".np{world}"
    configs.train.save_path = (get_save_path(*args.configs)
                               + f"{args.suffix}{tier_tag}{world_tag}")
    printr(f"[train.save_path] = {configs.train.save_path}")
    ckpt_dir = os.path.join(configs.train.save_path, "checkpoints")
    ckpt = CheckpointManager(ckpt_dir, keep=3)

    # degraded-mode batch geometry: the saved topology must be known
    # BEFORE the global batch and LR are derived — a shrunk cohort raises
    # num_batches_per_step so nbps * world (hence the global batch, the
    # scaled LR, steps_per_epoch, and any mid-epoch preempt cursor) is
    # preserved exactly
    elastic_pending = None
    if elastic_on:
        from dgc_tpu.resilience import elastic as _elastic
        saved_topo = ckpt.saved_topology()
        if saved_topo is not None and int(saved_topo["world"]) != world:
            new_nbps, note = _elastic.resolve_batch_geometry(
                int(saved_topo["world"]), world,
                configs.train.num_batches_per_step,
                preserve=elastic_preserve)
            if note:
                printr(f"[elastic] {note}")
            configs.train.num_batches_per_step = new_nbps
    printr(configs)

    ###########################################################
    # Dataset, model, optimizer, compression, train/eval step #
    ###########################################################

    printr(f'\n==> creating dataset "{configs.dataset}"')
    dataset = configs.dataset()
    nbps = configs.train.num_batches_per_step
    bs = configs.train.batch_size
    global_batch = world * nbps * bs
    eval_batch = world * bs

    printr(f'\n==> creating model "{configs.model}"')
    model = configs.model()
    rng = jax.random.PRNGKey(seed)
    # one example of the dataset's kind: a row of token ids, or an image
    if configs.dataset.get("seq_len") is not None:
        sample = jnp.zeros((1, configs.dataset.seq_len), jnp.int32)
    else:
        sample = jnp.zeros((1, configs.dataset.image_size,
                            configs.dataset.image_size, 3))
    variables = model.init(rng, sample, train=True)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    # Always thread a dropout rng; flax ignores rngs a model doesn't use.
    use_dropout = True

    named_params, _ = named_flatten(params)

    # LR: scale by nbps * world, warm up over warmup_lr_epochs (train.py:115-118)
    from dgc_tpu.data import (Prefetcher, epoch_batches, num_steps_per_epoch,
                              stage_ahead)
    steps_per_epoch = num_steps_per_epoch(
        len(dataset["train"]), global_batch, drop_last=nbps > 1)
    configs.train.base_lr = configs.train.optimizer.lr
    scaled_lr = configs.train.base_lr * nbps * world
    decay = (configs.train.scheduler()
             if "scheduler" in configs.train
             and configs.train.scheduler is not None else None)
    lr_schedule = make_lr_schedule(
        scaled_lr=scaled_lr, world_size=world,
        num_steps_per_epoch=steps_per_epoch,
        warmup_lr_epochs=configs.train.warmup_lr_epochs,
        decay=decay,
        schedule_lr_per_epoch=configs.train.schedule_lr_per_epoch)

    # resilience layer (configs/resilience.py, docs/RESILIENCE.md): in-graph
    # step guards + exchange checksum ride the jitted step; preemption
    # handling and the watchdog are host-side and installed further down
    rcfg = configs.train.get("resilience", None)
    res_on = bool(rcfg and rcfg.get("enabled", False))
    guards_cfg = None
    if res_on:
        from dgc_tpu.resilience import GuardConfig
        guards_cfg = GuardConfig(
            nonfinite=bool(rcfg.get("nonfinite_guard", True)),
            spike_window=int(rcfg.get("spike_window", 0) or 0),
            spike_factor=float(rcfg.get("spike_factor", 10.0)))
    res_checksum = bool(res_on and rcfg.get("checksum", False))

    printr(f'\n==> creating compression "{configs.train.compression}"')
    if configs.train.dgc:
        printr("\n==> initializing dgc compression")
        memory = configs.train.compression.memory()
        compression = configs.train.compression(
            memory=memory, verbose=True,
            **({"checksum": True} if res_checksum else {}))
        compression.initialize(
            (n, p) for n, p in named_params.items() if p.ndim > 1)
    else:
        if res_checksum:
            raise SystemExit("--train.resilience.checksum needs the sparse "
                             "DGC wire (configs with train.dgc = True)")
        compression = configs.train.compression()

    # optimize_bn_separately: BN params get weight_decay 0 (train.py:121-125).
    # On the flat path this is a per-coordinate 0/1 mask over the [P] buffer;
    # BN params are exactly the 'BatchNorm' leaves of the flax tree.
    wd_mask = None
    if configs.train.get("optimize_bn_separately", False):
        layout = ParamLayout.for_compressor(params, compression)
        wd_mask = layout.mask_vector(lambda n: "BatchNorm" not in n)

    printr(f'\n==> creating optimizer "{configs.train.optimizer}"')
    optimizer = configs.train.optimizer(lr=lr_schedule,
                                        weight_decay_mask=wd_mask)

    dist = DistributedOptimizer(
        optimizer, compression, axis_name=mesh.axis_names[0],
        world_size=world,
        local_axis_name=mesh.axis_names[1] if num_local > 1 else None,
        local_size=num_local)

    # online exchange replanning (configs/autotune.py or --autotune,
    # docs/PLANNER.md §Autotuning): the engine gets a per-bucket regime
    # plan up front; measured (bytes, ms) points refit the link model at
    # epoch boundaries and the step is rebuilt ONLY when the plan key
    # changes. Off = none of these paths run (byte-identical program).
    atcfg = configs.train.get("autotune", None)
    autotune_on = bool(args.autotune
                       or (atcfg and atcfg.get("enabled", False)))
    autotuner = None
    if autotune_on and not configs.train.dgc:
        raise SystemExit("--autotune plans the sparse DGC wire "
                         "(configs with train.dgc = True)")

    # straggler-adaptive exchange (configs/adaptive.py, --adaptive, or
    # DGC_ADAPTIVE=1 — the control plane's `adapt` action delivers the env
    # var through the supervisor's --env-file; docs/RESILIENCE.md
    # §Adaptive exchange). Resolved BEFORE the state build: the policy
    # verdict travels in TrainState.adaptive.
    acfg = configs.train.get("adaptive", None)
    adaptive_on = bool(args.adaptive or os.environ.get("DGC_ADAPTIVE")
                       or (acfg and acfg.get("enabled", False)))
    adaptive_cfg = None
    if adaptive_on:
        if not configs.train.dgc:
            raise SystemExit("--adaptive degrades the sparse DGC wire "
                             "(configs with train.dgc = True)")
        _tc = configs.train.get("telemetry", None)
        if not (_tc and _tc.get("enabled", False)
                and _tc.get("fleet", False)):
            raise SystemExit(
                "--adaptive reads the fleet w_clock lane: stack "
                "configs/fleet.py (train.telemetry.enabled + fleet) — "
                "configs/adaptive.py stacks both")
        from dgc_tpu.resilience.adaptive import AdaptiveConfig

        def _ak(k, d):
            return float(acfg.get(k, d)) if acfg else d
        adaptive_cfg = AdaptiveConfig(
            engage_gap_ms=_ak("engage_gap_ms", 100.0),
            min_frac=_ak("min_frac", 0.25),
            ramp_ms=_ak("ramp_ms", 500.0),
            deadline_factor=_ak("deadline_factor", 4.0),
            partial_frac=_ak("partial_frac", 0.02),
            floor_ms=_ak("floor_ms", 1.0))
        printr(f"[adaptive] {adaptive_cfg}")

    # decentralized gossip exchange (configs/gossip.py, docs/RESILIENCE.md
    # §Gossip exchange) — a plan-time OPT-IN: the gossip regime families
    # are never in the default candidate sweep (bounded staleness is a
    # consistency-model change), so the opt-in adds them and the planner
    # still falls back to the synchronous exchange where it models cheaper.
    gcfg = configs.train.get("gossip", None)
    gossip_on = bool(gcfg and gcfg.get("enabled", False))
    gossip_family = None
    gossip_plan = None       # the standing plan threaded into rebuilds
    gossip_kw = {}
    if gossip_on:
        if not configs.train.dgc:
            raise SystemExit("gossip decentralizes the sparse DGC wire "
                             "(configs with train.dgc = True)")
        gossip_family = "gossip_" + str(gcfg.get("topology", "ring"))

        def _gk(key):
            v = gcfg.get(key, None)
            return None if v is None else int(v)
        gossip_kw = dict(gossip_sync_every=_gk("sync_every"),
                         gossip_max_staleness=_gk("max_staleness"))

    flat_setup = make_flat_setup(variables, dist)
    if autotune_on:
        from dgc_tpu.compression.autotune import Autotuner
        from dgc_tpu.compression.planner import REGIMES
        autotuner = Autotuner(
            world=world,
            fabric_out=os.path.join(configs.train.save_path, "fabric.json"),
            min_points=int(atcfg.get("min_points", 2)) if atcfg else 2,
            candidates=(REGIMES + (gossip_family,) if gossip_on
                        else REGIMES),
            **gossip_kw)
        flat_setup = make_flat_setup(
            variables, dist, plan=autotuner.plan_for(flat_setup.engine))
        printr(f"[autotune] fabric {autotuner.fabric.name} "
               f"({autotuner.fabric.gbps:.3g} GB/s) -> "
               f"plan {list(flat_setup.engine.regimes)}")
    elif gossip_on:
        from dgc_tpu.compression.planner import plan_engine
        # kept for every warm-up rebuild: make_flat_setup re-fits it to
        # the fresh bucket geometry (Plan.replan preserves the gossip
        # candidates + schedule knobs)
        gossip_plan = plan_engine(flat_setup.engine, world=world,
                                  candidates=(gossip_family,), **gossip_kw)
        flat_setup = make_flat_setup(variables, dist, plan=gossip_plan)
        eng_plan = flat_setup.engine.plan
        if eng_plan is not None and eng_plan.gossip is not None:
            printr(f"[gossip] {eng_plan.gossip} -> "
                   f"plan {list(flat_setup.engine.regimes)}")
        else:
            printr("[gossip] planner kept the synchronous exchange on "
                   "this fabric (never-lose): no bucket chose "
                   f"{gossip_family}")
    state = shard_state(make_flat_state(variables, dist, flat_setup, world,
                                        guards=guards_cfg,
                                        adaptive=adaptive_cfg),
                        mesh, axis, dist_opt=dist)

    # resume from checkpoint (reference train.py:152-165); the topology
    # record rejects resuming under a different process/mesh/tier setup
    # with a clear error instead of an opaque orbax sharding failure
    topology = {"process_count": jax.process_count(), "world": world,
                "num_local_workers": num_local}
    elastic_opts = None
    if elastic_on:
        elastic_opts = {"per_worker_opt":
                        getattr(dist, "per_worker_opt_state", False)}
        if hasattr(compression, "elastic_reshard_opts"):
            # memory semantics (momentum_masking) come from the live
            # compressor, not a guess over the checkpoint bytes
            elastic_opts.update(compression.elastic_reshard_opts())
    last_epoch, best_metric = -1, None
    restored = ckpt.restore(state, best=args.evaluate, topology=topology,
                            elastic=elastic_on,
                            elastic_opts=elastic_opts) if (
        ckpt.latest_epoch() is not None or args.evaluate) else None
    resume_epoch, resume_batch = None, 0
    if restored is not None:
        host_state, last_epoch, meters = restored
        einfo = meters.pop("_elastic", None)
        if einfo is not None:
            printr(f"[elastic] resharded checkpoint state "
                   f"{einfo['from_world']} -> {einfo['to_world']} workers")
            elastic_pending = dict(einfo, epoch=last_epoch)
        if guards_cfg is not None and host_state.guards is None:
            # pre-resilience checkpoint: re-seed fresh guard counters
            # (deterministic zeros — identical on every process)
            from dgc_tpu.resilience import guard as _guard
            host_state = host_state.replace(
                guards=jax.tree.map(np.asarray,
                                    _guard.init_state(guards_cfg)))
        if jax.process_count() > 1 and einfo is None:
            # multi-host restore already produced global sharded arrays
            # placed by the template's shardings — no re-shard possible
            # (host materialization of non-addressable arrays would throw)
            state = host_state
        else:
            # single-process restore, or an elastic restore (which hands
            # back HOST numpy state: shard_state's multi-process path
            # assembles the global arrays collective-free)
            state = shard_state(jax.tree.map(jnp.asarray, host_state), mesh,
                                axis, dist_opt=dist)
        best_metric = meters.get(configs.train.metric + "_best")
        # an emergency (preemption) checkpoint records the IN-PROGRESS
        # epoch and the last completed batch index: resume re-enters that
        # epoch at the exact next batch instead of replaying it
        pb = meters.get("preempt_batch")
        if pb is not None:
            resume_epoch, resume_batch = last_epoch, int(pb) + 1
            last_epoch -= 1
            printr(f"\n[resumed] mid-epoch {resume_epoch} "
                   f"at batch {resume_batch}, best {best_metric}")
        else:
            printr(f"\n[resumed] epoch {last_epoch}, best {best_metric}")
    else:
        printr("\n==> train from scratch")

    eval_fn = build_eval_step(model.apply, mesh, world, axis=axis,
                              flat=flat_setup)

    def evaluate(state, split="test"):
        meters = {}
        for k, meter_cfg in configs.train.meters.items():
            meters[k.format(split)] = meter_cfg()
        ds = dataset[split]
        totals = None
        for idx in epoch_batches(len(ds), eval_batch, epoch=0,
                                 shuffle=False):
            images, labels = ds.get_batch(idx)
            counts = eval_fn(state.params, state.batch_stats,
                             host_local_to_global(images, mesh),
                             host_local_to_global(labels, mesh))
            # accumulate the count dict on device — int() per batch would
            # serialize eval behind every dispatched step
            totals = counts if totals is None else jax.tree.map(
                jnp.add, totals, counts)
        if totals is not None:
            n = int(totals["count"])
            for meter in meters.values():
                meter.update_counts(int(totals[f"top{meter.k}"]), n)
        return {k: m.compute() for k, m in meters.items()}

    # sanity eval before training (reference train.py:190-193)
    meters = evaluate(state)
    for k, v in meters.items():
        printr(f"[{k}] = {v:.2f}")
    if args.evaluate or last_epoch >= configs.train.num_epochs:
        return

    writer = MetricWriter(configs.train.save_path)

    # compression-health telemetry (configs/telemetry.py, docs/TELEMETRY.md):
    # per-step stats ride the jitted step's aux outputs; the async sink
    # drains completed device buffers on its own thread — the train loop
    # never adds a host sync. Coordinator-only files, like MetricWriter.
    tcfg = configs.train.get("telemetry", None)
    telemetry_on = bool(tcfg and tcfg.get("enabled", False))
    # fleet dispersion taps (configs/fleet.py, docs/TELEMETRY.md §Fleet
    # monitoring): per-worker columns in every record, a host-stamped
    # dispatch-interval clock input, and EVERY process writing its own
    # host<i>/ sink shard so the run-level aggregator
    # (dgc_tpu.telemetry.fleet / the live monitor) can merge the cohort
    fleet_on = bool(telemetry_on and tcfg.get("fleet", False))
    sink = None
    if telemetry_on:
        from dgc_tpu.telemetry.sink import TelemetrySink
        telem_every = int(tcfg.get("every", 1) or 1)
        if fleet_on:
            sink_path = os.path.join(configs.train.save_path, "telemetry",
                                     f"host{jax.process_index()}")
            sink_enabled = True
        else:
            sink_path = os.path.join(configs.train.save_path, "telemetry")
            sink_enabled = jax.process_index() == 0
        from dgc_tpu.control import resolve_run_id
        # supervised runs carry the supervisor's run_id (DGC_RUN_ID) so
        # the telemetry header, supervise stream, and every monitor gauge
        # agree on which run this is; unsupervised runs omit it and the
        # monitor falls back to the run dir name
        run_id = resolve_run_id()
        sink = TelemetrySink(
            sink_path,
            static=dict(flat_setup.engine.telemetry_static(),
                        world=world, num_local_workers=num_local,
                        process_index=jax.process_index(),
                        num_processes=jax.process_count(),
                        **({"run_id": run_id} if run_id else {})),
            rotate_bytes=int(tcfg.get("rotate_mb", 64)) << 20,
            enabled=sink_enabled,
            guards=guards_cfg is not None, fleet=fleet_on)
        printr(f"[telemetry] -> {sink.path or '(non-coordinator)'}"
               + (" [fleet]" if fleet_on else ""))
        if autotuner is not None:
            # refit/replan events ride the telemetry stream (the
            # AUTOTUNE_SMOKE gate and the monitor both read them there)
            autotuner.sink = sink
        if elastic_pending is not None:
            # the restore resharded across a topology change: record it
            # in the telemetry stream so readers can re-anchor per-worker
            # columns (same pattern as the engine_rebuild event)
            sink.write_record(dict(elastic_pending,
                                   event="elastic_restart"))
    if fleet_on:
        from dgc_tpu.telemetry import fleet as _fleet
    # previous step's dispatch stamp (the fleet step-time proxy); host
    # wall clock, never read inside the traced step
    prev_dispatch = None

    # structured tracing (configs/trace.py or --trace, docs/TELEMETRY.md
    # §Tracing): the one switch must flip BEFORE the step builds below
    # (the dgcph.* scopes bake into the program at trace time). Host spans
    # and counts go to the process-wide recorder from where the work
    # happens; with --profile they are dgc:* annotations in the profile
    from dgc_tpu.telemetry import trace as _trace
    trccfg = configs.train.get("trace", None)
    trace_on = bool(args.trace or (trccfg and trccfg.get("enabled", False)))
    if trace_on:
        _trace.enable(True)

    # host-side resilience: signal -> flag (the loop does the emergency
    # save at a step boundary); watchdog dumps stacks on a stalled step;
    # the flight recorder keeps a ring of recent step records for the
    # postmortem dump (watchdog stall / preemption / nonfinite streak)
    handler = watchdog = surgeon = None
    flight = flight_path = streak = None
    if res_on:
        from dgc_tpu.resilience import faults as _faults
        from dgc_tpu.resilience import preempt as _preempt
        handler = _preempt.PreemptionHandler()
        fl_steps = int(rcfg.get("flight_steps", 0) or 0)
        if fl_steps > 0:
            from dgc_tpu.telemetry.flight import FlightRecorder
            from dgc_tpu.control import resolve_run_id
            fl_run_id = resolve_run_id()
            flight = FlightRecorder(
                capacity=fl_steps,
                static=dict(flat_setup.engine.telemetry_static(),
                            world=world, num_local_workers=num_local,
                            save_path=configs.train.save_path,
                            **({"run_id": fl_run_id} if fl_run_id
                               else {})))
            flight_path = os.path.join(configs.train.save_path,
                                       "flight.json")
        ns = int(rcfg.get("nonfinite_streak", 0) or 0)
        if ns > 0:
            from dgc_tpu.telemetry.flight import NonfiniteStreak
            streak = NonfiniteStreak(ns)
        wd_secs = float(rcfg.get("watchdog_secs", 0) or 0)
        if wd_secs > 0:
            # tier-1 hang escalation: in-process diagnostics; the
            # heartbeat file (DGC_HEARTBEAT, supervisor-provided) is the
            # tier-2 signal — a stale mtime tells the supervisor to
            # SIGKILL us (docs/RESILIENCE.md §"Cohort surgery")
            watchdog = _preempt.Watchdog(
                wd_secs, sink=sink, flight=flight,
                flight_path=flight_path,
                heartbeat_path=os.environ.get("DGC_HEARTBEAT"))
        if bool(rcfg.get("surgery", False)):
            from dgc_tpu.resilience import surgery as _surgery
            surgeon = _surgery.SurgeryCoordinator(
                os.path.join(ckpt_dir, _surgery.ORDER_FILE),
                boundary_timeout=float(
                    rcfg.get("boundary_timeout", 60.0)),
                retries=int(rcfg.get("boundary_retries", 3)),
                backoff=float(rcfg.get("boundary_backoff", 5.0)),
                log=lambda m: printr(f"[surgery] {m}"))
        printr(f"[resilience] guards={guards_cfg} checksum={res_checksum} "
               f"watchdog={wd_secs or 'off'} "
               f"flight={fl_steps or 'off'} "
               f"surgery={'on' if surgeon is not None else 'off'}")

    ############
    # Training #
    ############

    step_fn = None
    autotune_pending = False     # a key()-changing replan awaits rebuild
    at_prev = None               # previous dispatch stamp (autotune)
    at_wire = 0                  # engine wire-bytes proxy for step points
    num_inputs = ((last_epoch + 1) * steps_per_epoch
                  + resume_batch) * global_batch
    # python-side completed-step counter (kill-fault drill only; the real
    # step counter lives on device in state.step — int() there would sync)
    gstep = (last_epoch + 1) * steps_per_epoch + resume_batch
    preempted = False
    preempt_at = -1
    surgery_exit = None      # the agreed excise Agreement, if any
    aborted = False          # nonfinite-streak breaker tripped
    last_ckpt_epoch = last_epoch
    for epoch in range(last_epoch + 1, configs.train.num_epochs):
        printr(f"\n==> training epoch {epoch}/{configs.train.num_epochs}")

        rebuild = step_fn is None
        if configs.train.dgc:
            rebuild |= compression.warmup_compress_ratio(epoch)
        # an epoch-boundary replan whose key() changed forces the one
        # rebuild it already paid for; same-key refits never land here
        rebuild |= autotune_pending
        if rebuild:
            # ratio change => new static attrs => new engine + re-jit
            # (reference compression.py:91-107; <= warmup_epochs+1 compiles)
            # (the standing gossip plan re-fits to the fresh geometry;
            # None when gossip is off or the autotuner owns the plan)
            flat_setup = make_flat_setup(variables, dist, plan=gossip_plan)
            if autotuner is not None:
                # replan against the FRESH bucket geometry under the
                # current (possibly refit) fabric — host-side only
                flat_setup = make_flat_setup(
                    variables, dist,
                    plan=autotuner.plan_for(flat_setup.engine))
            step_fn = build_train_step(model.apply, dist, mesh,
                                       num_batches_per_step=nbps,
                                       use_dropout=use_dropout,
                                       flat=flat_setup,
                                       model_dtype=_narrow_model_dtype(model),
                                       telemetry=telemetry_on,
                                       guards=guards_cfg,
                                       fleet=fleet_on,
                                       adaptive=adaptive_cfg)
            if sink is not None:
                # engine geometry changes with the warm-up ratio: record
                # it so readers can re-anchor the per-bucket columns
                sink.write_record(dict(
                    flat_setup.engine.telemetry_static(),
                    event="engine_rebuild", epoch=epoch))
            autotune_pending = False
            # the (bytes, ms) proxy for this engine's steps: the sparse
            # wire when the plan keeps one, else the dense psum bytes
            if autotuner is not None:
                at_wire = (flat_setup.engine.wire_bytes_per_worker()
                           or 4 * flat_setup.layout.total)

        ds = dataset["train"]
        t0 = time.time()
        seen = 0
        metrics = None
        loss_log = []
        base_key = jax.random.PRNGKey(seed)
        # --profile traces 8 steady-state steps of the first trained epoch
        # and then keeps training normally (the trace stops, the epoch
        # doesn't). The trace starts once the epoch's first step has
        # finished: that step compiles, and its host events fill the
        # profiler's 1M-event export and push the device ops out of it
        # (seen on the chip, PR 21)
        profile_pending = bool(args.profile and epoch == last_epoch + 1)
        profile_left = 0
        batches = None
        try:
            # background-thread batch prep (DataLoader-worker role) plus
            # one-ahead async device transfer: the host assembles batch
            # k+1 and its host->device copy is in flight while the device
            # runs step k
            # mid-epoch (preemption) resume: skip the batches the
            # interrupted run already consumed — the shuffle is a pure
            # function of (epoch, seed), so the sequence lines up exactly
            bofs = resume_batch if epoch == resume_epoch else 0
            epoch_iter = epoch_batches(
                len(ds), global_batch, epoch=epoch, seed=seed,
                drop_last=nbps > 1)
            if bofs:
                epoch_iter = itertools.islice(epoch_iter, bofs, None)
            batches = Prefetcher(ds, epoch_iter)
            staged = stage_ahead(
                batches,
                lambda b: (host_local_to_global(b[0], mesh),
                           host_local_to_global(b[1], mesh)))
            for rel_idx, (images, labels) in enumerate(staged):
                bidx = bofs + rel_idx
                # preemption check at the step boundary: agree_preempt is
                # a (tiny, host-side) collective on multi-process runs, so
                # every process takes the emergency-save path on the SAME
                # step — a lone worker breaking out would hang the rest.
                # With surgery on, the same gather widens to (preempt,
                # verdict, target) and grows a hang-safe deadline.
                if handler is not None and surgeon is not None:
                    ag = surgeon.agree(handler.requested)
                    if ag.lost:
                        # a member is hung/dead mid-gather: no further
                        # collective (emergency save included) can
                        # complete. Dump the flight ring, leave the
                        # exit-76 breadcrumb, and go down hard — recovery
                        # rolls back to the last atomic checkpoint (the
                        # dead worker's post-checkpoint residual is
                        # unrecoverable regardless; docs/RESILIENCE.md
                        # §"Cohort surgery")
                        if flight is not None:
                            flight.dump(flight_path,
                                        reason="surgery: cohort lost")
                        _surgery.write_exit_record(
                            os.path.join(ckpt_dir, _surgery.EXIT_RECORD),
                            ag, world=jax.process_count(),
                            process_index=jax.process_index(), step=gstep)
                        printr("[surgery] cohort lost at the boundary — "
                               f"exit {_surgery.EXIT_SURGERY} "
                               "(roll back to the last checkpoint)")
                        sys.stdout.flush()
                        os._exit(_surgery.EXIT_SURGERY)
                    if ag.excise or ag.preempt:
                        surgery_exit = ag if ag.excise else None
                        preempted, preempt_at = True, bidx - 1
                        break
                elif handler is not None and _preempt.agree_preempt(
                        handler.requested):
                    preempted, preempt_at = True, bidx - 1
                    break
                # span covers DISPATCH only (async jax: the call returns
                # as soon as the step is enqueued) — device-side time
                # lives in the profiler trace, not here
                with _trace.span("step.dispatch", step=gstep), \
                        _trace.step_annotation(gstep):
                    if fleet_on:
                        # deterministic straggler drill (DGC_FAULTS=
                        # slow:ms=M on ONE process): sleep BEFORE the
                        # stamp so the injected lag lands in this
                        # process's prep interval
                        from dgc_tpu.resilience import faults as _flt
                        if _flt.armed():
                            _flt.maybe_slow(gstep)
                        # w_clock lane: host PREP time — previous
                        # dispatch RETURN to this dispatch START. The
                        # dispatch call can block on the cohort
                        # collective; that wait equalizes across hosts
                        # and would erase the straggler's signature, so
                        # it stays outside the stamp.
                        now = time.perf_counter()
                        dt_ms = ((now - prev_dispatch) * 1000.0
                                 if prev_dispatch is not None else 0.0)
                        state, metrics = step_fn(
                            state, images, labels,
                            jax.random.fold_in(
                                base_key, epoch * 100003 + bidx),
                            _fleet.make_clock(dt_ms, mesh, world))
                        prev_dispatch = time.perf_counter()
                    else:
                        state, metrics = step_fn(
                            state, images, labels,
                            jax.random.fold_in(
                                base_key, epoch * 100003 + bidx))
                if profile_pending:
                    profile_pending = False
                    jax.block_until_ready(metrics["loss"])
                    jax.profiler.start_trace(
                        os.path.join(configs.train.save_path, "profile"))
                    profile_left = 8
                elif profile_left:
                    profile_left -= 1
                    if profile_left == 0:
                        jax.block_until_ready(metrics["loss"])
                        jax.profiler.stop_trace()
                if autotuner is not None:
                    # dispatch-interval (bytes, ms) point — host stamps
                    # only, same proxy as the fleet w_clock lane; the
                    # refit's prior-pinned intercept tolerates the
                    # included compute time
                    at_now = time.perf_counter()
                    if at_prev is not None:
                        autotuner.record_step((at_now - at_prev) * 1000.0,
                                              at_wire)
                    at_prev = at_now
                seen += 1
                num_inputs += global_batch
                gstep += 1
                if flight is not None:
                    # raw device scalars go into the ring (zero syncs);
                    # conversion happens only at dump time
                    flight.record(
                        gstep, epoch=epoch, batch=bidx,
                        num_inputs=num_inputs,
                        loss=metrics["loss"],
                        guards=metrics.get("guards"),
                        spans_ms=_trace.step_summary(),
                        last_ckpt_epoch=last_ckpt_epoch)
                if watchdog is not None:
                    watchdog.beat()
                if res_on and _faults.armed():
                    _faults.maybe_hang(gstep)
                    _faults.maybe_exit(gstep)
                    _faults.maybe_kill(gstep)
                if sink is not None and bidx % telem_every == 0:
                    # device arrays enqueued as-is: the sink's drain
                    # thread does the (blocking) device->host transfer;
                    # guard counters ride the same record (key-additive)
                    stats = metrics["telemetry"]
                    if guards_cfg is not None:
                        stats = {**stats, **metrics["guards"]}
                    if fleet_on:
                        # fleet columns + loss ride the same record
                        # (key-additive) so the monitor sees them all
                        stats = {**stats, **metrics["fleet"],
                                 "loss": metrics["loss"]}
                    sink.write(num_inputs, stats)
                logged = bidx % 50 == 0
                if logged:
                    # keep the device scalar: float() here would block the
                    # dispatch pipeline; drain_loss_log converts after the
                    # epoch's steps are all enqueued (dgclint: sync-in-loop)
                    loss_log.append((num_inputs, metrics["loss"]))
        finally:
            if batches is not None:  # release the prefetch thread on error
                batches.close()
            if profile_left:         # epoch shorter than the trace window
                jax.profiler.stop_trace()
        if preempted:
            break
        dt = time.time() - t0
        if metrics is None:
            printr("[warn] epoch produced no batches "
                   "(dataset smaller than the global batch with drop_last)")
        else:
            if not logged:
                loss_log.append((num_inputs, metrics["loss"]))
            # the drain is the epoch's one host sync: it waits for every
            # enqueued step (exchange included) to complete. The streak
            # breaker taps each converted loss.
            with _trace.span("step.drain", epoch=epoch):
                loss = drain_loss_log(
                    writer, loss_log,
                    on_loss=streak.update if streak is not None else None)
            printr(f"[loss] = {loss:.4f}  ({seen} steps, "
                   f"{dt / max(seen, 1) * 1000:.1f} ms/step)")
            if streak is not None and streak.tripped:
                aborted = True
                break

        if autotuner is not None:
            # epoch boundary: refit the link model over the accumulated
            # points (+ per-bucket device costs when a profile exists),
            # persist <save_path>/fabric.json, replan. All host-side —
            # zero extra collectives; a rebuild happens next epoch ONLY
            # when the plan key changed.
            at_prev = None       # don't span the eval/ckpt gap
            profile = None
            ppath = os.path.join(configs.train.save_path, "profile.json")
            if os.path.exists(ppath):
                try:
                    from dgc_tpu.telemetry.attrib import load_profile
                    profile = load_profile(ppath)
                except (ValueError, OSError, KeyError):
                    profile = None
            new_plan = autotuner.epoch_end(flat_setup.engine, epoch=epoch,
                                           profile=profile)
            if new_plan is not None:
                autotune_pending = True
                printr(f"[autotune] refit {autotuner.fabric.gbps:.3g} GB/s"
                       f" alpha {autotuner.fabric.alpha_ms:.3g} ms -> "
                       f"replan {list(new_plan.regimes)} (rebuild next "
                       f"epoch)")
            elif autotuner.refit_count:
                printr(f"[autotune] refit {autotuner.fabric.gbps:.3g} GB/s"
                       f" alpha {autotuner.fabric.alpha_ms:.3g} ms — plan "
                       f"unchanged (no recompile)")

        with _trace.span("eval", epoch=epoch):
            meters = evaluate(state)
        best = False
        if configs.train.get("metric") is not None:
            m = meters.get(configs.train.metric)
            if best_metric is None or (m is not None and best_metric < m):
                best_metric, best = m, True
            meters[configs.train.metric + "_best"] = best_metric
        for k, v in meters.items():
            printr(f"[{k}] = {v:.2f}")
            writer.add_scalar(k, v, num_inputs)

        with _trace.span("checkpoint.save", epoch=epoch):
            path = ckpt.save(epoch, state, meters, best=best,
                             topology=topology)
        last_ckpt_epoch = epoch
        printr(f"[save_path] = {path}")

    if aborted:
        # guards can skip individual bad steps, but a SUSTAINED nonfinite
        # run means the training state itself is gone — stop burning the
        # reservation and leave the flight recorder as the postmortem
        printr(f"\n[resilience] {streak.streak} consecutive nonfinite "
               f"losses at epoch {epoch} — aborting "
               f"(last checkpoint: epoch {last_ckpt_epoch})")
        if flight is not None:
            p = flight.dump(flight_path,
                            reason=f"nonfinite-streak x{streak.streak}")
            if p:
                printr(f"[resilience] flight recorder -> {p}")

    if preempted:
        # emergency checkpoint: full state (compressor memory included) +
        # the in-progress epoch and last completed batch, so resume picks
        # up at the exact next batch. All processes reach here on the same
        # step (agree_preempt), so the collective save lines up.
        if surgery_exit is not None:
            printr(f"\n[surgery] excise agreed: verdict="
                   f"{surgery_exit.verdict} target={surgery_exit.target}"
                   f" — stopping at epoch {epoch}, batch {preempt_at}")
        else:
            printr(f"\n[preempt] signal {handler.signum}: stopping at "
                   f"epoch {epoch}, batch {preempt_at}")
        if flight is not None:
            reason = (f"surgery: excise {surgery_exit.verdict} "
                      f"worker {surgery_exit.target}"
                      if surgery_exit is not None
                      else f"preempt signal {handler.signum}")
            p = flight.dump(flight_path, reason=reason)
            if p:
                printr(f"[preempt] flight recorder -> {p}")
        if bool(rcfg.get("emergency_checkpoint", True)):
            emeters = {"preempt_batch": preempt_at}
            if best_metric is not None:
                emeters[configs.train.metric + "_best"] = best_metric
            # emergency_save stamps _topology unconditionally: an elastic
            # restart of THIS checkpoint is exactly the case where the
            # record must exist
            path = _preempt.emergency_save(ckpt, epoch, state, emeters,
                                           topology=topology)
            printr(f"[preempt] emergency checkpoint -> {path}")
        if surgery_exit is not None:
            # orderly excise: everyone was alive at the boundary, so the
            # collective emergency save above is complete — leave the
            # exit-76 breadcrumb for the supervisors and retire the
            # consumed order (a relaunched cohort must not re-excise)
            _surgery.write_exit_record(
                os.path.join(ckpt_dir, _surgery.EXIT_RECORD),
                surgery_exit, world=jax.process_count(),
                process_index=jax.process_index(), step=gstep)
            _surgery.clear_order(surgeon.order_path)

    if trace_on:
        # the recorder's spans and counts, once, now that the run is over
        _trace.write(os.path.join(configs.train.save_path,
                                  "trace_records.jsonl"))
    if sink is not None:
        sink.close()
    writer.close()
    if watchdog is not None:
        watchdog.stop()
    if handler is not None:
        handler.uninstall()
    if aborted:
        # EX_SOFTWARE: unrecoverable training state — a supervisor must
        # NOT blindly relaunch (resume would replay the same divergence);
        # distinct from the preemption 75 below
        raise SystemExit(70)
    if preempted:
        _preempt.clean_shutdown()
        if surgery_exit is not None:
            # cohort surgery: the supervisor maps 76 to a survivors-only
            # relaunch under the published shrunk cohort spec (the PR-5
            # elastic reshard absorbs the excised worker's mass)
            raise SystemExit(76)
        # EX_TEMPFAIL: tell a supervisor (scripts/supervise.py) this was
        # a clean preemption with the emergency save already on disk —
        # relaunch (a plain 0 would read as "training finished")
        raise SystemExit(75)


if __name__ == "__main__":
    main()

"""The quickest proof that the system still starts on the chip.

One process, one command, no arguments::

    python chip_smoke.py

It drives the main path the way a user does — ``train.main()`` on
ResNet-50 at 224x224, 1000 classes, batch 32 per chip, f32, DGC at
compress ratio 0.001 (``configs/imagenet/resnet50.py`` +
``configs/dgc/wm0.py``) over synthetic data made from a seed — and checks
what comes out by the repo's own means. Every stage must pass; the first
failure is the exit status, and nothing downgrades it to a warning.

Stages, in order:

1. ``device``  the backend is ``tpu`` (anything else exits non-zero with
   the backend named); device kind/count and the jax, jaxlib and libtpu
   versions are printed.
2. ``sync``    one known-long jitted loop timed three ways — dispatch
   only, ended by ``block_until_ready``, ended by a forced scalar
   readback — so that ``block_until_ready_waits`` is an observation, not
   an assumption of the timing harnesses.
3. ``train``   a fresh one-epoch run through ``train.main()``: every
   step's loss finite, ``state.step`` advanced by the steps run, every
   per-worker state leaf sharded over all W chips, a checkpoint saved.
4. ``hlo``     the step that just ran, lowered and compiled again from
   its own arguments: it must contain the Mosaic custom call of the
   default route's compensate kernel (a run that silently took a jnp
   reference path cannot pass), and for W > 1 the sparse all-gather
   over all W chips.
5. ``resume``  the same command with ``--train.num_epochs 2``: restores
   the epoch-0 checkpoint (error-feedback memory included) and trains
   one more epoch; the second build of the step must come out of the
   persistent compile cache.
6. ``kernels`` ``scripts/tpu_check.py``'s compiled-vs-reference bitwise
   check of every Pallas kernel, in this same process (the chip belongs
   to one process); with it the apply pass that runs the optimizer's
   rule (``check_update_pass``) and the gradient pack's placement pass
   (``check_pack_pass``), each against the passes it replaced at the
   benchmark's own geometry.

Outputs go under ``runs/`` only (the full summary is
``runs/chip_smoke.json`` and the ``summary:`` line). The last line of
stdout is one JSON object: ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": ...}}``.
"""

import importlib.metadata
import json
import math
import os
import re
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

CONFIGS = ("configs/imagenet/resnet50.py", "configs/dgc/wm0.py")
SUFFIX = ".chipsmoke"
SYNTHETIC_SIZE = 512
BATCH_PER_CHIP = 32        # configs/imagenet/__init__.py
#: name, as XLA records it, of the jitted Pallas kernel that the default
#: TPU route of this model's engine compensates with (ResNet-50's big
#: buckets select through the segment-top-2 candidates fused into it)
COMPENSATE_KERNEL = "fused_compensate_bits_cands"


def log(msg):
    print(f"[chip_smoke] {msg}", flush=True)


def fail(stage, why):
    raise SystemExit(f"chip_smoke: stage '{stage}' FAILED: {why}")


def device_stage():
    import jax
    import jaxlib

    from dgc_tpu.utils.device import require_tpu

    require_tpu("chip_smoke.py")
    devices = jax.devices()
    info = {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                "libtpu": importlib.metadata.version("libtpu"),
                "python": sys.version.split()[0]}
    log(f"backend=tpu device={info} versions={versions}")
    return info, versions


def sync_stage():
    """Does ``block_until_ready`` wait for the device?  One jitted loop
    long enough (~0.2-0.5 s of matmuls) that an early return cannot be
    mistaken for a fast one."""
    import jax
    import jax.numpy as jnp

    n, iters = 2048, 3000

    @jax.jit
    def long_loop(x, w):
        x = jax.lax.fori_loop(
            0, iters, lambda _, a: jnp.tanh(
                jnp.dot(a, w, preferred_element_type=jnp.float32)
            ).astype(a.dtype), x)
        return x, jnp.sum(x.astype(jnp.float32))

    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (n, n), jnp.bfloat16)
    w = jax.random.normal(kw, (n, n), jnp.bfloat16) / math.sqrt(n)
    x, s = long_loop(x, w)                # compile + warm
    float(s)

    def timed(end):
        nonlocal x
        t0 = time.perf_counter()
        x, s = long_loop(x, w)
        end(x, s)
        ms = (time.perf_counter() - t0) * 1e3
        float(s)                          # drain before the next timing
        return ms

    dispatch, waited, read = [], [], []
    for _ in range(3):
        dispatch.append(timed(lambda x_, s_: None))
        waited.append(timed(lambda x_, s_: jax.block_until_ready(x_)))
        read.append(timed(lambda x_, s_: float(s_)))
    out = {"dispatch_only_ms": round(sorted(dispatch)[1], 3),
           "block_until_ready_ms": round(sorted(waited)[1], 3),
           "scalar_readback_ms": round(sorted(read)[1], 3)}
    if out["scalar_readback_ms"] < 50.0:
        fail("sync", f"the probe loop is too short to tell: {out}")
    # "waits" = ending on block_until_ready accounts for (nearly) all of
    # the time a forced readback proves the device needed
    out["block_until_ready_waits"] = bool(
        out["block_until_ready_ms"] >= 0.9 * out["scalar_readback_ms"])
    log(f"sync probe: {out}")
    return out


class StepRecorder:
    """Stands in for ``dgc_tpu.training.build_train_step`` while
    ``train.main()`` runs: the same builder, but every call of the step
    it returns is recorded — the loss of each step, the step counter on
    the way in and out, and the abstract arguments to lower it from."""

    def __init__(self, build):
        self._build = build
        self.step_fn = None
        self.abstract_args = None
        self.first_state_step = None
        self.first_memory_mass = None
        self.losses = []
        self.last_state = None

    def __call__(self, *args, **kwargs):
        import jax
        import jax.numpy as jnp

        step_fn = self._build(*args, **kwargs)
        self.step_fn = step_fn
        n_dev = jax.device_count()

        def abstract(x):
            # keep mesh shardings; an uncommitted single-device leaf (the
            # per-step PRNG key) must stay free to be placed by jit
            sh = x.sharding if len(x.sharding.device_set) == n_dev else None
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

        def recorded(state, *rest):
            if self.abstract_args is None:
                self.abstract_args = jax.tree.map(abstract, (state, *rest))
                self.first_state_step = int(state.step)
                self.first_memory_mass = float(sum(
                    jnp.sum(jnp.abs(v.astype(jnp.float32)))
                    for k, v in state.memory.items()
                    if k.startswith(("momentums", "velocities"))))
            state, metrics = step_fn(state, *rest)
            self.losses.append(metrics["loss"])
            self.last_state = state
            return state, metrics
        return recorded


def run_train(num_epochs):
    """One ``train.main()`` call with the step recorded."""
    import dgc_tpu.training as training
    import train
    from dgc_tpu.utils.config import Config

    Config.reset()
    rec = StepRecorder(training.build_train_step)
    training.build_train_step = rec
    t0 = time.perf_counter()
    try:
        train.main(["--configs", *CONFIGS, "--suffix", SUFFIX,
                    "--train.num_epochs", str(num_epochs),
                    "--dataset.synthetic_size", str(SYNTHETIC_SIZE)])
    finally:
        training.build_train_step = rec._build
    return rec, time.perf_counter() - t0


def check_run(stage, rec, steps, start_step):
    import jax
    import numpy as np

    losses = [float(x) for x in rec.losses]
    if len(losses) != steps:
        fail(stage, f"ran {len(losses)} steps, expected {steps}")
    if not all(np.isfinite(losses)):
        fail(stage, f"non-finite loss among {losses}")
    if rec.first_state_step != start_step:
        fail(stage, f"first step saw state.step={rec.first_state_step}, "
                    f"expected {start_step}")
    end = int(rec.last_state.step)
    if end != start_step + steps:
        fail(stage, f"state.step ended at {end}, expected "
                    f"{start_step + steps}")
    # every per-worker leaf ([W, ...]: DGC momentum/velocity/transmit
    # record, BN statistics) lives on all W chips, one row each
    W = jax.device_count()
    per_worker = {"memory": rec.last_state.memory,
                  "batch_stats": rec.last_state.batch_stats}
    flat, _ = jax.tree_util.tree_flatten_with_path(per_worker)
    for path, leaf in flat:
        name = jax.tree_util.keystr(path)
        shard = leaf.sharding.shard_shape(leaf.shape)
        if not (leaf.shape[0] == W and shard[0] == 1
                and len(leaf.sharding.device_set) == W):
            fail(stage, f"per-worker leaf {name} shape {leaf.shape} is "
                        f"not sharded one row per chip over {W} chips: "
                        f"shard {shard}, {leaf.sharding}")
    log(f"{stage}: {steps} steps, state.step {start_step} -> {end}, "
        f"losses {[round(x, 4) for x in losses]}, "
        f"{len(flat)} per-worker leaves on {W} chip(s)")
    rec.last_state = None      # checked: do not pin a state's HBM
    return losses


def hlo_stage(rec):
    """Lower and compile the recorded step from its own arguments and
    read what the chip was actually given."""
    import jax

    W = jax.device_count()
    lowered = rec.step_fn.lower(*rec.abstract_args)
    hlo = lowered.compile().as_text()
    mosaic = {}
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        jits = re.findall(r"jit\((\w+)\)", m.group(1)) if m else []
        name = jits[-1] if jits else "?"
        mosaic[name] = mosaic.get(name, 0) + 1
    if not mosaic.get(COMPENSATE_KERNEL):
        fail("hlo", f"no Mosaic custom call of {COMPENSATE_KERNEL} in the "
                    f"compiled step; tpu_custom_calls found: {mosaic}")
    out = {"mosaic_calls": mosaic}
    if W > 1:
        # the sparse exchange: an all-gather over ALL W workers in the
        # program we lowered, and (XLA:TPU may rewrite a small all-gather
        # into an all-reduce of a padded buffer) a collective over one
        # W-member replica group in the program it compiled
        asked = [int(g) for g in re.findall(
            r"all_gather.*?replica_groups = dense<.*?> : "
            r"tensor<\d+x(\d+)xi64>", lowered.as_text())]
        if W not in asked:
            fail("hlo", f"no all_gather over {W} workers in the lowered "
                        f"step (group sizes found: {asked})")
        group = "{{" + ",".join(str(i) for i in range(W)) + "}}"
        compiled_ops = sorted(set(re.findall(
            r"(all-gather|all-reduce|all-to-all|collective-permute)"
            r"(?:-start)?\([^\n]*replica_groups=" + re.escape(group), hlo)))
        if not compiled_ops:
            fail("hlo", f"no collective over replica group {group} in the "
                        "compiled step")
        out["all_gather_group_sizes"] = sorted(set(asked))
        out["compiled_collectives_over_all_chips"] = compiled_ops
    log(f"hlo: {out}")
    return out


class CacheCounter:
    """Counts JAX's persistent-compilation-cache hits and misses."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return {"hits": self.hits, "misses": self.misses}


def main():
    os.chdir(ROOT)
    sys.path.insert(0, os.path.join(ROOT, "scripts"))

    from dgc_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    device, versions = device_stage()
    W = device["count"]
    cache = {"dir": cache_dir,
             "entries_before": compile_cache.entries(cache_dir)}
    counter = CacheCounter()
    summary = {"ok": True, "device": device, "versions": versions,
               "model": "resnet50 224x224x3, 1000 classes, f32, batch "
                        "32/chip, DGC ratio 0.001 (wm0), synthetic data"}

    summary["sync"] = sync_stage()

    # a previous smoke's checkpoints would turn the fresh run into a
    # resume; the directory is this script's own (SUFFIX)
    import train
    save_path = train.get_save_path(*CONFIGS) + f"{SUFFIX}.np{W}"
    shutil.rmtree(save_path, ignore_errors=True)

    steps = -(-SYNTHETIC_SIZE // (BATCH_PER_CHIP * W))
    rec, secs = run_train(num_epochs=1)
    losses = check_run("train", rec, steps, start_step=0)
    if not os.path.isdir(os.path.join(save_path, "checkpoints", "e0")):
        fail("train", f"no epoch-0 checkpoint under {save_path}")
    after_train = counter.snapshot()
    summary["train"] = {"steps": steps, "losses": losses,
                        "wall_s": round(secs, 1),
                        "compile_cache": after_train,
                        "checkpoint": os.path.join(save_path,
                                                   "checkpoints", "e0")}

    summary["hlo"] = hlo_stage(rec)

    before_resume = counter.snapshot()
    rec2, secs2 = run_train(num_epochs=2)
    losses2 = check_run("resume", rec2, steps, start_step=steps)
    if not rec2.first_memory_mass > 0.0:
        fail("resume", "the restored DGC momentum/velocity buffers are "
                       "all zero — the error-feedback memory did not "
                       "come back from the checkpoint")
    after_resume = counter.snapshot()
    resume_hits = after_resume["hits"] - before_resume["hits"]
    if resume_hits < 1:
        fail("resume", "the rebuilt train step was compiled again: no "
                       f"persistent-cache hit ({before_resume} -> "
                       f"{after_resume}, cache {cache_dir})")
    summary["resume"] = {"steps": steps, "losses": losses2,
                         "wall_s": round(secs2, 1),
                         "restored_memory_mass": rec2.first_memory_mass,
                         "compile_cache_hits": resume_hits}

    import tpu_check
    kernels = tpu_check.check_kernels()
    bad = sorted(k for k, good in kernels.items() if not good)
    log(f"kernels: {len(kernels) - len(bad)}/{len(kernels)} bitwise equal "
        f"to their references" + (f"; MISMATCH: {bad}" if bad else ""))
    if bad:
        fail("kernels", f"compiled kernel != reference: {bad}")
    summary["kernels"] = kernels

    cache["entries_after"] = compile_cache.entries(cache_dir)
    cache.update(counter.snapshot())
    summary["compile_cache"] = cache
    log(f"compile cache: {cache}")

    # reached only when every stage passed
    os.makedirs("runs", exist_ok=True)
    with open(os.path.join("runs", "chip_smoke.json"), "w") as f:
        json.dump(summary, f, indent=1)
    log("summary: " + json.dumps(summary))
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

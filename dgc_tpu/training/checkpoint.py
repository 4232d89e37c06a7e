"""Checkpoint save/resume/rotate — parity with the reference subsystem
(SURVEY.md §3.4, /root/reference/train.py:152-173,244-264).

Replicated facts: checkpoints save every epoch and include the DGC
compression memory (momentums + velocities) as part of training state
(train.py:249-250); a ``latest`` pointer and a ``best`` copy are maintained;
only the last 3 epoch checkpoints are kept (train.py:260-263). Differences by
design: one checkpoint holds the whole sharded state (the per-worker memory
and BN stats carry their leading ``[world]`` axis) instead of one file per
Horovod rank, and restore re-places arrays on the mesh — so resume works
across different worker counts only if the mesh size matches, like the
reference.

Arrays are materialized to host numpy before saving (single-host orbax
PyTree checkpointing); restore hands back numpy pytrees which the caller
re-shards via ``shard_state``.
"""

import json
import os
import shutil
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from dgc_tpu.serving import protocol as serving_protocol

__all__ = ["CheckpointManager"]


def _without_empty(tree: Any) -> Any:
    """``tree`` with every zero-size leaf gone (None is an empty subtree):
    orbax refuses to write one, and the flat state of a model without
    batch statistics carries a [world, 0] ``batch_stats``."""
    return jax.tree.map(lambda x: None if x.size == 0 else x, tree)


def _with_empty(restored: Any, template: Any) -> Any:
    """``restored`` (read against ``_without_empty(template)``) with the
    template's zero-size leaves back in place."""
    leaves, treedef = jax.tree.flatten(template)
    read = iter(jax.tree.leaves(restored))
    return treedef.unflatten(
        [leaf if leaf.size == 0 else next(read) for leaf in leaves])


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = os.path.abspath(directory)
        self.keep = keep
        os.makedirs(self.directory, exist_ok=True)
        self._ckptr = ocp.StandardCheckpointer()

    # ------------------------------------------------------------------ #

    def _epoch_dir(self, epoch: int) -> str:
        return os.path.join(self.directory, f"e{epoch}")

    def _meta_path(self) -> str:
        return os.path.join(self.directory, "latest.json")

    def save(self, epoch: int, state: Any, meters: Dict[str, float],
             best: bool = False,
             topology: Optional[Dict[str, int]] = None) -> str:
        """Save epoch checkpoint, update latest pointer, rotate, track best.

        **Atomic**: the state AND its meters.json are written to
        ``e<N>.tmp`` and published with one ``os.replace`` — a crash or
        preemption mid-write leaves only a ``.tmp`` directory that the
        next run ignores (and ``restore`` falls back to the previous kept
        epoch), never a half-written ``e<N>`` that latest.json points at.

        Multi-process (``jax.process_count() > 1``): EVERY process must
        call this with the same global (sharded) state — orbax coordinates
        the distributed array write itself (the directory must be a shared
        filesystem, as on TPU pods) — while all the filesystem bookkeeping
        (rename, meters/latest files, best copy, rotation) happens on the
        coordinator only, fenced by barriers so no process races a
        directory that is being rotated. Single-process keeps the simple
        host-materialized write."""
        if getattr(state, "adaptive", None) is not None:
            # the straggler-adaptive policy state is memoryless (one
            # step's verdict, recomputed every step) and deliberately NOT
            # checkpointed: stripping it keeps old checkpoints and elastic
            # world-size changes restore-compatible — restore re-seeds a
            # fresh full-send verdict from the caller's template
            state = state.replace(adaptive=None)
        state = _without_empty(state)
        multi = jax.process_count() > 1
        coord = jax.process_index() == 0
        path = self._epoch_dir(epoch)
        tmp = path + ".tmp"
        if multi:
            from jax.experimental import multihost_utils
            if coord and os.path.exists(tmp):   # stale from a crashed run
                shutil.rmtree(tmp)
            multihost_utils.sync_global_devices(f"ckpt_pre_save_e{epoch}")
            self._ckptr.save(tmp, state)       # collective: global arrays
            self._ckptr.wait_until_finished()
            multihost_utils.sync_global_devices(f"ckpt_post_save_e{epoch}")
        else:
            host_state = jax.tree.map(np.asarray, jax.device_get(state))
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            self._ckptr.save(tmp, host_state)
            self._ckptr.wait_until_finished()
        if coord:
            # meters.json goes INTO the tmp dir before the rename, so the
            # published checkpoint is complete the instant it exists
            with open(os.path.join(tmp, "meters.json"), "w") as f:
                payload = {k: float(v) for k, v in meters.items()}
                payload["epoch"] = epoch
                if topology:
                    # process/mesh topology the state was written under —
                    # restoring under a different one would otherwise fail
                    # deep in orbax/XLA with an opaque sharding error (or
                    # silently reinterpret per-worker error-feedback state)
                    payload["_topology"] = dict(topology)
                json.dump(payload, f)
            if os.path.exists(path):           # same-epoch overwrite
                shutil.rmtree(path)
            os.replace(tmp, path)
            # the blessed rename-atomic idiom (and the model checker's
            # choke point): a crash between the epoch publish and this
            # pointer update leaves the OLD complete latest.json, and
            # restore's kept-epoch scan still finds the new epoch
            serving_protocol.write_json_atomic(self._meta_path(),
                                               {"epoch": epoch})
            if best:
                best_path = os.path.join(self.directory, "best")
                if os.path.exists(best_path):
                    shutil.rmtree(best_path)
                shutil.copytree(path, best_path)
            # rotate: keep the last `keep` epoch dirs (reference keeps 3)
            old = epoch - self.keep
            old_path = self._epoch_dir(old)
            if old >= 0 and os.path.exists(old_path):
                shutil.rmtree(old_path)
        if multi:
            # a process must not leave save() (and possibly restore
            # straight away) before the coordinator has written the
            # latest/best pointers and finished rotating — without this
            # fence a non-coordinator's immediate restore() can read a
            # missing/stale latest.json and silently report "nothing to
            # resume" (observed as a test flake under cold-compile skew)
            multihost_utils.sync_global_devices(f"ckpt_meta_e{epoch}")
        return path

    # ------------------------------------------------------------------ #

    @staticmethod
    def _legacy_sent_template(template, key: str):
        """Template with the flat engine's v0.4 'sent_bits' packed record
        (int32 words) replaced by the legacy full-[T] f32 vector under
        ``key`` — 'sent_c' (v0.3 transmit counts) or 'keep_c' (v0.2 keep
        mask). None when the state carries no packed record (the
        migration only applies to flat-engine DGC states). T comes from
        the momentum buffer (the word count is not invertible when
        T % 4096 == 2048)."""
        mem = getattr(template, "memory", None)
        if not (isinstance(mem, dict) and "sent_bits" in mem
                and "momentums_c" in mem):
            return None
        legacy = dict(mem)
        bits = legacy.pop("sent_bits")
        mc = legacy["momentums_c"]
        shape = tuple(np.shape(bits)[:-1]) + (np.shape(mc)[-1],)
        legacy[key] = np.zeros(shape, np.float32)
        return template.replace(memory=legacy)

    @staticmethod
    def _pack_transmitted_np(transmitted: np.ndarray) -> np.ndarray:
        """Bool [..., T] transmitted map -> the engine's packed int32 word
        record [..., W] (kernels.pack_sent_bits layout): word
        (a, l) of each trailing [A, 128] word view holds rows
        a*32 .. a*32+31 of lane l of the [T // 128, 128] row view."""
        T = transmitted.shape[-1]
        pad = (-T) % 4096
        if pad:
            z = np.zeros(transmitted.shape[:-1] + (pad,), bool)
            transmitted = np.concatenate([transmitted, z], axis=-1)
        s3 = transmitted.reshape(transmitted.shape[:-1] + (-1, 32, 128))
        m = np.arange(32, dtype=np.int64)[:, None]
        words = (s3.astype(np.int64) << m).sum(axis=-2)
        # fold into int32 range (bit 31 is the sign bit)
        words = np.where(words >= 2 ** 31, words - 2 ** 32, words)
        return np.ascontiguousarray(
            words.reshape(words.shape[:-2] + (-1,)).astype(np.int32))

    def saved_topology(self) -> Optional[Dict[str, int]]:
        """The ``_topology`` record of the newest restorable checkpoint
        (latest pointer first, then kept epochs, mirroring ``restore``'s
        walk), or None when there is nothing to resume or the checkpoint
        predates topology records. ``train.py`` reads this BEFORE
        building the step so an elastic restart can resolve its batch
        geometry (``resilience.elastic.resolve_batch_geometry``) against
        the world size the state was actually written under."""
        latest = self.latest_epoch()
        candidates = self._kept_epochs()
        if latest is not None:
            candidates = [latest] + [e for e in candidates if e != latest]
        for ep in candidates:
            meters_path = os.path.join(self._epoch_dir(ep), "meters.json")
            if not os.path.exists(meters_path):
                continue
            try:
                with open(meters_path) as f:
                    topo = json.load(f).get("_topology")
            except (ValueError, OSError):
                continue        # torn meters: restore() will skip it too
            return dict(topo) if topo else None
        return None

    def latest_epoch(self) -> Optional[int]:
        if not os.path.exists(self._meta_path()):
            return None
        try:
            with open(self._meta_path()) as f:
                return int(json.load(f)["epoch"])
        except (ValueError, KeyError, OSError):
            # torn/corrupt pointer (crash mid-write): restore() falls back
            # to scanning the kept epoch directories
            return None

    def _kept_epochs(self) -> list:
        """Epoch numbers of the on-disk ``e<N>`` checkpoint dirs, newest
        first (``.tmp`` staging dirs and ``best`` excluded)."""
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("e") and name[1:].isdigit() \
                    and os.path.isdir(os.path.join(self.directory, name)):
                out.append(int(name[1:]))
        return sorted(out, reverse=True)

    def restore(self, template: Any, epoch: Optional[int] = None,
                best: bool = False,
                topology: Optional[Dict[str, int]] = None,
                elastic: bool = False,
                elastic_opts: Optional[Dict[str, Any]] = None
                ) -> Optional[Tuple[Any, int, Dict[str, float]]]:
        """Restore (state, epoch, meters); None when nothing to resume.

        ``template`` is a freshly-initialized state pytree providing
        structure/shape/dtype targets. When both the checkpoint and the
        caller carry a ``topology`` record (process count / mesh shape /
        tier config), a mismatch raises an explicit error BEFORE the
        restore instead of failing deep inside orbax/XLA with an opaque
        sharding message.

        ``elastic=True`` (opt-in; the default stays fail-fast) turns a
        *world-size* mismatch into a host-side reshard instead: the
        state is restored to host numpy under the checkpoint's recorded
        world, run through ``resilience.elastic.reshard_state`` (error
        feedback merged/split with exact mass conservation), and handed
        back as a HOST pytree the caller must re-shard; the returned
        meters carry an ``_elastic`` record describing the conversion.
        ``elastic_opts`` forwards compressor-memory semantics
        (``DGCCompressor.elastic_reshard_opts()``) plus
        ``per_worker_opt`` for the Adasum scheme (refused). Checkpoints
        that predate ``_topology`` records restore as "written under the
        current topology, non-elastic" with a logged warning.

        When no explicit ``epoch`` is given and the newest checkpoint is
        corrupt (crash mid-write before atomic saves, truncated array
        files, unreadable meters), restore **falls back** to the previous
        kept epochs, newest first, instead of silently training from
        scratch while good checkpoints sit on disk. A topology mismatch is
        a configuration error, not corruption — it raises immediately.
        """
        if best:
            path = os.path.join(self.directory, "best")
            if not os.path.exists(path):
                return None
            try:
                return self._restore_one(path, -1, template, topology,
                                         best=True, elastic=elastic,
                                         elastic_opts=elastic_opts)
            except RuntimeError:
                raise
            except Exception as e:
                print(f"[checkpoint] incompatible checkpoint at {path}, "
                      f"ignoring: {self._errline(e)}")
                return None
        if epoch is not None:
            candidates = [epoch]
        else:
            latest = self.latest_epoch()
            candidates = self._kept_epochs()
            if latest is not None:
                candidates = [latest] + [e for e in candidates if e != latest]
        for i, ep in enumerate(candidates):
            path = self._epoch_dir(ep)
            if not os.path.exists(path):
                continue
            try:
                return self._restore_one(path, ep, template, topology,
                                         best=False, elastic=elastic,
                                         elastic_opts=elastic_opts)
            except RuntimeError:
                raise                     # topology mismatch: config error
            except Exception as e:
                more = any(os.path.exists(self._epoch_dir(x))
                           for x in candidates[i + 1:])
                print(f"[checkpoint] incompatible checkpoint at {path}, "
                      f"ignoring: {self._errline(e)}"
                      + (" — falling back to the previous kept epoch"
                         if more else ""))
        return None

    @staticmethod
    def _errline(e: Exception) -> str:
        s = str(e).splitlines()
        return s[0] if s else type(e).__name__

    def _restore_one(self, path: str, epoch: int, template: Any,
                     topology: Optional[Dict[str, int]], best: bool,
                     elastic: bool = False,
                     elastic_opts: Optional[Dict[str, Any]] = None
                     ) -> Tuple[Any, int, Dict[str, float]]:
        """Restore one checkpoint directory or raise (the public
        ``restore`` turns failures into kept-epoch fallback)."""
        saved_topology = None
        meters_path = os.path.join(path, "meters.json")
        if os.path.exists(meters_path):
            with open(meters_path) as f:
                saved_topology = json.load(f).get("_topology")
        if topology is not None and saved_topology is None:
            # pre-_topology checkpoint (PR-3-era and earlier): there is
            # nothing to compare or reshard against — treat it as written
            # under the current topology and restore non-elastically
            print(f"[checkpoint] {path} has no _topology record "
                  "(pre-elastic checkpoint): assuming it was written "
                  f"under the current topology {dict(topology)}; elastic "
                  "resharding is unavailable for it")
        mismatch = (topology is not None and saved_topology is not None
                    and dict(saved_topology) != dict(topology))
        elastic_info = None
        if mismatch and elastic:
            # opt-in elastic path: restore to host numpy under the world
            # the checkpoint was written at, then merge/split the
            # per-worker [world] axis (resilience/elastic.py) — the
            # caller re-shards the returned HOST state onto its mesh
            from dgc_tpu.resilience import elastic as _elastic
            opts = dict(elastic_opts or {})
            per_worker_opt = bool(opts.pop("per_worker_opt", False))
            old = _elastic.with_world(template,
                                      int(saved_topology["world"]),
                                      per_worker_opt=per_worker_opt)
            state = self._restore_guarded(path, old, force_host=True)
            state = _elastic.reshard_state(
                state, saved_topology, topology,
                per_worker_opt=per_worker_opt, **opts)
            elastic_info = {
                "from_world": int(saved_topology["world"]),
                "to_world": int(topology["world"]),
                "from_process_count":
                    int(saved_topology.get("process_count", 1)),
                "to_process_count": int(topology.get("process_count", 1)),
            }
        elif mismatch:
            raise RuntimeError(
                f"checkpoint at {path} was written under topology "
                f"{saved_topology} but this run has {dict(topology)} — "
                "resume with the same process/mesh/tier configuration, "
                "pass elastic=True (--elastic) to reshard the per-worker "
                "state across the world-size change, or start a fresh "
                "experiment directory")
        else:
            state = self._restore_guarded(path, template)
        meters: Dict[str, float] = {}
        if os.path.exists(meters_path):
            with open(meters_path) as f:
                meters = json.load(f)
        meters.pop("_topology", None)
        if elastic_info is not None:
            meters["_elastic"] = elastic_info
        if best:
            epoch = int(meters.pop("epoch", epoch))
        else:
            meters.pop("epoch", None)
        return state, epoch, meters

    def _restore_guarded(self, path: str, template: Any,
                         force_host: bool = False) -> Any:
        """``_restore_state`` with the pre-resilience fallback: a
        checkpoint without the guard-counter subtree retries without it
        (the caller re-seeds fresh guard state rather than discarding an
        otherwise-good checkpoint). The adaptive policy field is never
        saved (see :meth:`save`), so the restore always runs against the
        adaptive-stripped template and the template's fresh verdict is
        re-attached after — which also makes elastic world-size changes
        immune to the [world]-shaped ``w_frac`` leaf."""
        adaptive = getattr(template, "adaptive", None)
        if adaptive is not None:
            template = template.replace(adaptive=None)

        def read(tmpl):
            # zero-size leaves are never written (see :meth:`save`)
            return _with_empty(self._restore_state(
                path, _without_empty(tmpl), force_host=force_host), tmpl)

        try:
            state = read(template)
        except Exception:
            if getattr(template, "guards", None) is None:
                raise
            state = read(template.replace(guards=None))
            print(f"[checkpoint] {path} predates the resilience guard "
                  "counters — they start fresh")
        if adaptive is not None:
            state = state.replace(adaptive=adaptive)
        return state

    def _restore_state(self, path: str, template: Any,
                       force_host: bool = False) -> Any:
        if jax.process_count() > 1 and not force_host:
            # restore straight into the live sharded layout: global arrays
            # cannot be host-materialized per process, and the sharding on
            # the abstract template tells orbax how to place each shard
            host_template = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), x.dtype,
                    sharding=getattr(x, "sharding", None)), template)
        else:
            host_template = jax.tree.map(
                lambda x: np.asarray(jax.device_get(x)), template)
        def _restore_checked(tmpl):
            state = self._ckptr.restore(path, tmpl)
            # orbax only validates tree STRUCTURE; stale checkpoints from a
            # different flat layout restore silently with on-disk shapes —
            # reject those too
            mismatch = jax.tree.map(
                lambda a, b: np.shape(a) != np.shape(b), state, tmpl)
            if any(jax.tree.leaves(mismatch)):
                raise ValueError("leaf shapes differ from the current "
                                 "state layout")
            return state

        try:
            state = _restore_checked(host_template)
        except ValueError:
            # legacy engine-memory migrations, newest first. The
            # deferred-mask state was a full-[T] f32 keep MASK in v0.2
            # ('keep_c', 1.0 = keep) and a transmit COUNT in v0.3
            # ('sent_c', 0.0 = keep); v0.4 packs it into int32 words
            # ('sent_bits', kernels.pack_sent_bits). Retry with each
            # legacy key and convert, so old runs resume instead of
            # silently restarting — pending deferred masks survive the
            # conversion exactly. (Multi-process restores skip the
            # shape-changing migrations: the legacy leaf would need a
            # sharding the template cannot supply.)
            if jax.process_count() > 1:
                if self._legacy_sent_template(host_template,
                                              "sent_c") is not None:
                    # don't leave only the generic "incompatible,
                    # ignoring" line: a legacy checkpoint IS
                    # recoverable, just not from here — the operator
                    # should migrate it before the multi-process run
                    # silently restarts from scratch
                    print("[checkpoint] NOTE: this may be a legacy "
                          "(v0.2/v0.3) memory layout, which cannot be "
                          "migrated under multi-process restore; run a "
                          "single-process restore+save once to migrate "
                          "it, then resume multi-process")
                raise
            state = None
            for key, to_transmitted in (
                    ("sent_c", lambda s: np.asarray(s) != 0.0),
                    ("keep_c", lambda k: np.asarray(k) == 0.0)):
                legacy = self._legacy_sent_template(host_template, key)
                if legacy is None:
                    raise
                try:
                    state = _restore_checked(legacy)
                except ValueError:
                    continue
                mem = dict(state.memory)
                bits = self._pack_transmitted_np(
                    to_transmitted(mem.pop(key)))
                mem["sent_bits"] = bits
                state = state.replace(memory=mem)
                print(f"[checkpoint] migrated legacy {key} record at "
                      f"{path}")
                break
            if state is None:
                raise ValueError("no legacy memory layout matched")
        return state

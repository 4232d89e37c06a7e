"""Structured tracing: one switch, one recorder (docs/TELEMETRY.md §Tracing).

:func:`enable` (``--trace`` / ``configs/trace.py`` / ``DGC_TRACE=1``) turns
on two instruments, and with it off neither leaves anything behind:

* **Device scopes** — :func:`phase` / :func:`phased` wrap the stages of the
  step in ``jax.named_scope`` so every XLA op a stage lowers carries a
  ``dgcph.<phase>[.<part>][.b<bucket>]`` token in its ``op_name`` metadata:
  the DGC pipeline (``compensate → threshold → select → pack → allgather →
  decode → apply``, ``dense``; ``apply`` has the parts ``sort`` and
  ``stage``, and what is left of it is the pass, a Pallas call), the
  step's ``params_view``, ``plumbing``, ``fwd_bwd`` (part ``pack``: the
  gradients into the flat layout; a token model's own parts ``ssm``,
  ``attn``, ``gmu``, ``mlp``, ``head``: ``models/sambay.py``), ``update``
  (parts ``exchange`` and ``optimizer``) and ``loss``. A device profile then attributes each op
  to a phase and bucket (``benchmark/trace_reduce.py``,
  :mod:`telemetry.attrib`; both read a part token as its phase, and a
  metric of a part looks for the whole token). The scopes are
  **Python-static**: off,
  :func:`phase` returns a nullcontext and the lowered program is
  byte-identical to a build that never imported this module (the
  ``trace-off-compiles-away`` contract in ``analysis/suite``); on, they
  are pure metadata — zero new ops, zero new collectives
  (``trace-on-no-new-collectives``).

* **The recorder** — :func:`span`, :func:`count` and :func:`records` over
  one process-wide, in-memory recorder that ``enable(True)`` creates.
  Every layer reaches it as a module function, so spans (``input.*``,
  ``step.*``, ``exchange.trace``, ``checkpoint.save``, ``eval``) and
  counts (``input.queue_depth``, ``exchange.collective``,
  ``exchange.apply``, ``step.pack``, ``optimizer.wd_mask``,
  ``model.layers``, ``model.tokens``, ``model.scan_chunks``,
  ``model.attn_scores``) sit where the work happens.
  A span records its name, start and end (``perf_counter_ns``), thread,
  the id of the span that caused it and the ids its request carries
  (``step``, ``seq``; inherited by what it causes); a count belongs to
  the innermost span open when it was made that owns counts: every span
  but one opened with ``owns_counts=False``, which times and nests and
  leaves the counts to the span round it (``step.trace_model`` and
  ``exchange.trace`` split ``step.trace``'s seconds and take none of its
  counts). While a ``jax.profiler`` session is live, and only then, a
  span also opens
  ``jax.profiler.TraceAnnotation("dgc:" + name)``: the program's spans
  land in the profiler's own trace, on its clock, beside the device lanes
  (``train.py --trace --profile``). Off, :func:`span` returns one shared
  null context and :func:`count` returns at once: no lock, no ``jax``.
"""

import contextlib
import functools
import itertools
import json
import os
import sys
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

__all__ = ["PHASES", "SCOPE_PREFIX", "ANNOTATION_PREFIX", "enabled",
           "enable", "phase", "phased", "scope_name", "span", "count",
           "carry", "records", "step_summary", "step_annotation", "write"]

#: canonical phase vocabulary (attrib's table rows come out in this
#: order; unknown tokens still aggregate — the list is not a gate)
PHASES = ("compensate", "forward", "threshold", "select", "pack",
          "allgather", "decode", "apply", "dense", "params_view",
          "plumbing", "fwd_bwd", "update", "loss")

#: named-scope token prefix: scopes are ``dgcph.<phase>``,
#: ``dgcph.<phase>.<part>`` or ``dgcph.<phase>.b<bucket>`` — dots, not
#: slashes, so one scope stays one path component of the op_name metadata
SCOPE_PREFIX = "dgcph."
#: prefix of the program's host annotations in a profiler trace
ANNOTATION_PREFIX = "dgc:"
MAX_RECORDS = 65536       # the recorder's ring: the newest records win
#: ids a request carries from span to span (a train step; a batch)
REQUEST_IDS = ("step", "seq")

_ENABLED = os.environ.get("DGC_TRACE", "") == "1"


def _key_compile_cache_on_metadata(on: bool) -> None:
    """The markers live in op metadata only, and JAX's persistent
    compilation cache leaves metadata out of its key by default: a
    marker build would be served the marker-free executable of the same
    program, and the profile would carry no phase names (seen on the
    chip, PR 21 — the traced run hit the untraced run's cache entry)."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      bool(on))


def enabled() -> bool:
    """Whether scopes trace into new programs and the recorder records."""
    return _ENABLED


def enable(on: bool = True) -> bool:
    """Flip the switch; returns the previous value. On creates the
    recorder (an enabled process keeps the one it has), off drops it.

    The scopes take effect at TRACE time: already-jitted programs keep
    their compiled form (flip before ``build_train_step``)."""
    global _ENABLED, _RECORDER
    prev = _ENABLED
    _ENABLED = bool(on)
    if not _ENABLED:
        _RECORDER = None
    elif _RECORDER is None:
        _RECORDER = _Recorder()
    _key_compile_cache_on_metadata(_ENABLED)
    return prev


# ---------------------------------------------------------------------- #
# device scopes                                                          #
# ---------------------------------------------------------------------- #

def scope_name(name: str, bucket: int = -1, part: Optional[str] = None
               ) -> str:
    """The named-scope token for a phase (``bucket < 0`` = no bucket).
    A ``part`` names a region under the phase (``update.optimizer``);
    readers parse ``.b<n>`` as the only suffix, so a part reads as its
    phase and may not look like a bucket."""
    if part is not None and (bucket >= 0 or not part.isalpha()):
        raise ValueError(f"scope part {part!r}: letters only, and not "
                         "together with a bucket")
    return (SCOPE_PREFIX + name + (f".{part}" if part else "")
            + (f".b{bucket}" if bucket >= 0 else ""))


def phase(name: str, bucket: int = -1, part: Optional[str] = None):
    """Device-side phase marker for use inside traced code.

    Off (default): a nullcontext — nothing traces, the compiled program
    is byte-identical to one that never called this. On: a
    ``jax.named_scope`` whose token lands in every enclosed op's
    ``op_name`` metadata (the readers map it back to phase/bucket)."""
    if not _ENABLED:
        return contextlib.nullcontext()
    import jax
    return jax.named_scope(scope_name(name, bucket, part))


def phased(name: str):
    """Decorator form of :func:`phase` for whole-function kernels
    (``@phased("apply")`` on ``kernels.payload_apply_bits``)."""
    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with phase(name):
                return fn(*args, **kwargs)
        return wrapper
    return deco


# ---------------------------------------------------------------------- #
# the recorder                                                           #
# ---------------------------------------------------------------------- #

class _NullSpan:
    """What :func:`span` and :func:`carry` return with tracing off."""
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args) -> None:
        pass


_NULL = _NullSpan()


def _profiling():
    """``jax.profiler`` while one of its sessions is live, else None (a
    process that never imported jax has none, and stays without it)."""
    jax = sys.modules.get("jax")
    if jax is not None and jax.profiler.TraceAnnotation.is_enabled():
        return jax.profiler
    return None


class _Carry:
    """Request ids handed to what this thread opens next (:func:`carry`);
    a span is a carry that also records itself."""
    __slots__ = ("_rec", "args", "_ids", "_outer_ids")

    def __init__(self, rec, args):
        self._rec, self.args = rec, args

    def __enter__(self):
        th = self._rec.here()
        self._outer_ids = th.ids
        own = {k: self.args[k] for k in REQUEST_IDS if k in self.args}
        self._ids = th.ids = {**th.ids, **own} if own else th.ids
        return self

    def __exit__(self, *exc):
        self._rec.here().ids = self._outer_ids
        return False


class _Span(_Carry):
    """One open span; ``with`` records it when it closes."""
    __slots__ = ("name", "id", "_parent", "_t0", "_ann", "_owns")

    def __init__(self, rec, name, args, owns_counts=True):
        super().__init__(rec, args)
        self.name, self._owns = name, owns_counts

    def set(self, **args) -> None:
        """Add what only the span's own work can tell (the ``seq`` of the
        batch a queue handed over, the bytes staged)."""
        self.args.update(args)

    def __enter__(self):
        super().__enter__()
        th = self._rec.here()
        stack = th.stack
        self.id = next(self._rec.ids)
        self._parent = stack[-1] if stack else None
        stack.append(self.id)
        if self._owns:
            th.owners.append(self.id)
        prof = _profiling()
        self._ann = prof and prof.TraceAnnotation(
            ANNOTATION_PREFIX + self.name, **{**self._ids, **self.args})
        if self._ann is not None:
            self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter_ns()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        th = self._rec.here()
        th.stack.pop()
        if self._owns:
            th.owners.pop()
        args = self.args
        ids = {k: args.pop(k, self._ids.get(k)) for k in REQUEST_IDS}
        self._rec.add({"kind": "span", "name": self.name, "id": self.id,
                       "parent": self._parent,
                       "thread": threading.get_ident(),
                       "t0_ns": self._t0, "t1_ns": t1, **ids,
                       "args": args}, ms=(t1 - self._t0) / 1e6)
        return super().__exit__(*exc)


class _Recorder:
    """Spans and counts of one process, newest ``MAX_RECORDS`` kept."""

    def __init__(self):
        self.ids = itertools.count(1)       # next() is atomic in CPython
        self._thread = threading.local()
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=MAX_RECORDS)
        self._step_ms: Dict[str, float] = {}

    def here(self):
        """This thread's ``stack`` of open span ids, those of them that
        own counts (``owners``) and request ``ids``."""
        th = self._thread
        if not hasattr(th, "stack"):
            th.stack, th.owners, th.ids = [], [], {}
        return th

    def add(self, record: Dict[str, Any], ms: Optional[float] = None):
        with self._lock:
            self._ring.append(record)
            if ms is not None:
                self._step_ms[record["name"]] = self._step_ms.get(
                    record["name"], 0.0) + ms

    def count(self, name, value, args):
        th = self.here()
        self.add({"kind": "count", "name": name, "value": value,
                  "parent": th.owners[-1] if th.owners else None,
                  "thread": threading.get_ident(),
                  "t_ns": time.perf_counter_ns(),
                  **{k: args.pop(k, th.ids.get(k)) for k in REQUEST_IDS},
                  "args": args})

    def records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def step_summary(self, reset: bool) -> Dict[str, float]:
        with self._lock:
            out = {k: round(v, 4) for k, v in self._step_ms.items()}
            if reset:
                self._step_ms.clear()
        return out


_RECORDER: Optional[_Recorder] = None
if _ENABLED:
    enable(True)


def span(name: str, owns_counts: bool = True, **args):
    """Record one host span, ``<layer>.<what>``; nests freely within a
    thread. ``step=`` / ``seq=`` are the request's ids: what the span
    causes on its thread inherits them. ``with span(...) as s`` gives
    ``s.set(**args)`` for what is known only inside. A span with
    ``owns_counts=False`` splits the time of the span round it: spans
    opened inside name it as ``parent``, counts made inside belong to
    the nearest span that owns them."""
    rec = _RECORDER
    return _NULL if rec is None else _Span(rec, name, args, owns_counts)


def carry(**ids):
    """Hand request ids to the spans a call opens on this thread without
    a span of one's own (the producer's ``seq`` into ``get_batch``)."""
    rec = _RECORDER
    return _NULL if rec is None else _Carry(rec, ids)


def count(name: str, value, **args) -> None:
    """Record one count; it belongs to the span open on this thread."""
    rec = _RECORDER
    if rec is not None:
        rec.count(name, value, args)


def records() -> List[Dict[str, Any]]:
    """The recorder's spans (in the order they closed) and counts."""
    rec = _RECORDER
    return [] if rec is None else rec.records()


def step_summary(reset: bool = True) -> Dict[str, float]:
    """Per-span-name total ms since the last summary (the flight
    recorder stores one of these per step record)."""
    rec = _RECORDER
    return {} if rec is None else rec.step_summary(reset)


def step_annotation(step: int):
    """``StepTraceAnnotation("dgc:step")`` while a profiler session is
    live, so the profile groups device work by train step; else null."""
    prof = _profiling() if _RECORDER is not None else None
    return _NULL if prof is None else prof.StepTraceAnnotation(
        ANNOTATION_PREFIX + "step", step_num=int(step))


def write(path: str) -> int:
    """The records as JSON lines, written once (tmp + rename) when the
    run ends; returns how many."""
    recs = records()
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(path + ".tmp", "w") as fh:
        fh.writelines(json.dumps(r, default=str) + "\n" for r in recs)
    os.replace(path + ".tmp", path)
    return len(recs)

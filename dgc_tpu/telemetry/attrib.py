"""Device-profile attribution: XLA ops → DGC phases and buckets.

Promoted from ``scripts/profile_step.py`` so the op→phase mapping lives
in one audited place (profile_step, bench_stages, bench_model
``--trace-ab`` and bench.py's ``DGC_TRACE_AB`` all import from here).

Pipeline: run K steps under ``jax.profiler.trace(logdir)`` with
:mod:`telemetry.trace` device markers enabled → the profiler writes a
Chrome-trace ``*.trace.json.gz`` per host under
``logdir/plugins/profile/<ts>/`` → :func:`load_trace_events` +
:func:`device_events` pull out the leaf device ops →
:func:`phase_table` reads each op's ``tf_op`` metadata path for the
``dgcph.<phase>[.b<bucket>]`` token the named scopes planted and
aggregates per-phase / per-bucket device milliseconds →
:func:`profile_json` assembles the machine-readable per-bucket cost
table (schema ``dgc-profile`` v1) that the regime-aware exchange
planner consumes (docs/TELEMETRY.md §Phase attribution).

Backend note: only TPU/GPU device lanes carry ``hlo_category`` +
``tf_op`` op metadata. On a CPU-only host the profiler still writes a
trace but every event is a host lane — :func:`device_events` returns []
and the tables come out empty rather than wrong. Full attribution is an
on-chip tool; tests pin the parsing against a recorded device-format
fixture (tests/fixtures/xplane_trace.json).
"""

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from dgc_tpu.telemetry import trace as _trace

__all__ = ["PROFILE_SCHEMA", "PROFILE_VERSION", "load_trace_events",
           "device_events", "op_phase", "phase_table", "phase_rows",
           "aggregate_by_source", "profile_json", "write_profile",
           "load_profile"]

PROFILE_SCHEMA = "dgc-profile"
PROFILE_VERSION = 1

#: ``dgcph.<phase>`` / ``dgcph.<phase>.b<idx>`` / ``dgcph.<phase>.<part>``
#: anywhere in the op_name path (named scopes concatenate with "/" — the
#: token survives as one component because the scope name uses dots). A
#: part (letters only) reads as its phase and is a row under it
_PHASE_RE = re.compile(r"dgcph\.([A-Za-z_]+)(?:\.b(\d+)|\.([A-Za-z]+))?")

#: envelope / non-op lanes excluded from leaf totals
_ENVELOPES = ("jit_", "while", "Overhead", "idle")


# ---------------------------------------------------------------------- #
# trace loading / event selection                                        #
# ---------------------------------------------------------------------- #

def load_trace_events(path: str) -> List[Dict]:
    """Events of a profiler trace. ``path`` may be a profiler logdir
    (newest ``plugins/profile/*/*.trace.json.gz`` wins), or a direct
    ``.trace.json[.gz]`` / Chrome-trace ``.json`` file."""
    if os.path.isdir(path):
        cands = sorted(glob.glob(os.path.join(
            path, "plugins/profile/*/*.trace.json.gz")),
            key=os.path.getmtime)
        if not cands:
            raise FileNotFoundError(
                f"no *.trace.json.gz under {path}/plugins/profile/ — "
                f"did jax.profiler.trace() run?")
        path = cands[-1]
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        obj = json.load(fh)
    return obj.get("traceEvents", [])


def _pid_names(events: List[Dict]) -> Dict[int, str]:
    out = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            out[ev.get("pid")] = ev.get("args", {}).get("name", "")
    return out


def device_events(events: List[Dict], device: str = "auto") -> List[Dict]:
    """Leaf device-op events: ph "X" with a duration, on a device lane
    (process name contains "tpu"/"gpu", not "host"), not an envelope
    (jit_*/while wrappers), carrying ``hlo_category`` op metadata (the
    step-number / module lanes double-count ops and are dropped).

    ``device`` — "auto" takes any non-host accelerator lane; "tpu"/"gpu"
    restrict to that backend. CPU-only traces yield [] (host lanes carry
    no op metadata — see module docstring)."""
    pid_name = _pid_names(events)
    want = ("tpu", "gpu") if device == "auto" else (device,)
    out = []
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev:
            continue
        pname = pid_name.get(ev.get("pid"), "").lower()
        if "host" in pname or not any(w in pname for w in want):
            continue
        if ev["name"].startswith(_ENVELOPES):
            continue
        args = ev.get("args", {}) or {}
        if "hlo_category" not in args:
            continue
        out.append(ev)
    return out


# ---------------------------------------------------------------------- #
# op → phase mapping                                                     #
# ---------------------------------------------------------------------- #

def op_phase(event: Dict) -> Tuple[Optional[str], Optional[int]]:
    """(phase, bucket) of one device-op event, or (None, None) when the
    op's scope path carries no ``dgcph.`` token. The innermost (last)
    token wins — nested markers refine, not shadow."""
    name, bucket, _ = _innermost(event)
    return name, bucket


def _innermost(event: Dict) -> Tuple[Optional[str], Optional[int],
                                     Optional[str]]:
    """(phase, bucket, part) of the op's innermost ``dgcph.`` token."""
    tf_op = (event.get("args", {}) or {}).get("tf_op", "")
    hits = _PHASE_RE.findall(tf_op)
    if not hits:
        return None, None, None
    name, bucket, part = hits[-1]
    return name, (int(bucket) if bucket else None), part or None


def phase_table(events: List[Dict], steps: int = 1) -> Dict:
    """Aggregate device-op durations by DGC phase and bucket.

    Returns ``{"total_ms", "attributed_ms", "unattributed_ms",
    "phases": {phase: ms}, "parts": {"<phase>.<part>": ms},
    "buckets": {"b<idx>": {phase: ms}}, "ops": n}`` — all ms figures
    divided by ``steps`` (per-step). A part's time is IN its phase's
    (``apply.sort`` and ``apply.stage`` in ``apply``, whose rest is the
    pass; ``fwd_bwd.pack``; ``update.optimizer`` / ``update.exchange``,
    the latter what the engine runs outside its own phases): ``parts``
    splits a phase, it adds nothing to a total."""
    phases: Dict[str, float] = defaultdict(float)
    parts: Dict[str, float] = defaultdict(float)
    buckets: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    total = attributed = 0.0
    for ev in events:
        ms = ev["dur"] / 1e3
        total += ms
        name, bucket, part = _innermost(ev)
        if name is None:
            continue
        attributed += ms
        phases[name] += ms
        if part is not None:
            parts[f"{name}.{part}"] += ms
        if bucket is not None:
            buckets[f"b{bucket}"][name] += ms
    k = max(int(steps), 1)
    order = {p: i for i, p in enumerate(_trace.PHASES)}
    return {
        "total_ms": round(total / k, 6),
        "attributed_ms": round(attributed / k, 6),
        "unattributed_ms": round((total - attributed) / k, 6),
        "phases": {p: round(v / k, 6) for p, v in sorted(
            phases.items(), key=lambda kv: order.get(kv[0], 99))},
        "parts": {p: round(v / k, 6) for p, v in sorted(parts.items())},
        "buckets": {b: {p: round(v / k, 6) for p, v in sorted(
            t.items(), key=lambda kv: order.get(kv[0], 99))}
            for b, t in sorted(buckets.items(),
                               key=lambda kv: int(kv[0][1:]))},
        "ops": len(events),
    }


def phase_rows(table: Dict) -> List[Tuple[str, float]]:
    """The rows a :func:`phase_table` prints as: each phase in pipeline
    order, its parts indented beneath it."""
    rows = []
    for phase, ms in table["phases"].items():
        rows.append((phase, ms))
        rows += [("  " + part, v) for part, v in table["parts"].items()
                 if part.partition(".")[0] == phase]
    return rows


def aggregate_by_source(events: List[Dict], repo_root: str,
                        ) -> Tuple[Dict[str, float],
                                   Dict[str, Tuple[float, tuple]], float]:
    """profile_step's per-source view: (by_source, by_name,
    leaf_total_ms). by_source groups ops by ``source`` file:line (repo
    paths shortened; site-packages bucketed as "model"/"lib:{cat}"),
    by_name keeps op names with (src, cat, tf_op) sample metadata."""
    by_source: Dict[str, float] = defaultdict(float)
    by_name: Dict[str, list] = defaultdict(lambda: [0.0, None])
    leaf_total = 0.0
    for ev in events:
        args = ev.get("args", {}) or {}
        ms = ev["dur"] / 1e3
        src = args.get("source", "")
        src = src.replace(repo_root + "/", "").replace("scripts/../", "")
        cat = args.get("hlo_category", "?")
        if "site-packages" in src or not src:
            tfop = args.get("tf_op", "")
            key = ("model" if "ResNet" in tfop or "transpose" in tfop
                   or "conv" in tfop else f"lib:{cat}")
        else:
            key = f"{src} [{cat}]"
        by_source[key] += ms
        name = ev["name"]
        by_name[name][0] += ms
        if by_name[name][1] is None:
            by_name[name][1] = (src, cat, args.get("tf_op", "")[-80:])
        leaf_total += ms
    return (dict(by_source),
            {k: (v[0], v[1]) for k, v in by_name.items()}, leaf_total)


# ---------------------------------------------------------------------- #
# profile.json — the planner's cost table                                #
# ---------------------------------------------------------------------- #

def profile_json(dgc_table: Dict, dense_table: Optional[Dict] = None,
                 static: Optional[Dict] = None,
                 measured_overhead_ms: Optional[float] = None) -> Dict:
    """Assemble the machine-readable per-bucket cost table.

    ``dgc_table`` / ``dense_table`` — :func:`phase_table` outputs (per
    step). The exchange planner reads ``dgc.buckets`` (per-bucket,
    per-phase device ms — what a wire-format change would buy) and
    ``delta_ms`` (dgc leaf total minus dense: the profiled compression
    overhead, to reconcile against the paired-timing BENCH number in
    ``measured_overhead_ms``)."""
    out = {
        "schema": PROFILE_SCHEMA, "version": PROFILE_VERSION,
        "static": dict(static or {}),
        "dgc": dgc_table,
    }
    if dense_table is not None:
        out["dense"] = dense_table
        out["delta_ms"] = round(
            dgc_table["total_ms"] - dense_table["total_ms"], 6)
    exch = sum(v for p, v in dgc_table.get("phases", {}).items()
               if p not in ("fwd_bwd", "update", "loss"))
    out["exchange_phase_ms"] = round(exch, 6)
    if measured_overhead_ms is not None:
        out["measured_overhead_ms"] = round(float(measured_overhead_ms), 6)
    return out


def write_profile(obj: Dict, path: str) -> str:
    """Atomically write profile.json (tmp + rename)."""
    d = os.path.dirname(os.path.abspath(path))
    os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(obj, fh, indent=1)
    os.replace(tmp, path)
    return path


def load_profile(path: str) -> Dict:
    with open(path) as fh:
        obj = json.load(fh)
    if obj.get("schema") != PROFILE_SCHEMA:
        raise ValueError(f"{path}: not a {PROFILE_SCHEMA} file "
                         f"(schema={obj.get('schema')!r})")
    if obj.get("version") != PROFILE_VERSION:
        raise ValueError(f"{path}: profile version {obj.get('version')} "
                         f"(reader supports {PROFILE_VERSION})")
    return obj

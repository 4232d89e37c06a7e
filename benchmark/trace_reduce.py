"""From the profiler's trace to per-arm device tables.

One profiler session holds ``trace_steps`` steps of every arm, one arm
after the other, each arm's segment wrapped in a host annotation
``bench:<arm>:segment`` (``jax.profiler.TraceAnnotation``, so it sits on
the trace's own clock). The reduction:

1. load the session's ``*.trace.json.gz`` (an export at the profiler's
   1,000,000-event cap has lost events and is an error);
2. keep the device-op events: complete events on a device process that
   carry ``hlo_category`` (the step and module lanes do not);
3. give every op its SELF time — its duration minus what the ops nested
   inside it on the same lane cover — so that a ``while`` or a fusion
   wrapper is not counted twice; an op with nothing nested is a leaf, and
   only leaves count as "an operation ran on the device";
4. split the ops by arm (the host segment that contains their start; a
   session of ONE arm gives it every op, and one of several arms whose
   device lane is off the host's clock is an error: see ``split_arms``),
   and per chip compute the busy union, the idle gaps, and the ``dgcph.*``
   phase table (the op -> phase rule is ``telemetry/attrib.py``'s: the
   innermost ``dgcph.<phase>[.b<bucket>]`` token of the op's ``tf_op``).

Zero device events is an error, never an empty table.
"""

import glob
import gzip
import json
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

EVENT_CAP = 1_000_000
ANNOTATION_PREFIX = "bench:"
_PHASE_RE = re.compile(r"dgcph\.([A-Za-z_]+)(?:\.b(\d+))?")
#: phases of the exchange engine (everything between the gradient and the
#: optimizer update); the rest of the vocabulary is the step's own
EXCHANGE_PHASES = ("compensate", "forward", "threshold", "select", "pack",
                   "allgather", "decode", "apply", "dense")
COLLECTIVE_CATEGORIES = ("all-reduce", "all-gather", "all-to-all",
                         "collective-permute", "reduce-scatter",
                         "collective-broadcast")


#: the share of a session's device time that may lie outside every arm's
#: segment before the split is refused (none does in the chip fixtures)
STRAY_SHARE = 0.01


class TraceError(RuntimeError):
    """A trace that cannot be reduced (empty, capped, or unsplittable)."""


class Op(NamedTuple):
    name: str
    category: str
    tf_op: str
    start: float          # seconds on the trace's clock
    dur: float            # seconds
    self_dur: float       # seconds, nested ops taken out
    phase: Optional[str]
    bucket: Optional[int]


def is_leaf(op: Op) -> bool:
    """No other op of its lane ran inside it (a ``while`` is not a leaf)."""
    return op.self_dur == op.dur


def is_collective(op: Op) -> bool:
    """A collective op, or the ``-start`` or ``-done`` half of an
    asynchronous one."""
    return is_leaf(op) and (
        op.category.startswith(COLLECTIVE_CATEGORIES)
        or op.name.startswith(COLLECTIVE_CATEGORIES))


def collective_spans(ops: List[Op]) -> List[Tuple[float, float]]:
    """Merged intervals in which a collective is in flight on one chip: a
    synchronous op's own interval; for an asynchronous one, from the start
    of ``<kind>-start[.n]`` to the end of the ``<kind>-done[.n]`` with the
    same suffix (a half without its partner counts by itself)."""
    pending: Dict[str, float] = {}
    spans = []
    for op in ops:
        if not is_collective(op):
            continue
        kind, _, suffix = op.name.partition(".")
        if kind.endswith("-start"):
            pending[kind[:-len("-start")] + "." + suffix] = op.start
            spans.append((op.start, op.start + op.dur))
        elif kind.endswith("-done"):
            began = pending.pop(kind[:-len("-done")] + "." + suffix,
                                op.start)
            spans.append((began, op.start + op.dur))
        else:
            spans.append((op.start, op.start + op.dur))
    return merge_intervals(spans)


def is_pallas(op: Op) -> bool:
    """A Pallas (Mosaic) kernel: its ``tf_op`` path ends in ``pallas_call``
    under the jitted kernel's name (``.../jit(topk_rows)/pallas_call:``).
    XLA's own custom calls (category ``custom-call``, no ``tf_op``) take no
    device time and are not kernels."""
    return is_leaf(op) and "pallas_call" in op.tf_op


class ChipTrace(NamedTuple):
    chip: str
    ops: List[Op]
    window: Tuple[float, float]        # first op start, last op end
    busy: List[Tuple[float, float]]    # merged busy intervals

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        return sum(b - a for a, b in self.busy)


class ArmTrace(NamedTuple):
    arm: str
    steps: int
    chips: List[ChipTrace]
    host: List[Tuple[str, float, float]]   # (span, start, end), this arm's


# ---------------------------------------------------------------------- #
# loading                                                                #
# ---------------------------------------------------------------------- #

def find_trace_file(logdir: str) -> str:
    cands = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.trace.json.gz")),
        key=os.path.getmtime)
    if not cands:
        raise TraceError(f"no *.trace.json.gz under {logdir}: the "
                         "profiler wrote nothing")
    return cands[-1]


def load_events(path: str) -> List[Dict[str, Any]]:
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as fh:
        events = json.load(fh).get("traceEvents", [])
    if len(events) >= EVENT_CAP:
        raise TraceError(
            f"{path}: {len(events)} events, the export's cap of "
            f"{EVENT_CAP}: device ops have been dropped; trace fewer "
            "steps or lower the host tracer level")
    return events


def _process_names(events) -> Dict[Any, str]:
    return {ev.get("pid"): (ev.get("args") or {}).get("name", "")
            for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "process_name"}


def _is_device(process_name: str) -> bool:
    low = process_name.lower()
    return "host" not in low and ("tpu" in low or "gpu" in low)


def op_phase(tf_op: str) -> Tuple[Optional[str], Optional[int]]:
    hits = _PHASE_RE.findall(tf_op or "")
    if not hits:
        return None, None
    name, bucket = hits[-1]
    return name, (int(bucket) if bucket else None)


def _with_self_time(raw: List[Tuple[int, int, Dict]]) -> List[Op]:
    """Ops of one lane, sorted; self time by a stack sweep (an op that
    starts inside another one is its child). Times are whole picoseconds
    here, the trace's resolution, so that an op which starts where the
    last one ends is not taken for its child."""
    raw.sort(key=lambda r: (r[0], -r[1]))
    out: List[List[Any]] = []          # [start, dur, self_dur, event]
    stack: List[int] = []
    for start, dur, ev in raw:
        while stack and sum(out[stack[-1]][:2]) <= start:
            stack.pop()
        if stack:
            parent = out[stack[-1]]
            parent[2] -= min(dur, parent[0] + parent[1] - start)
        out.append([start, dur, dur, ev])
        stack.append(len(out) - 1)
    ops = []
    for start, dur, self_dur, ev in out:
        args = ev.get("args") or {}
        tf_op = args.get("tf_op", "") or ""
        phase, bucket = op_phase(tf_op)
        ops.append(Op(name=ev.get("name", ""),
                      category=args.get("hlo_category", ""),
                      tf_op=tf_op, start=start * 1e-12, dur=dur * 1e-12,
                      self_dur=max(self_dur, 0) * 1e-12, phase=phase,
                      bucket=bucket))
    return ops


def device_ops(events) -> Dict[str, List[Op]]:
    """{chip: ops sorted by start}; ops carry ``hlo_category``."""
    pname = _process_names(events)
    lanes: Dict[Tuple[Any, Any], List] = defaultdict(list)
    for ev in events:
        if ev.get("ph") != "X" or "dur" not in ev or "ts" not in ev:
            continue
        name = pname.get(ev.get("pid"), "")
        if not _is_device(name):
            continue
        if "hlo_category" not in (ev.get("args") or {}):
            continue
        lanes[(name, ev.get("tid"))].append(
            (round(ev["ts"] * 1e6), round(ev["dur"] * 1e6), ev))
    chips: Dict[str, List[Op]] = defaultdict(list)
    for (name, _), raw in lanes.items():
        chips[name].extend(_with_self_time(raw))
    for ops in chips.values():
        ops.sort(key=lambda o: o.start)
    return dict(chips)


def host_annotations(events) -> List[Tuple[str, float, float]]:
    """The harness's own spans in the trace: (name without prefix, start,
    end) in seconds, sorted by start."""
    out = []
    for ev in events:
        name = ev.get("name", "")
        if (ev.get("ph") == "X" and isinstance(name, str)
                and name.startswith(ANNOTATION_PREFIX) and "dur" in ev):
            out.append((name[len(ANNOTATION_PREFIX):], ev["ts"] * 1e-6,
                        (ev["ts"] + ev["dur"]) * 1e-6))
    out.sort(key=lambda s: s[1])
    return out


# ---------------------------------------------------------------------- #
# intervals                                                              #
# ---------------------------------------------------------------------- #

def merge_intervals(intervals: List[Tuple[float, float]]
                    ) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def gaps(busy: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Idle intervals between consecutive merged busy intervals."""
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] > busy[i][1]]


def overlap_s(a: List[Tuple[float, float]], b: List[Tuple[float, float]]
              ) -> float:
    """Seconds covered by both merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


# ---------------------------------------------------------------------- #
# arms                                                                   #
# ---------------------------------------------------------------------- #

def split_arms(events, steps: Dict[str, int]) -> Dict[str, ArmTrace]:
    """Per-arm traces. ``steps`` maps arm -> steps traced.

    The device lane is NOT always on the host annotations' clock: in the
    first profiler session of a 504M-parameter token fixture every device
    op sat 0.780 s before the host's dispatch of it (a session of its own
    for each arm, the second aligned to a millisecond; my chip run, PR
    38), and a split by the segment's bounds kept 793 of 1,360 ops: every
    per-step reading 0.57 of itself, a roofline share of 165%. So a
    session of one arm (``residency: one``) gives that arm every device
    op, and in a session of several arms ops outside every segment that
    hold more than ``STRAY_SHARE`` of the device time are an error, not
    a table that is quietly short."""
    chips = device_ops(events)
    n_ops = sum(len(v) for v in chips.values())
    if n_ops == 0:
        raise TraceError("the trace holds no device-op event (no event "
                         "with hlo_category on a device process): nothing "
                         "ran on the device inside the profiler session, "
                         "or the trace is not a device trace")
    host = host_annotations(events)
    segments = {name.split(":")[0]: (a, b) for name, a, b in host
                if name.endswith(":segment")}
    missing = [arm for arm in steps if arm not in segments]
    if missing:
        raise TraceError(
            f"no '{ANNOTATION_PREFIX}<arm>:segment' annotation for "
            f"{missing} in the trace ({len(host)} harness annotations "
            "found): the arms cannot be told apart")
    alone = len(steps) == 1
    if not alone:
        bounds = [segments[arm] for arm in steps]
        stray = sum(o.self_dur for ops in chips.values() for o in ops
                    if not any(lo <= o.start <= hi for lo, hi in bounds))
        total = sum(o.self_dur for ops in chips.values() for o in ops)
        if stray > STRAY_SHARE * total:
            raise TraceError(
                f"{stray:.6f} s of the session's {total:.6f} s of device "
                f"ops start outside every arm's segment {bounds}: the "
                "device lane is not on the host annotations' clock, and "
                "the arms cannot be told apart")
    out = {}
    for arm, n in steps.items():
        lo, hi = segments[arm]
        chip_traces = []
        for chip, ops in sorted(chips.items()):
            mine = ops if alone else [o for o in ops if lo <= o.start <= hi]
            if not mine:
                raise TraceError(f"arm '{arm}': no device op on {chip} "
                                 "inside its segment")
            busy = merge_intervals([(o.start, o.start + o.dur)
                                    for o in mine if is_leaf(o)])
            chip_traces.append(ChipTrace(
                chip=chip, ops=mine, window=(busy[0][0], busy[-1][1]),
                busy=busy))
        spans = [(name.split(":", 1)[1], a, b) for name, a, b in host
                 if name.startswith(arm + ":")
                 and not name.endswith(":segment")]
        out[arm] = ArmTrace(arm=arm, steps=n, chips=chip_traces,
                            host=spans)
    return out


def phase_table(arm: ArmTrace) -> Dict[str, Any]:
    """Device ms per step by ``dgcph`` phase and bucket, mean over the
    arm's chips; ``unattributed`` is what carries no scope."""
    phases: Dict[str, float] = defaultdict(float)
    buckets: Dict[str, Dict[str, float]] = defaultdict(
        lambda: defaultdict(float))
    total = 0.0
    for chip in arm.chips:
        for op in chip.ops:
            total += op.self_dur
            key = op.phase or "unattributed"
            phases[key] += op.self_dur
            if op.bucket is not None:
                buckets[f"b{op.bucket}"][key] += op.self_dur
    per = 1e3 / (arm.steps * len(arm.chips))
    return {
        "steps": arm.steps, "chips": len(arm.chips),
        "ops": sum(len(c.ops) for c in arm.chips),
        "total_ms": total * per,
        "phases": {p: v * per for p, v in sorted(
            phases.items(), key=lambda kv: -kv[1])},
        "buckets": {b: {p: v * per for p, v in t.items()}
                    for b, t in sorted(buckets.items(),
                                       key=lambda kv: int(kv[0][1:]))},
    }


def sum_ms_per_step(arm: ArmTrace, keep) -> float:
    """Mean over chips of the self time of ops ``keep(op)`` selects, in
    ms per step."""
    total = sum(op.self_dur for chip in arm.chips for op in chip.ops
                if keep(op))
    return total * 1e3 / (arm.steps * len(arm.chips))


def idle_share(chip: ChipTrace) -> float:
    return 1.0 - chip.busy_s / chip.window_s if chip.window_s > 0 else 0.0


def label_gaps(arm: ArmTrace) -> Dict[str, float]:
    """Idle seconds of the arm's fullest-idle chip, by the harness span
    that covers the middle of each gap ('unlabelled' when none does)."""
    chip = max(arm.chips, key=idle_share)
    out: Dict[str, float] = defaultdict(float)
    for a, b in gaps(chip.busy):
        mid = 0.5 * (a + b)
        label = "unlabelled"
        for name, lo, hi in arm.host:
            if lo <= mid <= hi:
                label = name
                break
        out[label] += b - a
    return dict(out)

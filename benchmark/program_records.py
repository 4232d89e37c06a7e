"""What the program's own recorder holds: the spans and counts that
``dgc_tpu.telemetry.trace`` keeps in memory (``records()``, same process;
the harness calls ``enable(True)`` before it builds under ``--trace 1``).
A program without such a recorder (the parent of the PR that added it)
yields no record, and the readers built on this return nothing."""

from typing import Any, Dict, List, Optional, Tuple


def records() -> List[Dict[str, Any]]:
    try:
        from dgc_tpu.telemetry import trace
    except ImportError:
        return []
    read = getattr(trace, "records", None)
    return read() if callable(read) else []


def span_seconds(name: str, under: Optional[str] = None) -> List[float]:
    """Durations of the spans called ``name``, in the order they closed;
    with ``under``, of those opened directly inside a span of that name."""
    recs = records()
    spans = [r for r in recs if r.get("kind") == "span"]
    inside = {r["id"] for r in spans if r.get("name") == under}
    return [(r["t1_ns"] - r["t0_ns"]) * 1e-9 for r in spans
            if r.get("name") == name
            and (under is None or r.get("parent") in inside)]


def batch_seconds(setup_spans: Dict[str, List[float]]
                  ) -> Tuple[Optional[str], List[float]]:
    """(whose spans, seconds each global batch took to make). The
    program's ``input.get_batch`` wherever it recorded any (its image
    splits; a ``pipeline`` cell's batches are made on its producer thread,
    where the harness cannot stand); else the harness's own round the same
    work (``inputs.host_batches``: a ``tokens`` configuration's batches,
    which go through no split of the program's), the pool's one draw
    shared over its batches; (None, []) where neither recorded one."""
    made = span_seconds("input.get_batch")
    if made:
        return "program:input.get_batch", made
    made = setup_spans.get("input.batch", [])
    if not made:
        return None, []
    pool = sum(setup_spans.get("input.pool", [])) / len(made)
    return "harness:input.batch", [s + pool for s in made]


def collective_bytes(engine: str) -> Optional[int]:
    """Bytes per worker and step that ``engine`` hands to its collectives:
    the sum of the ``exchange.collective`` counts made under the LAST
    ``step.trace`` span that traced that engine (a step traced twice is
    counted once; span ids grow with time)."""
    recs = records()
    traces = {r["id"] for r in recs
              if r.get("kind") == "span" and r.get("name") == "step.trace"}
    by_trace: Dict[int, int] = {}
    for r in recs:
        if (r.get("kind") == "count"
                and r.get("name") == "exchange.collective"
                and r["args"].get("engine") == engine
                and r.get("parent") in traces):
            by_trace[r["parent"]] = by_trace.get(r["parent"], 0) + r["value"]
    return by_trace[max(by_trace)] if by_trace else None

"""Compression-health telemetry for the DGC stack.

Three layers, one schema (``registry``):

* :mod:`dgc_tpu.telemetry.taps` — in-graph stat collection: a small pytree
  of per-step device scalars computed inside the jitted train/bench step and
  returned as an aux metrics output. Zero added host syncs or dispatches —
  the stats ride the step's existing outputs; ``telemetry=off`` never traces
  them at all.
* :mod:`dgc_tpu.telemetry.sink` — host-side async drain: a background
  thread pulls completed step-stat device buffers and appends
  schema-versioned JSONL (with rotation), plus CSV/summary readers.
* :mod:`dgc_tpu.telemetry.regress` — CLI regression gate comparing a fresh
  bench/telemetry run against a recorded baseline
  (``python -m dgc_tpu.telemetry.regress runs/baseline.json runs/new.jsonl``).

Plus the tracing/postmortem layer (same sink, own schemas):

* :mod:`dgc_tpu.telemetry.trace` — one switch, one in-memory recorder:
  host spans and counts where the work happens (``dgc:*`` annotations in
  the profiler's trace while a session is live) + device-side ``dgcph.*``
  named-scope phase markers, Python-static when off.
* :mod:`dgc_tpu.telemetry.attrib` — device-profile parsing: XLA ops →
  DGC phases/buckets via the markers; emits the per-bucket ``profile.json``
  cost table the exchange planner consumes.
* :mod:`dgc_tpu.telemetry.flight` — crash flight recorder: ring buffer of
  recent step records, dumped atomically on stall/preemption/nonfinite
  streak.

See docs/TELEMETRY.md.
"""

from dgc_tpu.telemetry.registry import (
    RUN_METRICS,
    SCHEMA,
    SCHEMA_VERSION,
    STEP_METRICS,
    MetricSpec,
    make_header,
    step_out_specs,
    step_stat_names,
)
from dgc_tpu.telemetry.flight import FlightRecorder, NonfiniteStreak
from dgc_tpu.telemetry.sink import (SchemaMismatchError, TelemetrySink,
                                    read_run, summarize)

__all__ = [
    "MetricSpec", "SCHEMA", "SCHEMA_VERSION", "STEP_METRICS", "RUN_METRICS",
    "make_header", "step_stat_names", "step_out_specs",
    "TelemetrySink", "SchemaMismatchError", "read_run", "summarize",
    "FlightRecorder", "NonfiniteStreak",
]

"""The harness's own spans: kept in memory, reduced at the end.

Three spans per step, recorded from the benchmark's side of the calls into
the program's layers: ``input.next`` (the ``next()`` on the staged
iterator: time the loop waits for data), ``dispatch`` (the call of the
jitted step, which returns when the step is enqueued) and one ``wait`` per
round (``block_until_ready``), under the arm's name; and under ``setup``
the making of each resident global batch (``inputs.host_batches``). While a
profiler session is live each span
also opens a ``jax.profiler.TraceAnnotation`` named ``bench:<arm>:<span>``,
which puts it on the device trace's clock.
"""

import contextlib
import time
from collections import defaultdict
from typing import Dict, List, Tuple


class Spans:
    def __init__(self):
        self.records: List[Tuple[str, str, float, float]] = []
        self.annotate = False

    @contextlib.contextmanager
    def span(self, arm: str, name: str):
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation(f"bench:{arm}:{name}")
        else:
            ann = contextlib.nullcontext()
        with ann:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                self.records.append((arm, name, t0, time.perf_counter()))

    def mark(self) -> int:
        return len(self.records)

    def seconds(self, since: int = 0) -> Dict[str, Dict[str, List[float]]]:
        """{arm: {span: [seconds, ...]}} of the records from ``since``."""
        out: Dict[str, Dict[str, List[float]]] = defaultdict(
            lambda: defaultdict(list))
        for arm, name, t0, t1 in self.records[since:]:
            out[arm][name].append(t1 - t0)
        return {arm: dict(v) for arm, v in out.items()}

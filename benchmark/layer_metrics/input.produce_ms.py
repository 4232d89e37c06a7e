"""input layer (dgc_tpu/data/datasets.py): milliseconds the split's
``get_batch`` takes to make one global batch, mean over the program's
``input.get_batch`` spans of the run (set-up in the resident cells, the
producer thread in a pipeline cell)."""

from benchmark.program_records import span_seconds


def read(trace, spans, cell):
    made = span_seconds("input.get_batch") if trace["arms"] else []
    return 1e3 * sum(made) / len(made) if made else None

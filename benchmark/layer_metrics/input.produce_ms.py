"""input layer (dgc_tpu/data/datasets.py; for tokens benchmark/inputs.py):
host milliseconds to make one global batch as the step receives it, mean
over the run's batches (set-up in the resident cells, the producer thread
in a pipeline cell): the program's ``input.get_batch`` spans where it
recorded any, else the harness's own round the same work
(``program_records.batch_seconds`` says which)."""

from benchmark.program_records import batch_seconds


def read(trace, spans, cell):
    if not trace["steps"]:
        return None
    _, made = batch_seconds(trace["setup_spans"])
    return 1e3 * sum(made) / len(made) if made else None

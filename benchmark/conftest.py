"""Six tests under ``tests/`` pin what ``BENCHMARK.json`` and
``benchmark/traffic/`` held before PR 45 (ISSUE 45, ``model_config``):
``test_trace_reduce.py``'s ``test_every_reader_on_the_{one,four}_chip_trace``
and ``test_part_readers.py``'s
``test_every_reader_of_the_benchmark_on_the_chip_fixtures`` (``len(want) ==
29``), ``test_rehearsal.py::test_the_fixtures_per_layer_metrics_are_the_repos``
(the fixture's own ``BENCHMARK.json``) and
``test_cells.py::test_every_traffic_file_loads`` (no traffic file stated
``residency``). PR 45 appended five readers (``model.ssm_ms``,
``model.attn_ms``, ``model.gmu_ms``, ``model.mlp_ms``, ``model.head_ms``)
and the first ``residency: one`` traffic file (``steady.s2048.one``), and,
changing the program, may not edit a file the benchmark had.
``tests/test_model_readers.py`` holds the five readers to a hand-made trace
with and without their tokens and to 0.0 on both chip fixtures, and the
new traffic file to what it states, so nothing goes unchecked. The
``benchmark`` PR that folds the five into the pinned dictionaries (34
each) and the fixture's ``BENCHMARK.json``, and lets a traffic file state
``one``, deletes this file (the marks are strict: a test that passes again
fails the run until it does; ROADMAP Speed 11)."""

import pytest

OUTDATED = {
    "test_trace_reduce.py": ("test_every_reader_on_the_one_chip_trace",
                             "test_every_reader_on_the_four_chip_trace"),
    "test_part_readers.py": (
        "test_every_reader_of_the_benchmark_on_the_chip_fixtures",),
    "test_rehearsal.py": (
        "test_the_fixtures_per_layer_metrics_are_the_repos",),
    "test_cells.py": ("test_every_traffic_file_loads",),
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.originalname in OUTDATED.get(item.path.name, ()):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="pinned before PR 45's five per_layer "
                "entries and its residency-one traffic file; see "
                "test_model_readers.py"))

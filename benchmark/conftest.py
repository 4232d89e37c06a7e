"""Three tests under ``tests/`` enumerate ``BENCHMARK.json``'s ``per_layer``
against wholes pinned at 21 entries: ``test_trace_reduce.py``'s
``test_every_reader_on_the_{one,four}_chip_trace`` (``len(want) == 21``)
and ``test_rehearsal.py::test_the_fixtures_per_layer_metrics_are_the_repos``
(the fixture's own ``BENCHMARK.json``). PR 42 appended eight readers and,
changing the program, may not edit a file the benchmark had: those three
now see eight entries they do not expect.
``tests/test_part_readers.py`` pins both chip fixtures' whole result, all
29 keys, the old values letter for letter, so nothing goes unchecked. The
`benchmark` PR that folds the eight into the pinned dictionaries and the
fixture's ``BENCHMARK.json`` deletes this file (the marks are strict: a
test that passes again fails the run until it does; ROADMAP Speed 11)."""

import pytest

OUTDATED = {
    "test_trace_reduce.py": ("test_every_reader_on_the_one_chip_trace",
                             "test_every_reader_on_the_four_chip_trace"),
    "test_rehearsal.py": (
        "test_the_fixtures_per_layer_metrics_are_the_repos",),
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name in OUTDATED.get(item.path.name, ()):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="pinned before PR 42's eight per_layer "
                "entries; see test_part_readers.py"))

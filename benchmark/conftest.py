"""``tests/test_trace_reduce.py`` pins, in two tests, the result of EVERY
reader ``BENCHMARK.json`` lists against the two chip fixtures. PR 23
appended ten readers and, changing the program, may not edit a file the
benchmark had: those two comparisons now see ten keys they do not expect.
``tests/test_program_readers.py`` pins both dictionaries whole, the old
values letter for letter, so nothing goes unchecked. The `benchmark` PR
that brings the two up to date deletes this file (the marks are strict: a
test that passes again fails the run until it does)."""

import pytest

OUTDATED = ("test_every_reader_on_the_one_chip_trace",
            "test_every_reader_on_the_four_chip_trace")


def pytest_collection_modifyitems(items):
    for item in items:
        if (item.name in OUTDATED
                and item.path.name == "test_trace_reduce.py"):
            item.add_marker(pytest.mark.xfail(
                strict=True, reason="pinned before PR 23's ten per_layer "
                "entries; see test_program_readers.py"))

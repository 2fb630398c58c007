"""The suite's per-test time limit (``conftest.py`` at the repo root),
exercised on inner test files in a separate pytest process, so that the
inner limits and the backstop's exit leave this process alone."""
from pathlib import Path

import pytest

CONFTEST = (Path(__file__).resolve().parents[1] / "conftest.py").read_text()

INNER = '''
import signal

import pytest


@pytest.mark.time_limit(2)
def test_spins():
    n = 0
    while True:  # the spinning line
        n += 1


@pytest.mark.time_limit(2)
def test_within_its_limit():
    pass


@pytest.mark.time_limit(1)
def test_spins_with_the_alarm_ignored():
    # a stand-in for a hang in C code, where the alarm's handler never
    # runs
    signal.signal(signal.SIGALRM, signal.SIG_IGN)
    while True:
        pass
'''


@pytest.fixture
def inner(pytester):
    pytester.makeconftest(CONFTEST)
    pytester.makepyfile(test_inner=INNER)
    return pytester


def test_spinning_test_fails_at_its_limit_naming_the_line(inner):
    res = inner.runpytest_subprocess(
        "-p", "no:cacheprovider", "-k", "not ignored")
    res.assert_outcomes(passed=1, failed=1)
    res.stdout.fnmatch_lines([
        ">*while True:  # the spinning line",
        "*TimeLimitExceeded: test_inner.py::test_spins exceeded its time "
        "limit of 2 s",
        "test_inner.py:*: TimeLimitExceeded",
    ])
    assert res.duration < 20


def test_hang_the_alarm_cannot_reach_ends_the_process(inner):
    # a one-second backstop keeps this check short
    assert "BACKSTOP_S = 30.0" in CONFTEST
    inner.makeconftest(CONFTEST.replace("BACKSTOP_S = 30.0",
                                        "BACKSTOP_S = 1.0"))
    res = inner.runpytest_subprocess(
        "-p", "no:cacheprovider", "-k", "ignored")
    # killed mid-test: no outcome and no summary line
    res.stdout.no_fnmatch_line("*= * in *s =*")
    res.stderr.fnmatch_lines(["*Timeout (0:00:02)!*",
                              "*test_spins_with_the_alarm_ignored*"])
    assert res.duration < 20

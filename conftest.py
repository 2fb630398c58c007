"""One time limit for every test of the suite (stdlib and pytest only).

Each phase of a test (setup, call, teardown) runs under
``DEFAULT_LIMIT_S`` seconds, or under the seconds its
``@pytest.mark.time_limit(seconds)`` marker gives. Two mechanisms:

- On the main thread, ``SIGALRM`` from ``setitimer`` raises
  :class:`TimeLimitExceeded` in whatever Python code is running: a
  spinning loop, an ``asyncio`` event loop, a ``subprocess.run`` wait
  (which kills its child on any exception). The test fails with the
  limit in its message and the interrupted line in its traceback.
- As a backstop for a hang inside C code, which a signal handler cannot
  interrupt, ``faulthandler`` dumps every thread's stack to stderr
  ``BACKSTOP_S`` seconds later and exits the process. Under xdist the
  controller reports the lost worker and starts a new one; with
  ``--dist loadfile`` that worker runs the crashed test again, so a
  hang that recurs ends one worker after another until xdist's restart
  limit. Off the main thread (where no signal handler runs) the
  backstop is the only limit.
"""
import faulthandler
import os
import signal
import sys
import threading

import pytest

pytest_plugins = ("pytester",)

# ~3x the slowest test, 80 s under `-p xdist -n 6 --dist loadfile` on
# an 8-core CPU host
DEFAULT_LIMIT_S = 240.0
BACKSTOP_S = 30.0

_STDERR_FD = pytest.StashKey[int]()


class TimeLimitExceeded(BaseException):
    """A test phase outran its time limit. A BaseException, so that an
    ``except Exception`` in the code under test cannot swallow it."""


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "time_limit(seconds): this test's per-phase time limit "
        f"in place of the suite's {DEFAULT_LIMIT_S:g} s")
    # capture redirects fd 2 while a test runs; the backstop's dump
    # goes to a copy of the real stderr taken now
    config.stash[_STDERR_FD] = os.dup(sys.__stderr__.fileno())


def pytest_unconfigure(config):
    os.close(config.stash[_STDERR_FD])


def _limited(item):
    marker = item.get_closest_marker("time_limit")
    limit = float(marker.args[0]) if marker else DEFAULT_LIMIT_S
    faulthandler.dump_traceback_later(
        limit + BACKSTOP_S, exit=True, file=item.config.stash[_STDERR_FD])
    on_main = threading.current_thread() is threading.main_thread()
    if on_main:
        def expire(signum, frame):
            __tracebackhide__ = True
            raise TimeLimitExceeded(
                f"{item.nodeid} exceeded its time limit of {limit:g} s")
        previous = signal.signal(signal.SIGALRM, expire)
        signal.setitimer(signal.ITIMER_REAL, limit)
    try:
        return (yield)
    finally:
        if on_main:
            signal.setitimer(signal.ITIMER_REAL, 0)
            signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


pytest_runtest_setup = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_call = pytest.hookimpl(wrapper=True)(_limited)
pytest_runtest_teardown = pytest.hookimpl(wrapper=True)(_limited)

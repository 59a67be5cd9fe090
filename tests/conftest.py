"""Test configuration: force an 8-device virtual CPU platform so
multi-device/sharding paths are exercised without TPU hardware
(analog of the reference testing model parallelism on cpu(0)/cpu(1),
ref: tests/python/unittest/test_multi_device_exec.py).

Must run before the jax backend is initialized (it is lazy, so doing
this at conftest import time is early enough).
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "0")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import contextlib  # noqa: E402
import faulthandler  # noqa: E402
import glob  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import warnings  # noqa: E402

import pytest  # noqa: E402

# Seconds one test's call phase may take, and the most a test may wait
# for a whole child run (tests/test_suite_limits.py holds every literal
# ``timeout=`` under tests/ to it).  The driver's clock is for the whole
# suite: one hang must cost this much of it, not all of it.
TEST_LIMIT_S = 300


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: over 60 s on a builder's machine whatever was "
        "tried; the driver's command deselects it, CHANGES.md names each")


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Fail a test whose call phase passes ``TEST_LIMIT_S``.  The
    watchdog thread writes every thread's stack at the limit (it needs
    no interpreter, so a hang inside native code shows too); a second
    later SIGALRM raises in the main thread, which ends any wait the
    interpreter can leave, and the run goes on."""
    if threading.current_thread() is not threading.main_thread():
        return (yield)

    def expired(signum, frame):
        pytest.fail(f"{item.nodeid} passed the suite's limit of "
                    f"{TEST_LIMIT_S} s a test (tests/conftest.py)")

    # file descriptor 2: the test's captured stderr, or the terminal
    faulthandler.dump_traceback_later(TEST_LIMIT_S, file=sys.__stderr__)
    previous = signal.signal(signal.SIGALRM, expired)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S + 1)
    try:
        return (yield)
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)
        faulthandler.cancel_dump_traceback_later()


class _Session:
    """A recorded ``jax.profiler`` session: its host-side events."""

    def __init__(self, logdir):
        self.logdir = logdir

    def _data(self):
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb"))
        return ProfileData.from_file(path)

    def host_events(self, prefix="mx."):
        """[(name, start_ns, duration_ns)] of the ``/host:CPU`` plane's
        events whose names start with ``prefix``."""
        return [(e.name, e.start_ns, e.duration_ns)
                for plane in self._data().planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name.startswith(prefix)]

    def start_time_ns(self):
        """The session's ``profile_start_time``: the wall-clock
        nanoseconds from which every event's ``start_ns`` counts."""
        with warnings.catch_warnings():     # jaxlib's stats type
            warnings.simplefilter("ignore", DeprecationWarning)
            return next(value for plane in self._data().planes
                        for key, value in plane.stats
                        if key == "profile_start_time")


@pytest.fixture
def profiler_session(tmp_path):
    """``with profiler_session() as rec: ...`` records what runs inside
    under ``jax.profiler`` (annotations only, no Python tracer: a
    session of well under a second)."""
    @contextlib.contextmanager
    def session():
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        logdir = tempfile.mkdtemp(dir=tmp_path)
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            yield _Session(logdir)
        finally:
            jax.profiler.stop_trace()
    return session


@pytest.fixture
def newest_spans():
    """newest_spans(): the flight recorder's ``span`` events of the
    newest profiler session."""
    def newest():
        from incubator_mxnet_tpu import tracing
        evs = tracing.events("span")
        last = max((e["session"] for e in evs), default=0)
        return [e for e in evs if e["session"] == last]
    return newest

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
import glob  # noqa: E402
import tempfile  # noqa: E402

import pytest  # noqa: E402


class _Session:
    """A recorded ``jax.profiler`` session: its host-side events."""

    def __init__(self, logdir):
        self.logdir = logdir

    def host_events(self, prefix="mx."):
        """[(name, start_ns, duration_ns)] of the ``/host:CPU`` plane's
        events whose names start with ``prefix``."""
        from jax.profiler import ProfileData
        path, = glob.glob(os.path.join(
            self.logdir, "plugins", "profile", "*", "*.xplane.pb"))
        return [(e.name, e.start_ns, e.duration_ns)
                for plane in ProfileData.from_file(path).planes
                if plane.name == "/host:CPU"
                for line in plane.lines for e in line.events
                if e.name.startswith(prefix)]


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

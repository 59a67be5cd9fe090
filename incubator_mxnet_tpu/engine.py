"""Execution-ordering facade over XLA's async dispatch.

The reference schedules every kernel through a threaded dependency
engine (ref: include/mxnet/engine.h:96, src/engine/threaded_engine.h)
whose job is (a) async execution, (b) read/write ordering, (c)
synchronization points.  XLA/PJRT already provides (a) and (b): jax
dispatch is asynchronous and data dependencies order execution on
device streams.  What remains is the *control surface*, kept here:

- ``wait_all()``       — analog of Engine::WaitForAll
- ``wait(arrays)``     — analog of WaitForVar / NDArray.wait_to_read
- naive mode           — analog of MXNET_ENGINE_TYPE=NaiveEngine: block
                         after every op, for debugging/determinism
- ``bulk(size)``       — analog of engine op bulking; a no-op context
                         manager kept for API parity (XLA fuses whole
                         jit regions already)
"""
import contextlib

import jax

from .utils.env import get_env

_state = {"naive": None}


def _is_naive():
    if _state["naive"] is None:
        _state["naive"] = get_env("MXTPU_ENGINE_TYPE") == "naive"
    return _state["naive"]


def set_engine_type(kind):
    """'async' or 'naive' (serial, block after each op)."""
    if kind not in ("async", "naive"):
        raise ValueError(kind)
    _state["naive"] = kind == "naive"


def maybe_block(value):
    """Called after each eager op; blocks in naive mode."""
    if _is_naive():
        jax.block_until_ready(value)
    return value


def wait_all():
    """Block until all pending device work is complete.

    Failures must surface: a dead backend raising here is the signal
    the caller asked for — swallowing it would turn "wait for
    completion" into a silent no-op."""
    jax.effects_barrier()
    # touching a fresh computation forces the queue to drain per-device;
    # local_devices only — a process cannot (and need not) wait on
    # devices addressable only by other hosts
    for d in jax.local_devices():
        jax.device_put(0, d).block_until_ready()


def wait(values):
    """Block until the given jax arrays are ready."""
    jax.block_until_ready(values)


@contextlib.contextmanager
def bulk(size=None):
    """API-parity shim for engine op bulking (XLA fuses jit regions)."""
    yield

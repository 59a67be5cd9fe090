"""Device contexts: ``tpu(i)`` / ``cpu(i)`` with a ``with ctx:`` stack.

TPU-native analog of the reference's Context (ref:
python/mxnet/context.py — mx.cpu()/mx.gpu(), `with ctx:` stack, and
include/mxnet/base.h Context struct).  ``gpu(i)`` is accepted as an
alias for ``tpu(i)`` so reference scripts run unmodified.

A Context maps onto a concrete ``jax.Device``.  On a CPU-only test
host with ``--xla_force_host_platform_device_count=N``, ``tpu(i)`` and
``cpu(i)`` both resolve to the i-th virtual CPU device, which is what
lets multi-device code paths be tested without TPU hardware.
"""
import threading

import jax

_ACCEL_TYPES = ("tpu", "gpu")  # jax.Device.platform of an accelerator


class Context:
    """A device context. devtype is 'cpu' or 'tpu'."""

    _default_ctx = threading.local()
    devtype2mask = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3,
                    "cpu_shared": 5}
    devmask2type = {1: "cpu", 2: "tpu", 3: "cpu_pinned", 5: "cpu_shared"}

    def __init__(self, device_type, device_id=0):
        if device_type == "gpu":  # compat alias
            device_type = "tpu"
        if device_type not in ("cpu", "tpu", "cpu_pinned", "cpu_shared"):
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = device_id

    # -- identity ---------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    def __str__(self):
        return self.__repr__()

    # -- jax mapping ------------------------------------------------------
    @property
    def jax_device(self):
        """The concrete jax.Device this context denotes."""
        devs = _devices_for(self.device_type)
        if not devs:
            # graceful degradation: fall back to whatever exists
            devs = jax.devices()
        return devs[self.device_id % len(devs)]

    def empty_cache(self):
        """Release cached device memory (analog of ctx.empty_cache)."""
        # XLA/PJRT owns the allocator; live buffers are freed by GC.
        import gc
        gc.collect()

    def memory_info(self):
        """(free_bytes, total_bytes) for this context's device (the
        reference's ``mx.context.gpu_memory_info`` role,
        python/mxnet/context.py).

        Reads the PJRT allocator's statistics (HBM on TPU).  Backends
        that expose no stats (virtual CPU devices) report host memory
        so capacity planning code keeps working off-device."""
        import os
        stats = None
        try:
            stats = self.jax_device.memory_stats()
        except Exception:  # noqa: BLE001 — optional PJRT surface
            stats = None
        if stats and stats.get("bytes_limit"):
            total = int(stats["bytes_limit"])
            used = int(stats.get("bytes_in_use", 0))
            return max(total - used, 0), total
        page = os.sysconf("SC_PAGE_SIZE")
        total = os.sysconf("SC_PHYS_PAGES") * page
        avail = os.sysconf("SC_AVPHYS_PAGES") * page
        return avail, total

    # -- with-statement stack --------------------------------------------
    def __enter__(self):
        if not hasattr(Context._default_ctx, "stack"):
            Context._default_ctx.stack = []
        Context._default_ctx.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._default_ctx.stack.pop()


def _devices_for(device_type):
    all_devs = jax.devices()
    if device_type.startswith("cpu"):
        cpus = [d for d in all_devs if d.platform == "cpu"]
        return cpus or all_devs
    accel = [d for d in all_devs if d.platform in _ACCEL_TYPES]
    # on CPU-only hosts, "tpu(i)" maps onto virtual cpu devices so that
    # multi-device code paths still exercise distinct devices
    return accel or all_devs


def cpu(device_id=0):
    """Return a CPU context."""
    return Context("cpu", device_id)


def tpu(device_id=0):
    """Return a TPU context."""
    return Context("tpu", device_id)


def gpu(device_id=0):
    """Compat alias for :func:`tpu` so reference scripts run unchanged."""
    return Context("tpu", device_id)


def cpu_pinned(device_id=0):
    return Context("cpu_pinned", device_id)


def num_tpus():
    """Number of attached accelerator devices (0 on pure-CPU hosts)."""
    return len([d for d in jax.devices() if d.platform in _ACCEL_TYPES])


num_gpus = num_tpus


def tpu_memory_info(device_id=0):
    """(free_bytes, total_bytes) of the accelerator's memory (the
    reference's ``mx.context.gpu_memory_info``)."""
    return tpu(device_id).memory_info()


gpu_memory_info = tpu_memory_info


def default_context():
    """Context used when none is given: innermost `with ctx:`, else
    tpu(0) if an accelerator is attached, else cpu(0)."""
    stack = getattr(Context._default_ctx, "stack", None)
    if stack:
        return stack[-1]
    return tpu(0) if num_tpus() else cpu(0)


def current_context():
    return default_context()

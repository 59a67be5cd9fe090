"""Wall-clock-only MFU/throughput gauge publisher.

``TrainPerfClock`` turns a per-step analytic cost (from the graph
cost model or a model's ``train_flops_per_token``) into the
``train_mfu`` / ``train_mbu`` / ``train_tokens_per_sec`` gauges.  It
reads ONLY ``time.monotonic()`` and host-side Python state — never a
device value — so ticking it on every training step adds **zero**
device->host syncs (the transfer-budget test in tests/test_perf.py
proves it; ci/lint.py's hot-sync rule covers this module).

Publication cadence: every ``MXTPU_PERF_INTERVAL`` ticks by default,
or exactly on the step sentinel's guard-interval read when the caller
passes its ``due`` flag — either way no sync is *added*, the gauges
ride cadences that already exist.
"""
import time

from .. import telemetry
from ..utils.env import get_env
from . import device_db

__all__ = ["TrainPerfClock"]


class TrainPerfClock:
    """Publishes train-side MFU/MBU/throughput gauges from wall time.

    flops_per_step / bytes_per_step: analytic cost of one full train
    step (already 3x-forward scaled).  tokens_per_step / items: for
    the throughput gauge.  All may be armed late via :meth:`arm`
    (e.g. once a graph is bound and costed).
    """

    def __init__(self, flops_per_step=0.0, bytes_per_step=0.0,
                 tokens_per_step=0.0, device=None, dtype="bfloat16"):
        self._flops = float(flops_per_step)
        self._bytes = float(bytes_per_step)
        self._tokens = float(tokens_per_step)
        self._dtype = dtype
        self._caps = device_db.caps_for(device) if device is not None \
            else None
        self._interval = max(1, get_env("MXTPU_PERF_INTERVAL"))
        self._ticks = 0
        self._win_steps = 0
        self._win_start = time.monotonic()
        self._g_mfu = telemetry.gauge("train_mfu")
        self._g_mbu = telemetry.gauge("train_mbu")
        self._g_tok = telemetry.gauge("train_tokens_per_sec")

    def arm(self, flops_per_step=None, bytes_per_step=None,
            tokens_per_step=None, device=None):
        """Set/replace the analytic cost after construction."""
        if flops_per_step is not None:
            self._flops = float(flops_per_step)
        if bytes_per_step is not None:
            self._bytes = float(bytes_per_step)
        if tokens_per_step is not None:
            self._tokens = float(tokens_per_step)
        if device is not None:
            self._caps = device_db.caps_for(device)

    def _ensure_caps(self):
        if self._caps is None:
            import jax
            self._caps = device_db.caps_for(jax.devices()[0])
        return self._caps

    def tick(self, due=None):
        """Count one step; publish when ``due`` (or every
        MXTPU_PERF_INTERVAL ticks when ``due`` is None).  Wall clock
        only — no device reads on any path."""
        self._ticks += 1
        self._win_steps += 1
        if due is None:
            due = self._ticks % self._interval == 0
        if not due:
            return
        now = time.monotonic()
        dt = now - self._win_start
        steps = self._win_steps
        self._win_start = now
        self._win_steps = 0
        if dt <= 0.0 or steps <= 0:
            return
        rate = steps / dt
        caps = self._ensure_caps()
        if self._tokens:
            self._g_tok.set(self._tokens * rate)
        peak = caps.peak(self._dtype)
        if self._flops and peak:
            self._g_mfu.set(self._flops * rate / peak)
        if self._bytes and caps.hbm_bytes_per_s:
            self._g_mbu.set(self._bytes * rate
                            / caps.hbm_bytes_per_s)

"""Device capability database: the single source of truth for peak
FLOP/s and HBM bandwidth per device kind (docs/observability.md
"Perf observatory").

Roofline classification needs bandwidth beside the FLOP/s peak, so
both live here.  Peaks are dense-matmul peaks for the MXU-native dtype
(bf16 on TPU); other dtypes derive by documented convention:

- ``bf16`` / ``fp16``: the MXU peak (the table value)
- ``fp32``: MXU peak / 8 (fp32 matmuls pass through the MXU as
  multiple bf16x3-style passes; a deliberately conservative factor)
- ``int8``: 2x the bf16 peak on v5e-generation and newer parts that
  advertise int8 MXU throughput; bf16 peak elsewhere

CPU hosts get *nominal* numbers so the roofline plumbing
(``Module.perf_report()``, CI tests) produces a verdict on a CPU-only
host; they are order-of-magnitude placeholders, overridable via
``MXTPU_PERF_CPU_PEAK_GFLOPS`` / ``MXTPU_PERF_CPU_GBPS``, and every
report that uses them carries ``"nominal_peaks": true``.
"""
from ..utils.env import get_env

__all__ = ["DeviceCaps", "DEVICE_DB", "caps_for_kind", "caps_for",
           "peak_flops", "roofline", "hbm_capacity", "headroom"]

# nominal per-device HBM for CPU hosts (the gate needs *a* capacity
# to plan against off-TPU; 32 GiB is far above any CI-sized graph, so
# the ladder only engages when MXTPU_HBM_BYTES shrinks it on purpose)
_CPU_NOMINAL_HBM = 32 * (1 << 30)


class DeviceCaps:
    """Peak capabilities of one device kind."""

    __slots__ = ("kind", "bf16_flops", "hbm_bytes_per_s", "int8_2x",
                 "nominal", "hbm_bytes", "nominal_hbm")

    def __init__(self, kind, bf16_flops, hbm_gb_s, int8_2x=False,
                 nominal=False, hbm_gib=None):
        self.kind = kind
        self.bf16_flops = float(bf16_flops)
        self.hbm_bytes_per_s = float(hbm_gb_s) * 1e9
        self.int8_2x = bool(int8_2x)
        self.nominal = bool(nominal)
        # per-chip HBM capacity; nominal_hbm marks values that are
        # placeholders (the CPU's) rather than datasheet
        self.nominal_hbm = bool(nominal) or hbm_gib is None
        self.hbm_bytes = float(
            (hbm_gib if hbm_gib is not None else 32) * (1 << 30))

    def peak(self, dtype="bfloat16"):
        """Peak FLOP/s for a compute dtype (convention in the module
        docstring)."""
        d = str(dtype)
        if d in ("bfloat16", "bf16", "float16", "fp16", "half"):
            return self.bf16_flops
        if d in ("int8", "uint8"):
            return self.bf16_flops * (2.0 if self.int8_2x else 1.0)
        if d in ("float32", "fp32", "float"):
            # CPU "bf16" nominal IS its fp32 peak — no MXU to derate
            return self.bf16_flops if self.nominal \
                else self.bf16_flops / 8.0
        return self.bf16_flops

    def capacity(self):
        """Usable per-device HBM in bytes: the ``MXTPU_HBM_BYTES``
        override when set (> 0), the generation's datasheet capacity
        otherwise (nominal for the CPU)."""
        override = float(get_env("MXTPU_HBM_BYTES"))
        return override if override > 0 else self.hbm_bytes

    def as_dict(self):
        return {"kind": self.kind, "bf16_flops": self.bf16_flops,
                "hbm_bytes_per_s": self.hbm_bytes_per_s,
                "nominal": self.nominal,
                "hbm_bytes": self.capacity(),
                "nominal_hbm": self.nominal_hbm}


# device_kind substring -> caps; first match wins, so keep the more
# specific tags ("v5p", "v5litepod") ahead of shorter ones ("v5e").
# Per-chip numbers (dense bf16 peak, HBM GB/s).
DEVICE_DB = [
    DeviceCaps("v6", 918e12, 1640.0, int8_2x=True, hbm_gib=32),
    DeviceCaps("v5p", 459e12, 2765.0, hbm_gib=95),
    DeviceCaps("v5e", 197e12, 819.0, int8_2x=True, hbm_gib=16),
    DeviceCaps("v5litepod", 197e12, 819.0, int8_2x=True, hbm_gib=16),
    DeviceCaps("v5 lite", 197e12, 819.0, int8_2x=True, hbm_gib=16),
    DeviceCaps("v4", 275e12, 1228.0, hbm_gib=32),
    DeviceCaps("v3", 123e12, 900.0, hbm_gib=16),
    DeviceCaps("v2", 45e12, 700.0, hbm_gib=8),
]


def _cpu_caps():
    """Nominal CPU caps (env-overridable; see module docstring)."""
    return DeviceCaps(
        "cpu",
        get_env("MXTPU_PERF_CPU_PEAK_GFLOPS") * 1e9,
        get_env("MXTPU_PERF_CPU_GBPS"),
        nominal=True, hbm_gib=_CPU_NOMINAL_HBM >> 30)


def caps_for_kind(kind):
    """Caps for a device-kind string: the matching ``DEVICE_DB`` row,
    nominal CPU caps for ``"cpu"`` (or no kind at all).  An
    accelerator that matches no row is an error, not a default — its
    MFU would be computed against CPU peaks and the OOM gate would
    plan against a made-up capacity."""
    k = (kind or "").lower()
    for caps in DEVICE_DB:
        if caps.kind in k:
            return caps
    if k in ("", "cpu"):
        return _cpu_caps()
    raise ValueError(
        f"device_kind {kind!r} matches no row of perf.DEVICE_DB: add "
        "its peak FLOP/s, HBM bandwidth and capacity there")


def caps_for(device):
    """Caps for a jax device object (``.device_kind``)."""
    return caps_for_kind(getattr(device, "device_kind", ""))


def peak_flops(device, dtype="bfloat16"):
    """Peak FLOP/s of a jax device for a compute dtype."""
    return caps_for(device).peak(dtype)


def hbm_capacity(device=None):
    """Usable per-device HBM bytes for a jax device (or the default
    backend when None): the ``MXTPU_HBM_BYTES`` override, else the
    device generation's datasheet value, else the nominal CPU
    capacity."""
    if device is None:
        import jax
        device = jax.devices()[0]
    return caps_for(device).capacity()


def headroom(used_bytes, device=None, margin=None):
    """Bytes of HBM still available after ``used_bytes``, holding
    back ``margin`` (default ``MXTPU_MEM_GATE_MARGIN``) of capacity
    for fragmentation/unmodeled scratch.  Negative = over budget."""
    if margin is None:
        margin = float(get_env("MXTPU_MEM_GATE_MARGIN"))
    cap = hbm_capacity(device)
    return cap * (1.0 - margin) - float(used_bytes)


def roofline(flops, bytes_moved, caps, dtype="bfloat16"):
    """Classify one workload against a device's roofline.

    Predicted time = max(compute time, memory time); the bound-by
    label says which wall the workload sits against (within 10% of
    the ridge both walls matter -> "balanced").
    """
    peak = caps.peak(dtype)
    bw = caps.hbm_bytes_per_s
    t_compute = flops / peak if peak else 0.0
    t_memory = bytes_moved / bw if bw else 0.0
    t = max(t_compute, t_memory)
    if t <= 0.0:
        bound = "idle"
    elif abs(t_compute - t_memory) <= 0.1 * t:
        bound = "balanced"
    elif t_compute > t_memory:
        bound = "compute"
    else:
        bound = "memory"
    intensity = (flops / bytes_moved) if bytes_moved else 0.0
    ridge = (peak / bw) if bw else 0.0
    return {"predicted_s": t, "compute_s": t_compute,
            "memory_s": t_memory, "bound": bound,
            "arithmetic_intensity": intensity,
            "ridge_intensity": ridge,
            "peak_flops": peak, "hbm_bytes_per_s": bw,
            "nominal_peaks": caps.nominal}

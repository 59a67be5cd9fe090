"""The Mamba-2 state-step kernel's share of its roofline in a serve
cell: the least time the chip could take for the bytes that the
traced window's ``ssm_state_step`` calls must move (ssm_state_cost.py,
at ``peaks.json``'s HBM bandwidth and the bf16 peak for their
operations) over the summed device time of those calls.  The calls are
counted from the window's decode launches (the program's
``serve_decode_dispatch`` spans of the newest session), one a Mamba
layer (``M`` in ``hybrid_override_pattern``) a launch, at the slots of
the cell's engine.  Nothing to read (no traced window, no such kernel
on the device, no launch): None.  A reading over 105% raises: the
bytes are counted too high, or the pattern misses part of the
kernel."""
from .. import flops
from . import program_span, ssm_state_cost

PATTERN = "ssm_state_step"


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace:
        return None
    seconds = sum(v for n, v in trace["op_seconds"].items()
                  if "tpu_custom_call" in n and PATTERN in n)
    events, _ = program_span.span_events()
    launches = sum(1 for e in events
                   if e["name"] == "serve_decode_dispatch")
    if seconds <= 0 or not launches:
        return None
    cfg = ctx["config"]
    calls = launches * cfg["hybrid_override_pattern"].count("M")
    slots = ctx["traffic"]["engine"]["max_batch"]
    peaks = ctx["peaks"]
    least, bound = flops.roofline_seconds(
        calls * ssm_state_cost.state_step_flops(cfg, slots),
        calls * ssm_state_cost.state_step_bytes(cfg, slots),
        peaks["flops_per_s"][spec["peak"]], peaks["hbm_bytes_per_s"])
    share = 100.0 * least / seconds
    ctx.setdefault("notes", {})[spec["name"]] = {
        "bound_by": bound, "kernel_seconds": seconds,
        "least_seconds": least, "calls": calls}
    if share > 105.0:
        raise RuntimeError(f"{spec['name']} reads {share:.1f}%: the "
                           "bytes are counted too high, or the pattern "
                           "misses part of the kernel")
    return share

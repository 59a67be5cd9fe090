"""A kernel's share of its roofline: the least time the chip could
take for the kernel's work in the traced window (the larger of
operations over peak FLOP/s and bytes over peak bytes/s, both counted
from shapes by the function ``spec["cost"]`` of flops.py) over the
summed device time of the events whose names match
``spec["patterns"]``.  No matching event: nothing to read."""
import re

from .. import flops


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace:
        return None
    patterns = [re.compile(p) for p in spec["patterns"]]
    seconds = sum(v for n, v in trace["op_seconds"].items()
                  if any(p.search(n) for p in patterns))
    if seconds <= 0:
        return None
    traffic = ctx["traffic"]
    n_flops, n_bytes = getattr(flops, spec["cost"])(
        ctx["config"], traffic["batch"], traffic["seq_len"])
    peaks = ctx["peaks"]
    least, bound = flops.roofline_seconds(
        n_flops * trace["steps"], n_bytes * trace["steps"],
        peaks["flops_per_s"][spec["peak"]], peaks["hbm_bytes_per_s"])
    share = 100.0 * least / seconds
    ctx.setdefault("notes", {})[spec["name"]] = {
        "bound_by": bound, "kernel_seconds": seconds,
        "least_seconds": least}
    if share > 105.0:
        raise RuntimeError(f"{spec['name']} reads {share:.1f}%: the "
                           "operations or bytes are counted too high, "
                           "or the patterns miss part of the kernel")
    return share

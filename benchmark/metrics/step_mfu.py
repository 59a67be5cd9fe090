"""The whole step's share of the chip's peak: the operations the
mathematics requires for the work done in the traced window (counted
by flops.py, recomputation not counted) over the window's length and
the bf16 peak of the chips used."""


def read(ctx, spec):
    trace = ctx.get("trace")
    if not trace or not ctx.get("flops_in_trace"):
        return None
    peak = ctx["peaks"]["flops_per_s"][spec["peak"]] * ctx["chips"]
    share = 100.0 * ctx["flops_in_trace"] / trace["window_s"] / peak
    if share > 105.0:
        raise RuntimeError(f"{spec['name']} reads {share:.1f}%: the "
                           "operations are counted too high or the "
                           "window leaves out part of the work")
    return share

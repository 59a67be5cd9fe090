"""Median duration of one of the benchmark's own spans, in ms, on the
benchmark's clock (``ctx["spans"]``: name -> seconds of each)."""
import statistics


def read(ctx, spec):
    values = (ctx.get("spans") or {}).get(spec["span"])
    if not values:
        return None
    return 1e3 * statistics.median(values)

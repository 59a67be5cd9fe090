"""A number taken from the program's own spans.

While a ``jax.profiler`` session records, the program's
``telemetry.span`` leaves one ``span`` event a span in its flight
recorder: ``id``, ``parent`` (the span open on the same thread when it
began), ``name``, ``t0``/``t1`` (seconds on ``perf_counter``),
``session`` (which recording it fell into) and the span's fields.
This reader takes the events of the newest session and does the
arithmetic itself, as ``spec`` says:

    span    the name of the spans read
    holds   keep only those with a descendant of this name
    minus   subtract from each the time of its descendants of these
            names (the outermost of them: nothing is taken twice)
    per     give 1e6 x summed seconds over the summed field ``per``
            (ms per thousand); without it, the median in ms

Nothing to read (no traced window, as in a rehearsal on the CPU; a
program without such spans) gives None.  What was counted on the way
goes to ``ctx["notes"]["program_span"]``.
"""
import statistics


def span_events():
    """(events of the newest session, events dropped by the ring)."""
    from incubator_mxnet_tpu import tracing
    events = tracing.events("span")
    if not events:
        return [], 0
    newest = max(e["session"] for e in events)
    return ([e for e in events if e["session"] == newest],
            tracing.recorder().dropped)


def seconds(e):
    return e["t1"] - e["t0"]


def ancestors(e, by_id):
    """The spans around ``e``, innermost first."""
    seen = set()
    while e.get("parent") in by_id and e["parent"] not in seen:
        seen.add(e["parent"])
        e = by_id[e["parent"]]
        yield e


def inside(events, names):
    """id -> seconds of the span's descendants called one of
    ``names``, counting of nested ones only the outermost."""
    by_id = {e["id"]: e for e in events}
    out = {}
    for e in events:
        if e["name"] not in names:
            continue
        for a in ancestors(e, by_id):
            if a["name"] in names:
                break               # counted with that one
            out[a["id"]] = out.get(a["id"], 0.0) + seconds(e)
    return out


def value(events, spec):
    mine = [e for e in events if e["name"] == spec["span"]]
    if spec.get("holds"):
        has = inside(events, [spec["holds"]])
        mine = [e for e in mine if e["id"] in has]
    if not mine:
        return None
    if spec.get("per"):
        total = sum(e[spec["per"]] for e in mine)
        return 1e6 * sum(map(seconds, mine)) / total if total else None
    taken = inside(events, spec.get("minus", []))
    return 1e3 * statistics.median(
        seconds(e) - taken.get(e["id"], 0.0) for e in mine)


def notes(events, dropped):
    """What the window's spans add up to: self seconds by name (a
    span's time less its children's), the compilations, and the share
    of prefill rows that were padding."""
    child_s = {}
    for e in events:
        if e.get("parent") is not None:
            child_s[e["parent"]] = child_s.get(e["parent"], 0.0) \
                + seconds(e)
    self_s, count = {}, {}
    for e in events:
        own = seconds(e) - child_s.get(e["id"], 0.0)
        self_s[e["name"]] = self_s.get(e["name"], 0.0) + own
        count[e["name"]] = count.get(e["name"], 0) + 1
    names = {e["id"]: e["name"] for e in events}
    out = {"spans_read": len(events), "ring_dropped": dropped,
           "count": count, "self_seconds": self_s,
           "compiles": [{"fun_name": e.get("fun_name"),
                         "cached": e.get("cached"),
                         "under": names.get(e.get("parent")),
                         "seconds": seconds(e)}
                        for e in events if e["name"] == "compile"]}
    prefills = [e for e in events if e["name"] == "serve_prefill"]
    rows = sum(e["bucket"] for e in prefills)
    if rows:
        out["prefill_padded_share"] = \
            1.0 - sum(e["tokens"] for e in prefills) / rows
    return out


def read(ctx, spec):
    if not ctx.get("trace"):
        return None
    events, dropped = span_events()
    if not events:
        return None
    said = ctx.setdefault("notes", {})
    if "program_span" not in said:
        said["program_span"] = notes(events, dropped)
    return value(events, spec)

"""The host's gap before each decode launch, from the engine's own
accounting.

Every ``serve_decode_dispatch`` span (the launch of a decode program)
but the first after an idle engine carries ``gap_s`` (seconds from the
last decode barrier's return to this launch, in which the device had
nothing queued but the prefills), ``busy_s`` (the prefills' device
seconds in it: their ``serve_prefill`` less their launches) and
``admitted`` (the admissions in it).  The reader takes the newest
session's spans through ``program_span.span_events()``, as ``spec``'s
``part`` says:

    exposed       median over launches with a gap of gap_s - busy_s +
                  the launch's own seconds: the host time each decode
                  step leaves the chip with an empty queue
    admit_stall   median over launches with admitted > 0 of gap_s +
                  the launch's own seconds: how long a step that takes
                  prompts in holds every decode stream between two
                  decode programs, the prefills' device time included

Nothing to read (no traced window, as in a rehearsal on the CPU; a
program without such spans; a window with no launch of that kind)
gives None.  What was read goes to ``ctx["notes"]["decode_gap"]``.
"""
import statistics

from . import program_span

PARTS = {
    "exposed": (lambda e: "gap_s" in e,
                lambda e: e["gap_s"] - e["busy_s"]
                + program_span.seconds(e)),
    "admit_stall": (lambda e: e.get("admitted", 0) > 0,
                    lambda e: e["gap_s"] + program_span.seconds(e)),
}


def session_complete(session, dropped):
    """Whether the ring kept all of the session's events: it dropped
    none, or none since the session's ``span_clock``, which the
    program records before the session's first span."""
    if not dropped:
        return True
    from incubator_mxnet_tpu import tracing
    return any(e.get("session") == session
               for e in tracing.events("span_clock"))


def notes(launches, dropped):
    gaps = [e for e in launches if "gap_s" in e]
    return {"launches": len(launches), "with_gap": len(gaps),
            "without_gap": len(launches) - len(gaps),
            "admitted": sum(e["admitted"] for e in gaps),
            "gap_s": sum(e["gap_s"] for e in gaps),
            "busy_s": sum(e["busy_s"] for e in gaps),
            "launch_s": sum(map(program_span.seconds, launches)),
            "session_complete": session_complete(
                launches[0]["session"], dropped)}


def read(ctx, spec):
    if not ctx.get("trace"):
        return None
    events, dropped = program_span.span_events()
    launches = [e for e in events if e["name"] == "serve_decode_dispatch"]
    if not launches:
        return None
    said = ctx.setdefault("notes", {})
    if "decode_gap" not in said:
        said["decode_gap"] = notes(launches, dropped)
    keep, value = PARTS[spec["part"]]
    values = [value(e) for e in launches if keep(e)]
    return 1e3 * statistics.median(values) if values else None

"""From a profiler trace (.xplane.pb) to numbers: device busy and idle
time, device time by operation, and the longest idle gaps with what
the host was doing in each.  Reads with ``jax.profiler.ProfileData``
alone.

What a trace of this chip looks like (TPU v5 lite, jax 0.9.0; see the
recorded one under testdata/): one plane per chip, ``/device:TPU:<n>``,
whose line ``XLA Ops`` holds one event per executed HLO operation,
nested where an operation (a ``while``, a ``call``) runs others; line
``XLA Modules`` holds one event per executed program.  Host threads
are lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
spans are events there under the name they were given.  All planes
share one clock.
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
WINDOW_SPAN = "bench.window"
TOP = 10


class NoDeviceOps(RuntimeError):
    """The trace holds no operation that ran on a device."""


def short_name(event_name):
    """An operation's event is named by its whole HLO text
    (``%fusion.3 = bf16[...] fusion(...), kind=kCustom, ...``): keep
    the instruction's name and what kind of thing it is."""
    head, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name[:100]
    head = head.lstrip("%")
    m = re.search(r'custom_call_target="([^"]+)"', rest) \
        or re.search(r"kind=(k\w+)", rest)
    return f"{head} {m.group(1)}" if m else head


def _events(line):
    return [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for e in line.events]


def _union(intervals):
    """Sorted, disjoint [start, end) covering the same points."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _self_times(events):
    """name -> nanoseconds in which that event was the innermost one
    running (an operation that runs others is charged only what its
    children leave)."""
    out, stack = {}, []          # stack of [end, name, child_ns, start]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, child, start = stack.pop()
            out[name] = out.get(name, 0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        close(s)
        stack.append([e, name, 0, s])
    close(float("inf"))
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(path):
    """The numbers of one trace: ``busy_s`` and ``window_s`` averaged
    over the chips, ``device_ops`` and ``idle_gaps`` as lists of
    [name, seconds] (most first, at most 10), ``op_seconds`` with every
    operation's own device time (chip 0), ``spans`` with every host
    span's durations by name."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, host_spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = _events(line)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host_spans += _events(line)
    if not devices or not any(devices.values()):
        raise NoDeviceOps(
            f"no operation ran on a device in {path}: planes "
            f"{[p.name for p in data.planes]}")

    marks = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if marks:
        lo, hi = marks[0]
    else:
        lo = min(s for ev in devices.values() for s, _, _ in ev)
        hi = max(e for ev in devices.values() for _, e, _ in ev)

    busy, gaps = [], []
    for chip, events in sorted(devices.items()):
        covered = _union(_clip([(s, e) for s, e, _ in events], lo, hi))
        busy.append(sum(e - s for s, e in covered))
        if chip == min(devices):
            edges = [lo] + [t for iv in covered for t in iv] + [hi]
            gaps = [(edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i]]

    first = devices[min(devices)]
    ops = _self_times([(max(s, lo), min(e, hi), n)
                       for s, e, n in first if e > lo and s < hi])
    spans = {}
    for s, e, n in host_spans:
        if n.startswith("bench.") and s >= lo and e <= hi:
            spans.setdefault(n, []).append((e - s) / 1e9)

    # what the host was doing in each gap: the gap's time goes to the
    # spans of ours it overlaps (they do not nest), the rest to no_span
    named = sorted((s, e, n) for s, e, n in host_spans
                   if n.startswith("bench.") and n != WINDOW_SPAN)
    by_host, first = {}, 0
    for s, e in gaps:                       # both in order of time
        while first < len(named) and named[first][1] <= s:
            first += 1
        left, k = e - s, first
        while k < len(named) and named[k][0] < e:
            both = min(e, named[k][1]) - max(s, named[k][0])
            if both > 0:
                by_host[named[k][2]] = by_host.get(named[k][2], 0) + both
                left -= both
            k += 1
        if left > 0:
            by_host["no_span"] = by_host.get("no_span", 0) + left

    def top(table):
        return [[short_name(n), v / 1e9] for n, v in sorted(
            table.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"busy_s": sum(busy) / len(busy) / 1e9,
            "window_s": (hi - lo) / 1e9,
            "chips": len(devices),
            "device_ops": top(ops), "idle_gaps": top(by_host),
            "op_seconds": {n: v / 1e9 for n, v in ops.items()},
            "longest_gap_s": max((e - s for s, e in gaps),
                                 default=0) / 1e9,
            "spans": spans}


def families(op_seconds, top=12):
    """Device seconds by family of operation: the instruction's name
    without its number (``multiply_reduce_fusion.12`` and ``.13`` are
    one family; a custom call goes by its target).  For PERF.md's
    "where the time goes"; the result line carries single operations."""
    out = {}
    for name, seconds in op_seconds.items():
        short = short_name(name)
        head, _, kind = short.partition(" ")
        family = kind if kind and not kind.startswith("k") \
            else re.sub(r"[.\d]+$", "", head)
        out[family] = out.get(family, 0.0) + seconds
    return sorted(out.items(), key=lambda kv: -kv[1])[:top]


def describe(path, limit=40):
    """Planes, lines and the commonest event names: for a first look
    at a trace by hand."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            events = list(line.events)
            names = {}
            for e in events:
                names[e.name] = names.get(e.name, 0) + e.duration_ns
            common = sorted(names.items(), key=lambda kv: -kv[1])
            out.append(f"  line {line.name!r}: {len(events)} events; "
                       + "; ".join(f"{n[:60]}={v / 1e6:.3f}ms"
                                   for n, v in common[:limit]))
    return "\n".join(out)

"""Attribution of the device's idle time to the layer the host was in.

The program opens ``funky.*`` spans (``repro.obs.span``) at each layer
boundary of the serving path.  They land on the profiler's host planes, on
the clock of the device's operations, one line per thread.  Each idle
instant of the first traced chip inside the traced window goes to one
layer, by the spans open on any host thread at that instant, in this
priority:

    launch   a ``funky.monitor.*`` span (launching a program, moving
             bytes, syncing)
    engine   else a ``funky.engine.*`` span (admission, page mapping,
             block-table flush, commit, the rest of the engine step)
    loop     else ``funky.runtime.step`` or a ``funky.router.*`` span
             (router pop and complete, the idle poll)
    none     nothing open

The idle instants are those ``trace.reduce`` counts: the window runs from
the ``bench.window`` span's start to where ``reduce`` ends it (cut where
the device record stops), and busy is the union of the chip's outermost
operations, so the layers sum to ``window_s - busy_s`` on one chip.  Each
span name's count (by start) and seconds inside the window come with it.
All times are seconds on the profiler's clock.
"""

from __future__ import annotations

import itertools

from bench.harness import trace

PREFIX = "funky."
LAYERS = ("launch", "engine", "loop")
NONE = "none"


def layer(name: str):
    """Index into ``LAYERS`` of a span name, or None for other events."""
    if name.startswith("funky.monitor."):
        return 0
    if name.startswith("funky.engine."):
        return 1
    if name == "funky.runtime.step" or name.startswith("funky.router."):
        return 2
    return None


def idle_intervals(events, lo: float, end: float):
    """Idle intervals, in time order, of one chip's op events ``(start,
    end, name)`` in start order, within [lo, end]; the outermost ops are
    taken as ``trace.device_summary`` takes them."""
    cur, stack = lo, []             # stack: ends of the open ops
    for s, t, _ in events:
        while stack and s >= stack[-1]:
            stack.pop()
        cs, ct = max(s, lo), min(t, end)
        if not stack and ct > cs:
            if cs > cur:
                yield cur, cs
            cur = max(cur, ct)
        stack.append(t)
    if end > cur:
        yield cur, end


def segments(host: list, lo: float, end: float) -> list:
    """``(start, end, layer)`` over [lo, end] wherever a program span is
    open, each with the highest-priority layer open there, in time
    order."""
    edges = []
    for s, t, n in host:
        k = layer(n)
        s, t = max(s, lo), min(t, end)
        if k is not None and t > s:
            edges += [(s, 1, k), (t, -1, k)]
    edges.sort()
    depth = [0] * len(LAYERS)
    out, prev = [], None
    for x, d, k in edges:
        if prev is not None and x > prev:
            top = next((i for i, c in enumerate(depth) if c), None)
            if top is not None:
                out.append((prev, x, top))
        depth[k] += d
        prev = x
    return out


def attribute(idle, segs: list) -> dict:
    """Seconds of the ``idle`` intervals (time order) under each layer of
    ``segs`` (from ``segments``), the rest under ``none``."""
    secs = [0.0] * (len(LAYERS) + 1)
    i = 0
    for s, t in idle:
        while i < len(segs) and segs[i][1] <= s:
            i += 1
        covered = 0.0
        j = i
        while j < len(segs) and segs[j][0] < t:
            a, b = max(s, segs[j][0]), min(t, segs[j][1])
            if b > a:
                secs[segs[j][2]] += b - a
                covered += b - a
            j += 1
        secs[-1] += (t - s) - covered
    return dict(zip(LAYERS + (NONE,), secs))


def span_totals(host: list, lo: float, end: float) -> dict:
    """Per program span name: spans started in [lo, end) and their
    seconds inside it."""
    out: dict = {}
    for s, t, n in host:
        if n.startswith(PREFIX) and lo <= s < end:
            c = out.setdefault(n, {"count": 0, "seconds": 0.0})
            c["count"] += 1
            c["seconds"] += min(t, end) - s
    return out


def reduce(devices: dict, host: list, lo: float, end: float) -> dict:
    """``devices``: plane name -> op events in start order (any iterable);
    ``host``: host events ``(start, end, name)``, program spans among
    them; [lo, end]: the traced window as ``trace.reduce`` cuts it.

    Returns ``idle_by_layer`` (seconds per layer and ``none``, on the
    first chip that ran anything) and ``spans`` (``span_totals``)."""
    idle = dict.fromkeys(LAYERS + (NONE,), 0.0)
    for dev in sorted(devices):
        events = iter(devices[dev])
        head = next(events, None)
        if head is not None:
            idle = attribute(idle_intervals(itertools.chain([head], events),
                                            lo, end),
                             segments(host, lo, end))
            break
    return {"idle_by_layer": idle, "spans": span_totals(host, lo, end)}


def summarize(path: str, window_s: float) -> dict:
    """Read an ``.xplane.pb`` and ``reduce`` it over the window that
    ``trace.summarize`` reported (``window_s`` from the start of
    ``bench.window``)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    bench, host, devs = [], [], {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_dev and line.name == trace.OPS_LINE:
                devs[plane.name] = line
            elif plane.name.startswith("/host:"):
                for ev in trace._events(line):
                    (bench if ev[2].startswith(trace.SPAN_PREFIX)
                     else host).append(ev)
    lo = trace.window(bench)[0]
    return reduce({k: trace._events(v) for k, v in devs.items()}, host, lo,
                  lo + window_s)


def idle_share(tr: dict, name: str):
    """100 x the ``name`` layer's idle seconds / ``window_s``, or None where
    the trace holds no program span (a program without them)."""
    if not tr or not tr.get("spans") or tr["window_s"] <= 0:
        return None
    return 100.0 * tr["idle_by_layer"][name] / tr["window_s"]

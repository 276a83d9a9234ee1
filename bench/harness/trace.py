"""Reduction of a profiler trace to device busy time, per-program device
time, the top device operations and the longest idle gaps.

The JAX profiler writes an ``.xplane.pb``; ``jax.profiler.ProfileData``
reads it.  Each chip is a plane named ``/device:TPU:<i>``; its ``XLA Ops``
line holds one event per operation run on the chip, in start order, a loop
or call op spanning the ops it runs (so ops nest), and its ``XLA Modules``
line one event per program execution (``jit_<program>(<id>)``).  Host
planes hold the runtime's events and the benchmark's own
``jax.profiler.TraceAnnotation`` spans, whose names start with ``bench.``;
``bench.window`` marks the measured window.  A window of tens of seconds
holds millions of op events, so ops are reduced in one pass as they are
read, never stored.  The profiler keeps a bounded number of device events:
where the host went on launching programs (``PjitFunction(...)`` events)
after the last device event, the device's record was cut short, and the
traced window ends at that event.  All times are in seconds on the
profiler's clock.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
LAUNCH_PREFIX = "PjitFunction("
# a launch this long after the device's last event means the record stopped
TRUNCATION_S = 0.25


def program_name(module: str) -> str:
    """``jit_decode_step(17)`` -> ``decode_step``."""
    m = re.match(r"^(?:jit_)?([A-Za-z0-9_.-]+?)(?:\(.*)?$", module)
    return m.group(1) if m else module


def op_name(op: str) -> str:
    """``%fusion.154 = bf16[8,11008]{...} fusion(...)`` -> ``fusion.154``."""
    return op.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def device_summary(events, lo: float, hi: float, top: int = 10) -> dict:
    """One pass over a chip's op events, ``(start, end, name)`` in start
    order: busy seconds in [lo, hi] (union of the outermost ops), each op
    name's self time (its span less the ops nested in it) within the
    window, the ``top`` longest idle gaps before the last op, and ``end``,
    where the last op ends (``lo`` if none ran)."""
    busy, cur = 0.0, lo
    selft: dict = {}
    gaps: list = []                 # min-heap of (length, start, end)
    stack: list = []                # open ops: [end, name, self seconds]

    def close(item):
        selft[item[1]] = selft.get(item[1], 0.0) + item[2]

    def gap(s, t):
        if t - s > 0:
            heapq.heappush(gaps, (t - s, s, t))
            if len(gaps) > top:
                heapq.heappop(gaps)

    for s, t, name in events:
        while stack and s >= stack[-1][0]:
            close(stack.pop())
        cs, ct = max(s, lo), min(t, hi)
        d = max(0.0, ct - cs)
        if stack:
            stack[-1][2] -= d       # nested: not its parent's own time
        elif d > 0:
            gap(cur, cs)
            busy += ct - max(cs, cur) if ct > cur else 0.0
            cur = max(cur, ct)
        stack.append([t, op_name(name), d])
    while stack:
        close(stack.pop())
    return {"busy_s": busy, "self_s": selft, "end": cur,
            "gaps": sorted(((s, t) for _, s, t in gaps),
                           key=lambda g: g[0] - g[1])}


def traced_end(ends: list, host: list, hi: float) -> float:
    """End of the traced window: ``hi``, or the devices' last event where
    the host launched programs after it that the trace does not show."""
    if not ends:
        return hi                   # no device record to be cut short
    last = max(ends)
    late = any(s > last + TRUNCATION_S and s < hi and n.startswith(
        LAUNCH_PREFIX) for s, _, n in host)
    return last if late else hi


def label_gap(spans: list, host: list, g: tuple) -> str:
    """What the host was doing in an idle gap: the innermost benchmark span
    over its middle, else the host event that covers most of it."""
    mid = 0.5 * (g[0] + g[1])
    inner = [(s, n) for s, t, n in spans
             if s <= mid <= t and n != WINDOW_SPAN]
    if inner:
        return max(inner)[1]
    best, name = 0.0, "unattributed"
    span = g[1] - g[0]
    for s, t, n in host:
        if t - s > 4 * span:
            continue                       # umbrella events (threads, loops)
        ov = min(t, g[1]) - max(s, g[0])
        if ov > best:
            best, name = ov, n
    return name


def window(spans: list) -> tuple:
    w = [(s, t) for s, t, n in spans if n == WINDOW_SPAN]
    if not w:
        raise ValueError("trace has no bench.window span")
    return w[0]


def reduce(devices: dict, modules: dict, spans: list, host: list,
           top: int = 10) -> dict:
    """``devices``: plane name -> op events in start order (any iterable);
    ``modules``: plane name -> program executions; ``spans`` / ``host``:
    benchmark spans and other host events, each ``(start, end, name)``.

    Returns busy and traced window seconds (busy averaged over the chips
    that ran anything; the window cut where the device record stops),
    device seconds and executions per program, the top device operations
    by self time and the first chip's longest idle gaps."""
    lo, hi = window(spans)
    busy, ops, ends, first = [], {}, [], None
    for dev in sorted(devices):
        d = device_summary(devices[dev], lo, hi, top)
        if d["busy_s"] <= 0 and not d["self_s"]:
            continue
        busy.append(d["busy_s"])
        ends.append(d["end"])
        for n, sec in d["self_s"].items():
            ops[n] = ops.get(n, 0.0) + sec
        if first is None:
            first = d
    end = traced_end(ends, host, hi)
    gaps = []
    if first is not None:
        gaps = first["gaps"] + ([(first["end"], end)]
                                if end > first["end"] else [])
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    programs: dict = {}
    for dev in modules:
        for s, t, n in modules[dev]:
            if lo <= s < end:
                c, sec = programs.get(n, (0, 0.0))
                programs[n] = (c + 1, sec + (t - s))
    return {
        "window_s": end - lo,
        "cut_s": hi - end,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "chips_traced": len(busy),
        "programs": {n: {"count": c, "seconds": s}
                     for n, (c, s) in programs.items()},
        "device_ops": sorted(([n, s] for n, s in ops.items() if s > 0),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": [[label_gap(spans, host, g), g[1] - g[0]]
                      for g in gaps],
    }


def _events(line):
    for e in line.events:
        s = e.start_ns * 1e-9
        yield s, s + e.duration_ns * 1e-9, e.name


def summarize(path: str, top: int = 10) -> dict:
    """Read an ``.xplane.pb`` and reduce it; ``lines`` lists every plane's
    lines."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans, host, lines = [], [], []
    devs, mods = {}, {}
    for plane in pd.planes:
        is_dev = plane.name.startswith("/device:") and "CPU" not in plane.name
        for line in plane.lines:
            if is_dev and line.name == OPS_LINE:
                devs[plane.name] = line
            elif is_dev and line.name == MODULES_LINE:
                mods[plane.name] = [(s, t, program_name(n))
                                    for s, t, n in _events(line)]
            elif plane.name.startswith("/host:"):
                for ev in _events(line):
                    (spans if ev[2].startswith(SPAN_PREFIX)
                     else host).append(ev)
            lines.append((plane.name, line.name))
    out = reduce({k: _events(v) for k, v in devs.items()}, mods, spans, host,
                 top)
    out["lines"] = lines
    return out

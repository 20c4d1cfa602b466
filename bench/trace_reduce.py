"""From a profiler trace to the per-layer numbers of a traced run.

The reduction works on plain interval lists, so a test can hand-build
them:

* ``devices``: ``{chip: [(start_ns, end_ns, op_name), ...]}``, the
  operations that ran on each chip;
* ``host``: ``[(start_ns, end_ns, name), ...]``, the spans of the host
  thread that drove the calls, nested by time;
* ``calls``: ``[(start_ns, end_ns), ...]``, the timed calls (the host
  spans named ``CALL_SPAN``).

Busy time is the union of a chip's operation intervals inside the calls;
the idle share is one minus busy over the calls' time, averaged over the
chips.  An idle gap is a stretch between two operations of a chip, from
the first call's start to the last call's end; it is named by the
innermost host span under its midpoint, prefixed with whether that lies
inside a call or between calls, and gaps of one name are added up.

``from_xspace`` reads the same lists from a JAX profiler trace
(``jax.profiler.ProfileData``): device planes are those named
``/device:<KIND>:<n>``, their operations are the events of the line
``XLA Modules`` (one per execution of a compiled program), and the host
thread is the line that carries the ``CALL_SPAN`` events.  A scan of
1e5 steps would record some 1e7 per-op events a call and overflow the
chip's trace buffers, so traced runs compile without per-op trace marks
(``bench/run.py``) and the reduction reads whole program executions.  A
trace whose device buffers overflowed (a ``Trace Buffers Dropped``
event) is incomplete: ``from_xspace`` then returns no devices, and the
device metrics stay silent.
"""

from __future__ import annotations

import bisect
import re

#: name of the host span the harness opens around each timed call
CALL_SPAN = "bench.call"
#: entries kept in each list of the breakdown
TOP = 10
#: the event a chip records when its trace buffers overflowed
DROPPED = "Trace Buffers Dropped"

_DEVICE_PLANE = re.compile(r"^/device:([A-Z]+):(\d+)$")


def union(intervals) -> list[tuple[int, int]]:
    """Merged, sorted union of ``(start, end, ...)`` intervals."""
    out: list[list[int]] = []
    for s, e in sorted((int(iv[0]), int(iv[1])) for iv in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(s: int, e: int, windows, starts) -> int:
    """Length of ``[s, e)`` inside the merged ``windows`` (``starts`` are
    their start points, for bisection)."""
    i = max(0, bisect.bisect_right(starts, s) - 1)
    total = 0
    while i < len(windows) and windows[i][0] < e:
        total += max(0, min(e, windows[i][1]) - max(s, windows[i][0]))
        i += 1
    return total


def host_segments(host) -> list[tuple[int, int, str]]:
    """The host timeline cut where its innermost span changes:
    ``(start, end, innermost span name)``, calls left out."""
    evs = sorted(((int(s), int(e), n) for s, e, n in host
                  if n != CALL_SPAN and e > s), key=lambda x: (x[0], -x[1]))
    bounds = sorted({b for s, e, _ in evs for b in (s, e)})
    segs, stack, i = [], [], 0
    for a, b in zip(bounds, bounds[1:]):
        while i < len(evs) and evs[i][0] <= a:
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] <= a:
            stack.pop()
        if stack:
            segs.append((a, b, stack[-1][2]))
    return segs


def _namer(host, calls):
    segs = host_segments(host)
    seg_starts = [s for s, _, _ in segs]
    call_starts = [s for s, _ in calls]

    def name(t: int) -> str:
        where = ("in call" if overlap(t, t + 1, calls, call_starts)
                 else "between calls")
        i = bisect.bisect_right(seg_starts, t) - 1
        if i >= 0 and segs[i][0] <= t < segs[i][1]:
            return f"{where}: {segs[i][2]}"
        return where
    return name


def _top(totals: dict, chips: int) -> list[list]:
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:TOP]
    return [[k, v / chips / 1e9] for k, v in ranked]


def reduce(devices: dict, host: list, calls: list) -> dict:
    """The reduced trace of one traced run: per chip, the busy ns inside
    the calls; the calls' ns; the operations and idle gaps that took most
    time, in seconds averaged over the chips."""
    calls = union(calls)
    starts = [s for s, _ in calls]
    span = (calls[0][0], calls[-1][1]) if calls else (0, 0)
    name = _namer(host, calls)
    chips = sorted(devices)
    busy, ops, gaps = {}, {}, {}
    for chip in chips:
        merged = union(devices[chip])
        busy[chip] = sum(overlap(s, e, calls, starts) for s, e in merged)
        for s, e, op in devices[chip]:
            part = overlap(int(s), int(e), calls, starts)
            if part:
                ops[op] = ops.get(op, 0) + part
        edges = [(span[0], span[0])] + merged + [(span[1], span[1])]
        for (_, e0), (s1, _) in zip(edges, edges[1:]):
            lo, hi = max(e0, span[0]), min(s1, span[1])
            # a gap that crosses a call's edge is named piece by piece
            cuts = sorted({lo, hi} | {t for c in calls for t in c
                                      if lo < t < hi})
            for a, b in zip(cuts, cuts[1:]):
                key = name((a + b) // 2)
                gaps[key] = gaps.get(key, 0) + (b - a)
    n = max(1, len(chips))
    return {"chips": chips, "window_ns": sum(e - s for s, e in calls),
            "busy_ns": busy, "device_ops": _top(ops, n),
            "idle_gaps": _top(gaps, n)}


def from_xspace(space) -> tuple[dict, list, list]:
    """``(devices, host, calls)`` of a ``jax.profiler.ProfileData``."""
    devices, host, calls, dropped = {}, [], [], False
    for plane in space.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    devices[plane.name] = [(ev.start_ns, ev.end_ns, ev.name)
                                           for ev in line.events]
                elif line.name == "XLA TraceMe":
                    dropped |= any(ev.name == DROPPED for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                events = [(ev.start_ns, ev.end_ns, ev.name)
                          for ev in line.events]
                mine = [(s, e) for s, e, n in events if n == CALL_SPAN]
                if mine:
                    calls.extend(mine)
                    host.extend(events)
    return ({} if dropped else devices), host, calls

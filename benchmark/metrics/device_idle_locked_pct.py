"""Device: the share of the profiled sub-window in which device 0 is idle
while a host thread holds the combiner lock, as the program's
`nr.round.hold` and `nr.read.hold` spans (profiler annotations, on the
device trace's clock) show it. The lock admits one holder at a time, so
those spans do not overlap. The idle stretches are the gaps between the
busy `XLA Ops` intervals of device 0; their overlap with the spans is
divided by the window `device_idle_pct` divides by, so this share is at
most that one. Host work under the lock, a compile or a pause inside a
round shows here; a stall with the lock free does not.

The trace is the one `run.py` writes, under `.bench_out/trace` of the
checkout this file lies in. None off the TPU, and where the trace holds
no hold span (a program without them)."""

import os

from benchmark.trace_reduce import find_xplane, union_intervals

HOLD_SPANS = ("nr.round.hold", "nr.read.hold")
TRACE_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), ".bench_out", "trace")


def idle_under(busy, holds) -> float:
    """Length of the gaps between the `busy` intervals that lie under the
    `holds` intervals (both lists of [start, end), any order)."""
    b = union_intervals(busy)
    gaps = [(e0, s1) for (_, e0), (s1, _) in zip(b, b[1:])]
    h = union_intervals(holds)
    total, i, j = 0.0, 0, 0
    while i < len(gaps) and j < len(h):
        s, e = max(gaps[i][0], h[j][0]), min(gaps[i][1], h[j][1])
        if e > s:
            total += e - s
        if gaps[i][1] < h[j][1]:
            i += 1
        else:
            j += 1
    return total


def intervals(path: str):
    """(busy intervals of device 0's `XLA Ops`, hold spans of any host
    thread) of a trace, in ns; None when it holds no TPU device plane."""
    from jax.profiler import ProfileData

    busy, holds, dev0 = [], [], None
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:TPU:"):
            if dev0 is None or plane.name < dev0:
                dev0 = plane.name
                busy = [(e.start_ns, e.start_ns + e.duration_ns)
                        for line in plane.lines if line.name == "XLA Ops"
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            holds += [(e.start_ns, e.start_ns + e.duration_ns)
                      for line in plane.lines for e in line.events
                      if e.name in HOLD_SPANS]
    return (busy, holds) if dev0 is not None else None


def read(ctx, trace_dir: str = TRACE_DIR):
    if ctx.get("trace") is None or ctx.get("window_s", 0) <= 0:
        return None
    path = find_xplane(trace_dir)
    got = intervals(path) if path else None
    if not got or not got[1]:
        return None
    busy, holds = got
    return 100.0 * idle_under(busy, holds) / 1e9 / ctx["window_s"]

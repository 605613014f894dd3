"""Device-0 idle time under the program's own host spans.

The trainer marks its host phases with profiler spans on the line that
holds the benchmark's ``chipbench.solve`` spans (``trace.Trace.host``),
on the device ops' clock:

    repro.setup      entry of ``run`` / ``run_sharded`` up to round 1
    repro.round      one round (a step annotation, ``step_num`` = t)
      repro.dispatch   the key split and the round's enqueue
      repro.readback   the metric's read-back and the stop test
    repro.finish     the final iterate's copy to the host

An idle instant of device 0 is one of the window in which no device-0
operation runs (``trace.idle_gaps``). The readers charge each such
instant to the host phase that covers it. The sets below never
overlap, so their sums add up to at most the window's idle time; what
lies under no ``repro.*`` span (the harness between solves) is left
out of all three.
"""
from __future__ import annotations

from chipbench import trace

SETUP, ROUND, DISPATCH, READBACK, FINISH = (
    "repro.setup", "repro.round", "repro.dispatch", "repro.readback",
    "repro.finish")


def overlap_ns(a, b) -> int:
    """Length of the intersection of two lists of ``(start, end)``
    intervals, each list free of overlaps within itself."""
    a, b = sorted(a), sorted(b)
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def named(host, name: str, lo: int, hi: int) -> list:
    """The host spans called ``name`` that reach into [lo, hi]."""
    return [s for s in host if s.name == name and s.end > lo and s.start < hi]


def phases(host, lo: int, hi: int) -> dict:
    """The window's host intervals by phase: ``readback``, ``dispatch``
    (every dispatch but each solve's first) and ``call`` (set-up, each
    solve's first dispatch, finish), clipped to [lo, hi]."""
    dispatch = named(host, DISPATCH, lo, hi)
    first = set()
    for solve in named(host, trace.SOLVE_SPAN, lo, hi):
        inside = [d for d in dispatch
                  if solve.start <= d.start < solve.end]
        if inside:
            first.add(min(inside, key=lambda d: d.start))

    def clip(spans):
        return [(max(s.start, lo), min(s.end, hi)) for s in spans]

    return {"readback": clip(named(host, READBACK, lo, hi)),
            "dispatch": clip([d for d in dispatch if d not in first]),
            "call": clip(named(host, SETUP, lo, hi) + list(first)
                         + named(host, FINISH, lo, hi))}


def idle_ms(ctx, phase: str) -> float | None:
    """Device-0 idle milliseconds in the window under ``phase``'s host
    spans; ``None`` with no device-0 operation or no ``repro.round``
    span to read."""
    lo, hi = ctx.window
    if not ctx.device0 or not named(ctx.trace.host, ROUND, lo, hi):
        return None
    gaps = trace.idle_gaps(ctx.device0, lo, hi)
    return overlap_ns(gaps, phases(ctx.trace.host, lo, hi)[phase]) * 1e-6


def rounds(ctx) -> int:
    """The ``repro.round`` spans in the window."""
    return len(named(ctx.trace.host, ROUND, *ctx.window))


def solves(ctx) -> int:
    """The ``chipbench.solve`` spans in the window."""
    return len(named(ctx.trace.host, trace.SOLVE_SPAN, *ctx.window))

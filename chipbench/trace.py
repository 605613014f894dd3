"""Reduce a JAX profiler trace to the intervals the metrics read.

``load`` reads the newest ``*.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData`` and keeps, in nanoseconds on the trace's
one clock:

  * per device, the operations that ran on it (the ``XLA Ops`` line of
    each ``/device:TPU:<n>`` plane). Each event is named by its HLO
    instruction's text; the instruction's name (``scd.9``,
    ``all-reduce.1``, ``fusion.12``) and opcode (``custom-call``,
    ``while``) are parsed from it. A ``while``, ``conditional`` or
    ``call`` event holds the events of its body;
  * the host events of the thread that ran the benchmark's solves
    (the line holding the ``chipbench.solve`` spans): the benchmark's
    own spans and JAX's dispatch events.

Everything after ``load`` is plain arithmetic on those intervals, and
is tested on built traces.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

SOLVE_SPAN = "chipbench.solve"
_DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:(\d+)$")
_OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")
# "%scd.9 = (f32[250]{0:T(256)}, ...) custom-call(s32[256]{0} %pad.46, ..."
_HLO = re.compile(r"^%?(?P<name>[^\s=]+) = .*?\s(?P<op>[a-z][\w\-]*)\(")
CONTAINERS = frozenset({"while", "conditional", "call"})
_COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)")


@dataclass(frozen=True)
class Span:
    name: str
    start: int            # ns
    end: int              # ns
    op: str = ""          # the HLO opcode of a device event


def parse_op(text: str) -> tuple[str, str]:
    """``(name, opcode)`` of a device event named by its HLO text; an
    event that is not HLO text keeps its name and has no opcode."""
    m = _HLO.match(text)
    return (m.group("name"), m.group("op")) if m else (text, "")


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)   # ordinal -> [Span]
    host: list = field(default_factory=list)      # [Span], sorted

    def window(self) -> tuple[int, int] | None:
        """From the first solve span's start to the last one's end."""
        solves = [s for s in self.host if s.name == SOLVE_SPAN]
        if not solves:
            return None
        return min(s.start for s in solves), max(s.end for s in solves)


def base_name(op: str) -> str:
    """``scd.3`` -> ``scd``: the name an operation was given, without
    the number the compiler appends."""
    return _SUFFIX.sub("", op)


def is_scd(span: Span) -> bool:
    """The Pallas SCD kernel (``pallas_call(..., name="scd")``)."""
    return base_name(span.name) == "scd"


def is_collective(span: Span) -> bool:
    """An operation that moves data between chips, by its opcode."""
    return bool(_COLLECTIVE.match(span.op))


def _device_span(event) -> Span:
    name, op = parse_op(event.name)
    return Span(name, int(event.start_ns), int(event.end_ns), op)


def load(trace_dir: Path) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    trace = Trace()
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    trace.devices[int(m.group(1))] = sorted(
                        (_device_span(e) for e in line.events),
                        key=lambda s: s.start)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans = [Span(e.name, int(e.start_ns), int(e.end_ns))
                         for e in line.events]
                if any(s.name == SOLVE_SPAN for s in spans):
                    trace.host = sorted(spans, key=lambda s: s.start)
    return trace


def _clip(spans, lo: int, hi: int):
    for s in spans:
        a, b = max(s.start, lo), min(s.end, hi)
        if b > a:
            yield s, a, b


def busy_ns(spans, lo: int, hi: int) -> int:
    """Length of the union of the spans inside [lo, hi]: overlapping
    operations count once."""
    total, cur_a, cur_b = 0, None, None
    for _, a, b in sorted(_clip(spans, lo, hi), key=lambda t: t[1]):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def idle_gaps(spans, lo: int, hi: int) -> list[tuple[int, int]]:
    """The intervals of [lo, hi] in which no span runs, longest first."""
    gaps, t = [], lo
    for _, a, b in sorted(_clip(spans, lo, hi), key=lambda t: t[1]):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return sorted(gaps, key=lambda g: g[0] - g[1])


def op_ns(spans, lo: int, hi: int, match) -> int:
    """Summed durations inside [lo, hi] of the spans ``match`` accepts."""
    return sum(b - a for s, a, b in _clip(spans, lo, hi) if match(s))


def count(spans, lo: int, hi: int, match) -> int:
    return sum(1 for s, _, _ in _clip(spans, lo, hi) if match(s))


def host_activity(host, a: int, b: int) -> str:
    """What the host was doing in the gap [a, b]: the innermost host
    event, other than the solve span, that covers the gap's midpoint."""
    mid = (a + b) // 2
    best = None
    for s in host:
        if s.start > mid:
            break
        if s.end >= mid and s.name != SOLVE_SPAN and (
                best is None or s.end - s.start < best.end - best.start):
            best = s
    return best.name if best else "no host event"


def breakdown(trace: Trace, device: int, lo: int, hi: int,
              n: int = 10) -> dict:
    """The device operations that took most time on ``device`` within
    the window (by name without the compiler's number; a loop's body
    counts, the loop itself does not), and the longest idle gaps named
    by what the host was doing."""
    spans = trace.devices.get(device, [])
    per_op: dict[str, int] = {}
    leaves = [s for s in spans if s.op not in CONTAINERS]
    for s, a, b in _clip(leaves, lo, hi):
        key = base_name(s.name)
        per_op[key] = per_op.get(key, 0) + (b - a)
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:n]
    gaps = idle_gaps(spans, lo, hi)[:n]
    return {"device_ops": [[k, v * 1e-9] for k, v in ops],
            "idle_gaps": [[host_activity(trace.host, a, b), (b - a) * 1e-9]
                          for a, b in gaps]}

"""Profiler trace -> device busy time, per-operation device time, and the
device's idle gaps attributed to what the host was doing.

``load`` reads the newest ``*.xplane.pb`` under a trace directory with
``jax.profiler.ProfileData`` into plain lists of ``(name, start_ns,
duration_ns)``: the operations of each TPU core (its ``XLA Ops`` line) and
the benchmark's own host spans (``bench.*`` ``TraceAnnotation``s).
``summarize`` reduces those lists inside the ``bench.window`` span:

- busy: the union of the intervals in which an operation ran, per chip,
  averaged over the chips that ran any (``chip_busy_s``: summed);
- op time: summed device durations per operation name over every chip:
  the HLO instruction's name (``maxsim_scores.1``, ``fusion.3``), cut
  from the instruction text that a TPU's ``XLA Ops`` line carries as the
  event name (``%maxsim_scores.1 = f32[...] custom-call(...)``), with the
  number of chips whose lane has the op, so that one dispatch of a program
  that runs on every chip counts once;
- idle gaps: the complement of busy inside the window, each piece billed
  to the innermost ``bench.*`` span covering it (``host.none`` where no
  span does).
"""
from __future__ import annotations

import bisect
import re
import shutil
from dataclasses import dataclass, field
from pathlib import Path

WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
NO_SPAN = "host.none"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
TOP = 10
HLO_TEXT = re.compile(r"^%?([^\s=%]+) = ")


def clear(trace_dir: Path) -> None:
    shutil.rmtree(trace_dir, ignore_errors=True)


def op_name(event_name: str) -> str:
    """The instruction name of a device op event: ``maxsim_scores.1`` for
    ``%maxsim_scores.1 = f32[...] custom-call(...)``; a name that is no
    instruction text stays as it is."""
    m = HLO_TEXT.match(event_name)
    return m.group(1) if m else event_name


def load(trace_dir: Path) -> dict:
    """{"devices": [[(name, start_ns, dur_ns), ...] per TPU core],
    "host": [(name, start_ns, dur_ns), ...] bench spans}."""
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    data = ProfileData.from_file(str(files[-1]))
    devices, host = [], []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = [(op_name(e.name), e.start_ns, e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            host.extend((e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events
                        if e.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # averaged over the chips that ran ops
    op_s: dict                    # op name -> device seconds (all chips)
    op_n: dict                    # op name -> events in the window
    idle_s: dict                  # host span -> idle device seconds
    op_lanes: dict = field(default_factory=dict)  # op -> chips that ran it
    chips: int = 1                # chips that ran ops

    @property
    def chip_busy_s(self) -> float:
        """Busy time summed over the chips that ran ops."""
        return self.busy_s * self.chips

    def op_seconds(self, match) -> tuple:
        """(device seconds summed over chips, dispatches) of the ops whose
        name ``match`` accepts: an op's events over the chips whose lane
        has it (one for an op not counted by lane)."""
        names = [n for n in self.op_s if match(n)]
        return (sum(self.op_s[n] for n in names),
                sum(self.op_n[n] / self.op_lanes.get(n, 1) for n in names))

    def breakdown(self) -> dict:
        top = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:TOP]
        gaps = sorted(self.idle_s.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[n, s] for n, s in top],
                "idle_gaps": [[n, s] for n, s in gaps]}


def summarize(trace: dict) -> Summary:
    windows = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0]
    spans = _Spans([(s, s + d, n) for n, s, d in trace["host"]
                    if n != WINDOW_SPAN and _clip(s, s + d, lo, hi)])
    op_s, op_n, lanes, busy, idle = {}, {}, {}, [], {}
    for ops in trace["devices"]:
        ivs, names = [], set()
        for name, s, d in ops:
            c = _clip(s, s + d, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            names.add(name)
            op_s[name] = op_s.get(name, 0.0) + (c[1] - c[0]) * 1e-9
            op_n[name] = op_n.get(name, 0) + 1
        for name in names:
            lanes[name] = lanes.get(name, 0) + 1
        if not ivs:
            continue
        merged = _union(ivs)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        for gs, ge in _gaps(merged, lo, hi):
            for name, sec in spans.attribute(gs, ge).items():
                idle[name] = idle.get(name, 0.0) + sec
    n_chips = max(len(busy), 1)
    return Summary((hi - lo) * 1e-9, sum(busy) / n_chips, op_s, op_n,
                   {k: v / n_chips for k, v in idle.items()}, lanes,
                   n_chips)


def _gaps(merged: list, lo: float, hi: float) -> list:
    out, t = [], lo
    for s, e in merged:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class _Spans:
    """Host spans sorted by start, for billing device gaps to them."""

    def __init__(self, spans: list):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.longest = max((e - s for s, e, _ in self.spans), default=0)

    def attribute(self, gs: float, ge: float) -> dict:
        """Seconds of the gap [gs, ge) under each innermost span: cut the
        gap at every span edge inside it and bill each piece to the
        shortest span covering it."""
        j = bisect.bisect_left(self.starts, ge)
        first = bisect.bisect_left(self.starts, gs - self.longest)
        cover = [sp for sp in self.spans[first:j] if sp[1] > gs]
        edges = sorted({gs, ge, *(x for s, e, _ in cover for x in (s, e)
                                  if gs < x < ge)})
        out = {}
        for a, b in zip(edges, edges[1:]):
            inner = [(e - s, n) for s, e, n in cover if s <= a and e >= b]
            name = min(inner)[1] if inner else NO_SPAN
            out[name] = out.get(name, 0.0) + (b - a) * 1e-9
        return out

"""The program's own spans in a traced run: the serving frontend's host
spans and the cascade stage of each device op.

``trace_reduce.load`` keeps the benchmark's ``bench.*`` spans alone. This
module reads the same ``*.xplane.pb`` under ``harness.TRACE_DIR / <cell>``
and keeps, besides those, the ``frontend.*`` spans that
``repro.retrieval.frontend`` writes around one dispatch, and the
``cascade.*`` scope (``jax.named_scope`` in ``repro.retrieval.engine``)
found in each device op's ``SCOPE_STAT``: a stat of the op's metadata,
which ``jax.profiler.ProfileData`` does not show, so ``bench.xspace``
reads the file. Reduced inside ``bench.window`` with ``trace_reduce``'s
window, union, gap and innermost-span helpers:

- idle: each device gap billed to the innermost span among the
  ``bench.*`` and ``frontend.*`` spans covering it;
- scope time: summed device durations of the ops under each scope;
- flushes: ``frontend.flush`` spans that began in the window.

The names are copied here, not imported from the program, so that the
yardstick cannot move with the program; ``bench/tests`` checks the copies
against ``repro.retrieval.tracing``. ``read`` parses and reduces each
file once (cached by path and mtime), however many readers ask.
"""
from __future__ import annotations

import re
from dataclasses import dataclass
from pathlib import Path

from bench import trace_reduce as TR
from bench import xspace

FLUSH = "frontend.flush"
PAD = "frontend.pad"
LAUNCH = "frontend.launch"
SYNC = "frontend.sync"
TRANSLATE = "frontend.translate"
FRONTEND_PREFIX = "frontend."
SCOPE_MASK = "cascade.mask"
SCOPE_SCAN = "cascade.scan"
SCOPE_RERANK = "cascade.rerank"
# the stat of an ``XLA Ops`` event's metadata that holds the op's scope
# path on a TPU (``jit(local_body)/cascade.scan/jit(maxsim_scores)/
# pallas_call:``)
SCOPE_STAT = "tf_op"
SCOPE = re.compile(r"(?:^|/)(cascade\.(?:mask|scan|rerank))(?:[/:]|$)")
KEEP = (TR.HOST_PREFIX, FRONTEND_PREFIX)

_CACHE: dict = {}


def scope_of(path: str) -> str | None:
    """The cascade scope in an op's scope path, or None."""
    m = SCOPE.search(path)
    return m.group(1) if m else None


def _newest(trace_dir: Path) -> Path | None:
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    return files[-1] if files else None


def load(trace_dir: Path) -> dict:
    """{"devices": [[(op, start_ns, dur_ns, scope), ...] per TPU core],
    "host": [(name, start_ns, dur_ns), ...] bench and frontend spans} of
    the newest trace file under ``trace_dir``."""
    path = _newest(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return _parse(path)


def _parse(path: Path) -> dict:
    devices, host = [], []
    for plane in xspace.read(path).planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        if TR.DEVICE_PLANE.match(plane.name):
            scopes = {k: scope_of(v) for k, v in
                      xspace.metadata_stat(plane, SCOPE_STAT).items()}
            devices.append([(TR.op_name(n), s, d, scopes.get(k))
                            for line in plane.lines
                            if line.name == TR.OPS_LINE
                            for n, s, d, k in xspace.events(line, names)])
        elif plane.name.startswith("/host:"):
            keep = {k for k, n in names.items() if n.startswith(KEEP)}
            host.extend((n, s, d) for line in plane.lines
                        for n, s, d, k in xspace.events(line, names)
                        if k in keep)
    return {"devices": devices, "host": host}


@dataclass
class Spans:
    idle_s: dict        # innermost bench/frontend span -> idle device s
    scope_s: dict       # cascade scope -> device seconds
    flushes: int        # frontend.flush spans begun in the window


def summarize(trace: dict) -> Spans:
    """Reduce ``load``'s lists inside the ``bench.window`` span; idle and
    scope time are averaged over the chips that ran ops."""
    windows = [(s, s + d) for n, s, d in trace["host"]
               if n == TR.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {TR.WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0]
    inside = [(s, s + d, n) for n, s, d in trace["host"]
              if n != TR.WINDOW_SPAN and TR._clip(s, s + d, lo, hi)]
    spans = TR._Spans(inside)
    flushes = sum(1 for s, _, n in inside if n == FLUSH and lo <= s < hi)
    idle, scope_s, chips = {}, {}, 0
    for ops in trace["devices"]:
        ivs = []
        for _, s, d, scope in ops:
            c = TR._clip(s, s + d, lo, hi)
            if c is None:
                continue
            ivs.append(c)
            if scope is not None:
                scope_s[scope] = scope_s.get(scope, 0.0) \
                    + (c[1] - c[0]) * 1e-9
        if not ivs:
            continue
        chips += 1
        for gs, ge in TR._gaps(TR._union(ivs), lo, hi):
            for name, sec in spans.attribute(gs, ge).items():
                idle[name] = idle.get(name, 0.0) + sec
    n = max(chips, 1)
    return Spans({k: v / n for k, v in idle.items()},
                 {k: v / n for k, v in scope_s.items()}, flushes)


def read(run) -> Spans | None:
    """The program's spans of a traced run, summarized once per trace
    file; None for an untraced run or when its trace file is gone."""
    if run.trace is None:
        return None
    from bench import harness
    path = _newest(harness.TRACE_DIR / run.cell.name)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = summarize(_parse(path))
    return _CACHE[key]


def per_dispatch_ms(run, seconds) -> float | None:
    """``seconds`` over the window's dispatches, in ms; None without
    dispatches."""
    n = run.counters.get("dispatches", 0)
    return 1e3 * seconds / n if n else None

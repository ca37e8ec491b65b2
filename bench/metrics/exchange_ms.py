"""exchange_ms: device time of the ops under the cascade's
``cascade.exchange`` scope, per dispatch in the window, in ms, averaged
over the chips that ran ops, from the profiler trace.

On a doc-sharded store the scope marks the cross-chip exchange of each
stage (``repro.retrieval.topk.gathered_merge_topk`` after the scan; the
all-gathers of the shards' rerank scores and ids and their merge after
the rerank). It nests inside ``cascade.scan`` and ``cascade.rerank``,
whose readers (``bench.program_spans``, first scope of the path) keep
billing these ops to their stage; this reader matches the scope anywhere
in an op's scope path (the ``tf_op`` stat of its metadata). None where
no device op carries the scope: one chip, or a program without it.

The scope's name is copied here, not imported from the program, so that
the yardstick cannot move with the program; ``bench/tests`` checks the
copy against ``repro.retrieval.tracing``.
"""
import re

from bench import program_spans as PS
from bench import trace_reduce as TR
from bench import xspace

SCOPE_EXCHANGE = "cascade.exchange"
IN_EXCHANGE = re.compile(r"(?:^|/)cascade\.exchange(?:[/:]|$)")

_CACHE: dict = {}


def parse(path) -> dict:
    """{"devices": [[(op, start_ns, dur_ns, scope path or None), ...] per
    TPU core], "host": [(name, start_ns, dur_ns), ...] benchmark spans}
    of one trace file: ``program_spans.load``'s lists with each op's whole
    scope path in place of its first cascade scope."""
    devices, host = [], []
    for plane in xspace.read(path).planes:
        names = {k: v.name for k, v in plane.event_metadata.items()}
        if TR.DEVICE_PLANE.match(plane.name):
            paths = xspace.metadata_stat(plane, PS.SCOPE_STAT)
            devices.append([(TR.op_name(n), s, d, paths.get(k))
                            for line in plane.lines
                            if line.name == TR.OPS_LINE
                            for n, s, d, k in xspace.events(line, names)])
        elif plane.name.startswith("/host:"):
            host.extend((n, s, d) for line in plane.lines
                        for n, s, d, _ in xspace.events(line, names)
                        if n.startswith(TR.HOST_PREFIX))
    return {"devices": devices, "host": host}


def exchange_seconds(trace: dict) -> float | None:
    """Device seconds of the exchange's ops inside the ``bench.window``
    span, averaged over the chips that ran ops there; None where no op
    carries the scope."""
    windows = [(s, s + d) for n, s, d in trace["host"]
               if n == TR.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {TR.WINDOW_SPAN!r} span in the trace")
    lo, hi = windows[0]
    total, chips, seen = 0.0, 0, False
    for ops in trace["devices"]:
        ran = False
        for _, s, d, path in ops:
            c = TR._clip(s, s + d, lo, hi)
            if c is None:
                continue
            ran = True
            if path and IN_EXCHANGE.search(path):
                seen = True
                total += (c[1] - c[0]) * 1e-9
        chips += ran
    return total / max(chips, 1) if seen else None


def read(run):
    if run.trace is None:
        return None
    from bench import harness
    path = PS._newest(harness.TRACE_DIR / run.cell.name)
    if path is None:
        return None
    key = (str(path), path.stat().st_mtime_ns)
    if key not in _CACHE:
        _CACHE.clear()
        _CACHE[key] = exchange_seconds(parse(path))
    secs = _CACHE[key]
    return None if secs is None else PS.per_dispatch_ms(run, secs)

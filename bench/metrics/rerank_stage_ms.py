"""rerank_stage_ms: device time of the ops under the cascade's
``cascade.rerank`` scope (the gather-rerank kernel with its mask
conversion, its top-k and take), per dispatch in the window, in ms, from
the profiler trace (``bench.program_spans``); counted as
``scan_stage_ms``. None where no device op carries the scope."""
from bench import program_spans as PS


def read(run):
    sp = PS.read(run)
    if sp is None or PS.SCOPE_RERANK not in sp.scope_s:
        return None
    return PS.per_dispatch_ms(run, sp.scope_s[PS.SCOPE_RERANK])

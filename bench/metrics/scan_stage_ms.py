"""scan_stage_ms: device time of the ops under the cascade's
``cascade.scan`` scope (the scan kernel, the top-k and the merge around
it), per dispatch in the window, in ms, from the profiler trace
(``bench.program_spans``). An op that XLA adds outside the program's
operations, such as a layout copy of a stored array, carries that
array's name and no scope, and is not counted. None where no device op
carries the scope."""
from bench import program_spans as PS


def read(run):
    sp = PS.read(run)
    if sp is None or PS.SCOPE_SCAN not in sp.scope_s:
        return None
    return PS.per_dispatch_ms(run, sp.scope_s[PS.SCOPE_SCAN])

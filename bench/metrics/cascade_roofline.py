"""cascade_roofline: the whole served cascade against its roofline: the
least time of both stages' work (``scan_roofline.work`` and
``rerank_roofline.work``) summed over every dispatch answered in the
window, at one chip's peaks, over the device's busy time in the window
summed over the chips that ran (profiler trace). It
reads the same work whatever implements a stage, so it still bounds a
claim after a kernel is fused, replaced or taken off the path."""
from bench import manifest

_scan = manifest.metric_reader("scan_roofline")
_rerank = manifest.metric_reader("rerank_roofline")


def read(run):
    if run.trace is None or not run.buckets or run.trace.busy_s <= 0:
        return None
    total = sum(_scan.least(run, B, Q)[0] + _rerank.least(run, B, Q)[0]
                for B, Q in run.buckets)
    return 100.0 * total / run.trace.chip_busy_s

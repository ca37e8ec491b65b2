"""rerank_roofline: the Pallas gather-rerank kernel (``kernels/maxsim``
``maxsim_rerank_pallas``) against its roofline, from the profiler trace.

The work is what exact reranking needs for a dispatched (B, Q) bucket:
each query's ``prefetch_k`` candidate pages read once at full resolution,
and one multiply-add per (query token, page vector, candidate,
coordinate). Least time and share as in ``scan_roofline``: on a
doc-sharded store each chip reranks its members of the candidate set,
padded to a fixed number of rows, and the share counts the filler rows as
time spent on no work."""
import re

from bench import manifest

# the custom call's op name in the compiled cascade (see scan_roofline)
KERNEL = re.compile(r"^(maxsim_rerank|_rerank_kernel)(\.\d+)?$")
FAMILY = "maxsim_rerank"
_scan = manifest.metric_reader("scan_roofline")


def work(B: int, Q: int, shapes: dict) -> tuple:
    """(operations, bytes) of one rerank of B x prefetch_k candidates."""
    _, D, d = shapes["full"]
    L = shapes["prefetch_k"]
    return 2 * B * L * Q * D * d, B * L * D * d * shapes["full_itemsize"]


def least(run, B: int, Q: int) -> tuple:
    return _scan.least_s(*work(B, Q, run.shapes),
                         run.shapes["full_itemsize"], run.peaks)


def read(run):
    if run.trace is None or not run.buckets:
        return None
    secs, calls = _scan.kernel_seconds(run, KERNEL, FAMILY)
    if calls == 0 or secs <= 0:
        return None
    per = [least(run, B, Q) for B, Q in run.buckets]
    mean = sum(t for t, _ in per) / len(per)
    bounds = sorted({b for _, b in per})
    run.note(f"rerank_roofline: {calls:g} kernel dispatches, {secs:.6f} "
             f"chip-seconds, least {mean * 1e3:.4f} ms per call, "
             f"{'/'.join(bounds)}-bound")
    return 100.0 * mean * calls / secs

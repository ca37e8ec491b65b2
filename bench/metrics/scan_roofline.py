"""scan_roofline: the Pallas MaxSim scan kernel (``kernels/maxsim``
``maxsim_pallas``) against its roofline, from the profiler trace.

The work is what the algorithm needs for a dispatched (B, Q) bucket, not
what one implementation moves: the pooled corpus read once, and one
multiply-add per (query token, pooled vector, page, coordinate). The
least time is the larger of bytes / HBM bandwidth and operations / peak
(the peak of the stored dtype), at one chip's peaks; the share is that
least time, summed over the kernel's dispatches, over the kernel's device
time summed over chips. A dispatch of a doc-sharded store runs the kernel
once on every chip, each over its share of the corpus: it counts once,
and its chip-seconds are what it spent on all of them, so an evenly
split scan reads what one chip doing all of it would."""
import re

# the op name of the kernel's custom call in the compiled cascade (the
# jitted wrapper's name, as a described-v5e compile of the cascade shows);
# the Pallas body's own name as well, should a later XLA use that
KERNEL = re.compile(r"^(maxsim_scores|_maxsim_kernel)(\.\d+)?$")
FAMILY = "maxsim_scan"       # its family in the kernel dispatch registry


def work(B: int, Q: int, shapes: dict) -> tuple:
    """(operations, bytes) of one scan over the pooled corpus."""
    n, D, d = shapes["pooled"]
    return 2 * B * Q * D * n * d, n * D * d * shapes["pooled_itemsize"]


def least_s(ops: float, nbytes: float, itemsize: int, peaks: dict) -> tuple:
    """(least seconds, 'compute' or 'bytes')."""
    peak = peaks["int8_ops"] if itemsize == 1 else peaks["bf16_flops"]
    t_ops, t_bytes = ops / peak, nbytes / peaks["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "bytes")


def least(run, B: int, Q: int) -> tuple:
    return least_s(*work(B, Q, run.shapes), run.shapes["pooled_itemsize"],
                   run.peaks)


def kernel_seconds(run, kernel, family: str) -> tuple:
    """(device seconds summed over chips, dispatches) of the ops
    ``kernel`` matches (``trace_reduce.Summary.op_seconds``). Nothing
    matching is an error when the dispatch registry shows the family ran
    its Pallas kernel in this run: the op's name has changed under the
    pattern, and the metric would vanish unseen."""
    secs, calls = run.trace.op_seconds(kernel.match)
    if calls == 0 and family in run.kernels_ran:
        top = [n for n, _ in run.trace.breakdown()["device_ops"]]
        raise RuntimeError(f"{family} ran its Pallas kernel but no traced "
                           f"op matches {kernel.pattern!r}; the device's "
                           f"longest ops: {top}")
    return secs, calls


def read(run):
    if run.trace is None or not run.buckets:
        return None
    secs, calls = kernel_seconds(run, KERNEL, FAMILY)
    if calls == 0 or secs <= 0:
        return None
    per = [least(run, B, Q) for B, Q in run.buckets]
    mean = sum(t for t, _ in per) / len(per)
    bounds = sorted({b for _, b in per})
    run.note(f"scan_roofline: {calls:g} kernel dispatches, {secs:.6f} "
             f"chip-seconds, "
             f"least {mean * 1e3:.4f} ms per call, {'/'.join(bounds)}-bound")
    return 100.0 * mean * calls / secs

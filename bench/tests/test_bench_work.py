"""The work counters of the roofline readers against hand-computed shapes,
and the readers on a recorded window."""
import types

import pytest

from bench import manifest, trace_reduce

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9}
COLPALI = {"n_docs": 24576, "pooled": (24576, 34, 128), "pooled_itemsize": 2,
           "full": (24576, 1024, 128), "full_itemsize": 2,
           "prefetch_k": 256}

scan = manifest.metric_reader("scan_roofline.batch")
rerank = manifest.metric_reader("rerank_roofline.batch")
cascade = manifest.metric_reader("cascade_roofline.batch")


def test_scan_work_colpali_full_bucket():
    ops, nbytes = scan.work(16, 32, COLPALI)
    assert ops == 2 * 16 * 32 * 34 * 24576 * 128          # 109.5 GFLOP
    assert nbytes == 24576 * 34 * 128 * 2                 # 214 MB, once
    t, bound = scan.least_s(ops, nbytes, 2, PEAKS)
    assert bound == "compute" and t == pytest.approx(ops / 197e12)
    assert t == pytest.approx(0.5558e-3, rel=1e-3)


def test_rerank_work_colpali_full_bucket():
    ops, nbytes = rerank.work(16, 32, COLPALI)
    assert ops == 2 * 16 * 256 * 32 * 1024 * 128
    assert nbytes == 16 * 256 * 1024 * 128 * 2           # 1.07 GB
    t, bound = scan.least_s(ops, nbytes, 2, PEAKS)
    assert bound == "bytes" and t == pytest.approx(nbytes / 819e9)


def test_int8_store_is_held_to_the_int8_peak():
    ops, nbytes = scan.work(16, 32, dict(COLPALI, pooled_itemsize=1))
    assert nbytes == 24576 * 34 * 128
    t, bound = scan.least_s(ops, nbytes, 1, PEAKS)
    assert bound == "compute" and t == pytest.approx(ops / 393e12)


def _run(buckets, op_s, op_n, busy_s, window_s=1.0, ran=()):
    trace = trace_reduce.Summary(window_s, busy_s, op_s, op_n, {})
    return types.SimpleNamespace(trace=trace, buckets=buckets,
                                 shapes=COLPALI, peaks=PEAKS,
                                 kernels_ran=frozenset(ran),
                                 note=lambda msg: None)


def test_roofline_readers_share_of_least_time():
    least_scan = scan.least(_run([], {}, {}, 0), 16, 32)[0]
    least_rr = rerank.least(_run([], {}, {}, 0), 16, 32)[0]
    run = _run([(16, 32)] * 10,
               {"maxsim_scores.1": 10 * 4 * least_scan,
                "maxsim_rerank.1": 10 * 2 * least_rr, "fusion": 0.01},
               {"maxsim_scores.1": 10, "maxsim_rerank.1": 10, "fusion": 10},
               busy_s=10 * (4 * least_scan + 2 * least_rr) + 0.01)
    assert scan.read(run) == pytest.approx(25.0)
    assert rerank.read(run) == pytest.approx(50.0)
    assert cascade.read(run) == pytest.approx(
        100 * 10 * (least_scan + least_rr) / run.trace.busy_s)


def test_readers_return_nothing_without_the_kernel_or_trace():
    # a fusion named after the wrapper's jit is not the kernel
    run = _run([(16, 32)], {"fusion": 0.01, "maxsim_scores_fusion": 0.01},
               {"fusion": 1, "maxsim_scores_fusion": 1}, 0.01)
    assert scan.read(run) is None and rerank.read(run) is None
    run.trace = None
    assert cascade.read(run) is None


@pytest.mark.parametrize("reader,family", [(scan, "maxsim_scan"),
                                           (rerank, "maxsim_rerank")])
def test_reader_fails_when_a_kernel_that_ran_is_not_in_the_trace(reader,
                                                                 family):
    run = _run([(16, 32)], {"fusion": 0.01}, {"fusion": 1}, 0.01,
               ran=[family])
    with pytest.raises(RuntimeError, match=family):
        reader.read(run)

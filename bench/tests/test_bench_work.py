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


MS = 1_000_000   # ns
COLPALI_98K = dict(COLPALI, n_docs=98304, pooled=(98304, 34, 128),
                   full=(98304, 1024, 128))


def _traced(lanes: int, scan_ms: float, rerank_ms: float, n: int = 10,
            shapes=COLPALI):
    """A window of ``n`` (16, 32) dispatches, each the scan then the rerank
    on every one of ``lanes`` chips, summarized as a run would be."""
    period = 2 * (scan_ms + rerank_ms) * MS
    lane = []
    for i in range(n):
        t = 1 * MS + i * period
        lane += [("maxsim_scores.1", t, scan_ms * MS),
                 ("maxsim_rerank.1", t + scan_ms * MS, rerank_ms * MS)]
    trace = trace_reduce.summarize(
        {"devices": [list(lane) for _ in range(lanes)],
         "host": [(trace_reduce.WINDOW_SPAN, 0, int(n * period + 2 * MS))]})
    return types.SimpleNamespace(trace=trace, buckets=[(16, 32)] * n,
                                 shapes=shapes, peaks=PEAKS,
                                 kernels_ran=frozenset(),
                                 note=lambda msg: None)


@pytest.mark.parametrize("reader", [scan, rerank, cascade],
                         ids=["scan", "rerank", "cascade"])
def test_work_split_over_four_chips_reads_as_one_chip(reader):
    # the same dispatches split evenly over four chips' lanes, each chip
    # taking a quarter of the time, read what one chip doing it all reads
    one = reader.read(_traced(1, 4 * 1.77, 4 * 3.29))
    four = reader.read(_traced(4, 1.77, 3.29))
    assert four == pytest.approx(one, rel=1e-12)
    assert 0 < four < 100


def test_four_chip_scan_reads_a_chips_share_of_the_whole_corpus():
    # 98,304 pages over four chips, each scanning its 24,576 in the
    # one-chip kernel time: the one-chip share, not four times it
    least_24k = scan.least(_run([], {}, {}, 0), 16, 32)[0]
    run = _traced(4, 1.77, 3.29, shapes=COLPALI_98K)
    assert scan.read(run) == pytest.approx(100 * least_24k / 1.77e-3,
                                           rel=1e-9)
    assert scan.read(run) < 35


def test_one_chip_readings_keep_their_formula_exactly():
    # at one chip the per-chip counts reduce to the formulas of one lane:
    # share = mean least time * kernel events / kernel seconds, and the
    # cascade's summed least time over busy time
    run = _traced(1, 1.77, 3.29)
    t = run.trace
    for reader, name in ((scan, "maxsim_scores.1"),
                         (rerank, "maxsim_rerank.1")):
        mean = reader.least(run, 16, 32)[0]
        assert reader.read(run) == 100.0 * mean * t.op_n[name] \
            / t.op_s[name]
    total = sum(scan.least(run, B, Q)[0] + rerank.least(run, B, Q)[0]
                for B, Q in run.buckets)
    assert cascade.read(run) == 100.0 * total / t.busy_s

"""bench.trace_reduce on small traces: busy union, idle share, op time
and the billing of device gaps to host spans."""
import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import trace_reduce as TR

MS = 1_000_000   # ns


def _trace():
    # window [0, 100) ms; two overlapping ops, one op straddling the end,
    # one op before the window (ignored)
    dev = [("scan", 10 * MS, 20 * MS), ("fusion.1", 25 * MS, 10 * MS),
           ("rerank", 90 * MS, 20 * MS), ("early", -20 * MS, 10 * MS)]
    host = [("bench.window", 0, 100 * MS),
            ("bench.pump", 0, 40 * MS),           # covers [0, 40)
            ("bench.submit", 5 * MS, 2 * MS),     # inner span in a gap
            ("bench.sleep", 50 * MS, 30 * MS)]    # [50, 80)
    return {"devices": [dev], "host": host}


def test_busy_union_and_op_time():
    s = TR.summarize(_trace())
    assert s.window_s == pytest.approx(0.1)
    # union: [10, 35) + [90, 100) = 35 ms
    assert s.busy_s == pytest.approx(0.035)
    assert s.op_s == pytest.approx({"scan": 0.02, "fusion.1": 0.01,
                                    "rerank": 0.01})
    assert s.op_n == {"scan": 1, "fusion.1": 1, "rerank": 1}
    assert s.op_seconds(lambda n: n in ("scan", "rerank")) == (
        pytest.approx(0.03), 2)


def test_gaps_billed_to_innermost_span():
    s = TR.summarize(_trace())
    # gaps: [0, 10) under pump except [5, 7) under submit; [35, 40) pump;
    # [40, 50) none; [50, 80) sleep; [80, 90) none
    assert s.idle_s == pytest.approx({"bench.pump": 0.013,
                                      "bench.submit": 0.002,
                                      TR.NO_SPAN: 0.02,
                                      "bench.sleep": 0.03})
    assert sum(s.idle_s.values()) == pytest.approx(s.window_s - s.busy_s)
    b = s.breakdown()
    assert b["device_ops"][0] == ["scan", pytest.approx(0.02)]
    assert b["idle_gaps"][0] == ["bench.sleep", pytest.approx(0.03)]


def test_busy_averages_over_chips_that_ran():
    t = _trace()
    t["devices"].append([("scan", 0, 50 * MS)])
    t["devices"].append([])                   # a chip that ran nothing
    s = TR.summarize(t)
    assert s.busy_s == pytest.approx((0.035 + 0.05) / 2)


@pytest.mark.parametrize("event, name", [
    ("%maxsim_scores.1 = f32[384,16,64]{2,1,0:T(8,128)S(1)} custom-call("
     "f32[512,128]{1,0} %bitcast.27), custom_call_target=\"tpu_custom_call\"",
     "maxsim_scores.1"),
    ("%fusion.3 = (f32[16,256]{1,0}, s32[16,256]{1,0}) fusion(f32[16,24576]"
     "{1,0} %reshape.2), kind=kCustom, calls=%fused_computation.3",
     "fusion.3"),
    ("maxsim_rerank.1", "maxsim_rerank.1"),
])
def test_device_events_are_named_by_their_instruction(event, name):
    # a TPU's XLA Ops line names each event by the instruction's text
    assert TR.op_name(event) == name


def test_missing_window_is_an_error():
    with pytest.raises(ValueError):
        TR.summarize({"devices": [], "host": [("bench.pump", 0, 1)]})


def test_load_reads_host_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation(TR.WINDOW_SPAN):
        for _ in range(3):
            with TraceAnnotation("bench.pump"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = TR.load(tmp_path)
    names = [n for n, _, _ in t["host"]]
    assert names.count("bench.pump") == 3 and TR.WINDOW_SPAN in names
    assert t["devices"] == []                 # no TPU plane on this host
    s = TR.summarize(t)
    assert s.busy_s == 0 and s.window_s > 0


def test_an_op_on_every_chip_counts_one_dispatch():
    # one dispatch of a doc-sharded program runs its ops on every chip
    lanes = [[("scan", 10 * MS, 20 * MS), ("rerank", 40 * MS, 10 * MS)]
             for _ in range(4)]
    s = TR.summarize({"devices": lanes, "host": [("bench.window", 0,
                                                  100 * MS)]})
    assert s.chips == 4
    assert s.op_n == {"scan": 4, "rerank": 4}
    assert s.op_lanes == {"scan": 4, "rerank": 4}
    assert s.op_seconds(lambda n: n == "scan") == (pytest.approx(0.08), 1)
    assert s.busy_s == pytest.approx(0.03)
    assert s.chip_busy_s == pytest.approx(0.12)


def test_one_chip_counts_are_its_events():
    s = TR.summarize(_trace())
    assert s.chips == 1 and s.op_lanes == {"scan": 1, "fusion.1": 1,
                                           "rerank": 1}
    assert s.chip_busy_s == s.busy_s

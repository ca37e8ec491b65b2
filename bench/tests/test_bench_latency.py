"""The open-loop readers on a hand-made window: latencies from the
scheduled arrival, a failed request infinitely late, the generator's lag,
rows per dispatch from the frontend's counters."""
import types

import numpy as np
import pytest

from bench import harness, manifest


class _Handle:
    def __init__(self, t_done, error=None):
        self.t_done, self.error = t_done, error

    def done(self):
        return self.t_done is not None


def _run(reqs, mode="open", counters=None):
    requests = [types.SimpleNamespace(query=0, t_sched=s, t_sent=s + lag,
                                      handle=_Handle(d, e))
                for s, lag, d, e in reqs]
    cell = types.SimpleNamespace(traffic={"mode": mode})
    return harness.Run(cell, 1.0, 0.0, 10.0, requests,
                       counters or {"dispatches": 0, "rows_real": 0},
                       [], {}, {})


def test_latency_from_scheduled_arrival_and_failures_infinite():
    # 20 answered 10 ms after their schedule, however late they were sent
    reqs = [(i * 0.1, 0.004, i * 0.1 + 0.010, None) for i in range(20)]
    run = _run(reqs)
    assert manifest.metric_reader("p50_ms").read(run) == pytest.approx(10.0)
    assert manifest.metric_reader("p95_ms").read(run) == pytest.approx(10.0)
    reqs[3] = (0.3, 0.0, None, None)                # never answered
    reqs[7] = (0.7, 0.0, 0.71, RuntimeError("x"))   # errored
    lat = _run(reqs).latencies_ms()
    assert np.isinf(lat).sum() == 2
    assert manifest.metric_reader("p95_ms").read(_run(reqs)) == np.inf


@pytest.mark.parametrize("mode,expect", [("open", 4.0), ("closed", None)])
def test_generator_lag_only_for_open_loop(mode, expect):
    reqs = [(i * 0.1, 0.004, i * 0.1 + 0.010, None) for i in range(20)]
    lag = manifest.metric_reader("gen_lag_p95_ms").read(_run(reqs, mode))
    assert lag == (pytest.approx(expect) if expect else None)


def test_rows_per_dispatch_from_counters():
    reader = manifest.metric_reader("rows_per_dispatch")
    run = _run([], counters={"dispatches": 4, "rows_real": 30})
    assert reader.read(run) == pytest.approx(7.5)
    assert reader.read(_run([])) is None

"""A run whose timed path is broken underneath, with the look for a chip
skipped, comes out not correct: an answer altered where it is produced,
on the open-loop mix."""
from bench import control
from bench.tests import tiny


def test_altered_answer_is_caught():
    c = tiny.cell("colpali-24k.batch", traffic="poisson")
    out = tiny.run(c, 2**31 + 79, patch=control.alter_answers)
    assert out["attempted"] > 0
    assert not out["correct"]
    assert out["checks"]["score_err"]["value"] > \
        out["checks"]["score_err"]["limit"]
    assert set(out["metrics"]) == {"qps", "setup_s"}

"""``correct`` on a sound run, and the comparison's numbers on hand-made
answers."""
import numpy as np

from bench import reference
from bench.tests import tiny

SEED = 2**31 + 77


def test_sound_run_is_correct_and_reports_its_metrics():
    out = tiny.run(tiny.cell("colpali-24k.batch"), SEED)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"qps", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["score_err"]["value"] < 1e-6
    assert out["device"]["platform"] == "cpu"


def _ref(cand_scores, exact_cand, extra_exact, extra_pooled):
    order = np.argsort(-cand_scores[0], kind="stable")
    return {"pooled_top": np.concatenate(
                [cand_scores[:, order], [[-1.0]]], axis=1),
            "cand": order[None],
            "exact": np.concatenate([exact_cand[:, order], extra_exact],
                                    axis=1),
            "pooled_extra": extra_pooled}


def test_compare_reads_each_fault():
    # four candidates (pages 0-3) by pooled score, exact scores below
    pooled = np.array([[4.0, 3.0, 2.0, 1.0]])
    exact = np.array([[10.0, 9.0, 8.0, 7.0]])
    ok = reference.compare(
        _ref(pooled, exact, np.array([[10.0, 9.0]]), np.array([[4.0, 3.0]])),
        np.array([[10.0, 9.0]]), np.array([[0, 1]]), 4, 4, 1e-3)
    assert ok["score_err"] == 0 and ok["missed_gap"] == 0
    assert ok["prefetch_gap"] == 0 and ok["bad_ids"] == 0
    # the served score of page 1 is off by 0.09
    bad = reference.compare(
        _ref(pooled, exact, np.array([[10.0, 9.0]]), np.array([[4.0, 3.0]])),
        np.array([[10.0, 9.09]]), np.array([[0, 1]]), 4, 4, 1e-3)
    assert bad["score_err"] == np.float32(0.09) / 10 or \
        abs(bad["score_err"] - 0.009) < 1e-9
    # page 2 served in place of page 1: page 1 is missed
    miss = reference.compare(
        _ref(pooled, exact, np.array([[10.0, 8.0]]), np.array([[4.0, 2.0]])),
        np.array([[10.0, 8.0]]), np.array([[0, 2]]), 4, 4, 1e-3)
    assert abs(miss["missed_gap"] - 0.1) < 1e-9
    # an id out of range, a repeat, a rising pair
    odd = reference.compare(
        _ref(pooled, exact, np.array([[9.0, 9.0]]), np.array([[3.0, 3.0]])),
        np.array([[9.0, 9.5]]), np.array([[1, 1]]), 4, 4, 1e-3)
    assert odd["bad_ids"] == 1 and odd["misordered"] == 1


def test_routing_faults_count_this_runs_twins_only():
    from bench import harness
    fams = harness.KERNEL_FAMILIES
    zero = {f: {"pallas": 0, "jnp": 0, "ref": 0} for f in fams}
    # off the TPU the kernel path is the interpreted scan, the rerank's jnp
    # twin and the pooling operator; earlier 'ref' traces in the process
    # (other tests) are not this run's
    before = dict(zero, maxsim_scan={"pallas": 0, "jnp": 0, "ref": 5})
    after = {"maxsim_scan": {"pallas": 2, "jnp": 0, "ref": 5},
             "maxsim_rerank": {"pallas": 0, "jnp": 2, "ref": 0},
             "pooling": {"pallas": 0, "jnp": 3, "ref": 0}}
    assert harness._routing_faults(before, after) == 0
    twin = dict(after, maxsim_scan={"pallas": 2, "jnp": 0, "ref": 6})
    assert harness._routing_faults(before, twin) == 1
    assert harness._routing_faults(zero, zero) == len(fams)

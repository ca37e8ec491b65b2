"""The control: the program's own int8 path, one precision below the
configuration's bfloat16 store, has to come out not correct."""
from bench import control
from bench.tests import tiny


def test_int8_store_fails_the_comparison():
    out = tiny.run(tiny.cell("colpali-24k.batch"), 2**31 + 78,
                   quantize=control.INT8)
    assert not out["correct"]
    checks = out["checks"]
    assert checks["score_err"]["value"] > checks["score_err"]["limit"]

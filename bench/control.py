"""Readings that the limits of ``correct`` are set from, in one process.

    python3 bench/control.py --workload <cell> --seeds 1,2,... \\
        [--control-seeds 21,22,23] [--fault-seeds 31,32,33] --seconds <s>

For each ``--seeds`` seed, one run of the cell as the benchmark makes it
(a short window at the cell's own load, then the comparison with the
reference): the lower readings. For each ``--control-seeds`` seed the same
run with the program's own int8 path switched on for the pooled and the
full-resolution vectors (``IngestPipeline(quantize=...)``), the nearest
precision below the configuration's bfloat16 store: the upper readings.
For each ``--fault-seeds`` seed, a run whose frontend alters one answer of
every dispatch where it is produced. Every run prints its checks as one
JSON line; the last line gives, per number, the largest program reading
and the smallest control and fault readings.

Needs the TPU the cell runs on, like ``bench/run.py``; one process, so
the compiles are shared and set-up is paid once per seed.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

INT8 = ("initial", "mean_pooling")


def alter_answers(fe) -> None:
    """Break the timed path: every dispatch returns its first row's best
    page id shifted by one, as if the answer were corrupted where it is
    produced."""
    inner = fe._dispatch

    def dispatch(qp, qmp, rows, **kw):
        scores, ids, degraded = inner(qp, qmp, rows, **kw)
        ids = ids.copy()
        ids[0, 0] = (ids[0, 0] + 1) % fe.retriever.n_docs
        return scores, ids, degraded

    fe._dispatch = dispatch


def readings(cell, seeds, seconds, device_peaks, kind: str) -> list:
    from bench import harness
    out = []
    for seed in seeds:
        res = harness.run_cell(
            cell, seed, seconds, False, t_process=T_START,
            device_peaks=device_peaks,
            quantize=INT8 if kind == "control" else (),
            patch=alter_answers if kind == "fault" else None)
        line = {"kind": kind, "seed": seed, "correct": res["correct"],
                "checks": {k: c["value"] for k, c in res["checks"].items()},
                "metrics": {k: m["value"] for k, m in res["metrics"].items()}}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def summarize(lines: list) -> dict:
    """Per number: max over program runs, min over control/fault runs."""
    out = {}
    for kind, pick in (("program", max), ("control", min), ("fault", min)):
        runs = [ln["checks"] for ln in lines if ln["kind"] == kind]
        if runs:
            out[kind] = {k: pick(r[k] for r in runs) for k in runs[0]}
            out[kind]["runs"] = len(runs)
            out[kind]["correct"] = sum(ln["correct"] for ln in lines
                                       if ln["kind"] == kind)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import harness, manifest
    from repro.launch.runtime import setup_compile_cache
    cell = manifest.load_cell(args.workload)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        harness.log(f"control: needs a TPU, JAX found {devs[0].platform!r}")
        return 2
    device_peaks = harness.peaks(devs[0].device_kind)
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    def seeds(s):
        return [int(x) for x in s.split(",") if x]

    lines = []
    for kind, s in (("program", args.seeds), ("control", args.control_seeds),
                    ("fault", args.fault_seeds)):
        lines += readings(cell, seeds(s), args.seconds, device_peaks, kind)
    print(json.dumps({"summary": summarize(lines)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

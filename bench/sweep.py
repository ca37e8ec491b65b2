"""Find the rate an open-loop cell can sustain: one corpus, a ladder of
arrival rates, each a short window of the cell's own generator.

    python3 bench/sweep.py --workload <open-loop cell> --seed <n> \\
        --rates 300,500,700 --seconds <s>

For each rate it prints one JSON line: requests offered, how many were
answered inside the window, how long the queue took to drain after the
last arrival (a backlog that grows through the window shows as a drain
time that grows with the window), the median and 95th-percentile latency
from scheduled arrival, and rows per dispatch. The knee is the highest
rate whose answers keep pace with its arrivals; a cell's rate is fixed
below it, in its mix file, from such a sweep made once on the chip.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from bench import harness, manifest
    from repro.launch.runtime import setup_compile_cache
    cell = manifest.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        harness.log("sweep: needs a TPU")
        return 2
    setup_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    served = harness.Served(cell, args.seed)
    served.warm(cell.traffic["warm"])
    gen = manifest.generator(cell.traffic)
    fe = served.fe
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(cell.traffic, rate=rate)
        before = dict(fe.stats)
        requests, t0, t1 = gen.drive(fe, served.queries, served.lens, mix,
                                     args.seconds, args.seed)
        t_drained = time.perf_counter()
        lat = np.asarray([(r.handle.t_done - r.t_sched) * 1e3
                          for r in requests])
        in_window = sum(r.handle.t_done <= t1 for r in requests)
        n_disp = fe.stats["dispatches"] - before["dispatches"]
        rows = fe.stats["rows_real"] - before["rows_real"]
        print(json.dumps({
            "rate": rate, "offered": len(requests),
            "answered_in_window": int(in_window),
            "drain_s": t_drained - t1,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "rows_per_dispatch": rows / max(n_disp, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

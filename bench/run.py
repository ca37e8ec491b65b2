"""The chip benchmark of the served MaxSim cascade: one run of one cell.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Run from the root of a checkout, on a machine that holds the chips the
cell asks for. The cells, their configurations (``bench/configs``), traffic
mixes (``bench/traffic``) and metrics (``bench/metrics``) are named in
``BENCHMARK.json``. With ``--trace 0`` the last line of standard output
is a JSON object with the cell's end-to-end metrics; with ``--trace 1``
the window runs under the profiler and the object carries the per-layer
metrics, the device's busy time and a breakdown instead. The numbers
compared with the plain reference, each beside its limit, are the last
lines of standard error and the object's last key, ``checks``.

The run exits non-zero, and prints no result, when JAX finds no TPU,
fewer chips than the cell asks for, or a device kind without a row in
``bench/peaks.json``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def process_age() -> float:
    """Seconds since this process started, read from /proc: the time
    before the first line of this file ran counts as set-up too."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return max(uptime - start_ticks / os.sysconf("SC_CLK_TCK"), 0.0)
    except (OSError, ValueError, IndexError):
        return 0.0


def main(argv=None) -> int:
    t_process = T_START - (process_age() - (time.perf_counter() - T_START))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness, manifest
    cell = manifest.load_cell(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        harness.log(f"bench: needs a TPU, JAX found {devs[0].platform!r}")
        return 2
    if len(devs) < cell.chips:
        harness.log(f"bench: {cell.name} needs {cell.chips} chips, JAX "
                    f"found {len(devs)}")
        return 2
    try:
        device_peaks = harness.peaks(devs[0].device_kind)
    except LookupError as e:
        harness.log(f"bench: {e}")
        return 2

    from repro.launch.runtime import setup_compile_cache
    harness.log(f"compile cache: {setup_compile_cache()}")
    # every program from the cache after a cell's first run, however
    # quickly it compiled
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           t_process=t_process, device_peaks=device_peaks)
    for name, c in out["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

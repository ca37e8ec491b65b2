"""A cell of the real benchmark cut to a size the CPU test run holds:
512 pages, a 64-query pool, one cohort of closed-loop clients, 24
answers compared."""
import json
import time

from bench import manifest

PEAKS = {"bf16_flops": 197e12, "int8_ops": 393e12,
         "hbm_bytes_per_s": 819e9}


def cell(name: str, pages: int = 512, rate: float = 20.0,
         traffic: str = "") -> manifest.Cell:
    """Cell ``name`` cut down; ``traffic`` puts another mix file of
    ``bench/traffic`` in place of the cell's own."""
    c = manifest.load_cell(name)
    cfg = dict(c.config, pages=pages,
               check=dict(c.config["check"], sample=24))
    mix = c.traffic
    if traffic:
        with open(manifest.BENCH_DIR / "traffic" / f"{traffic}.json") as f:
            mix = dict(json.load(f), name=traffic)
    mix = dict(mix, queries=dict(mix["queries"], pool=64))
    if mix["mode"] == "open":
        mix["rate"] = rate
    else:
        mix["clients"] = cfg["frontend"]["max_batch"]    # one cohort
    return manifest.Cell(c.name, cfg, mix, c.chips, c.end_to_end,
                         c.per_layer)


def run(c: manifest.Cell, seed: int, seconds: float = 5.0, **kw) -> dict:
    from bench import harness
    return harness.run_cell(c, seed, seconds, False,
                            t_process=time.perf_counter(),
                            device_peaks=PEAKS, **kw)

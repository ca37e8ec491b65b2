"""``BENCHMARK.json`` and the files it names, found by name.

A cell is one entry of ``workloads``: a configuration (``configs/<name>``
via the manifest's ``file``) under a traffic mix (``traffic/<mix>.json``,
which names its generator module under ``traffic/``). A metric is read by
``metrics/<name>.py``, or by ``metrics/<base>.py`` for a name
``<base>.<suffix>``: the suffix only splits one quantity by the cells
that report it. Adding a cell, a mix or a metric therefore adds files and
entries; it edits none.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


@dataclass(frozen=True)
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: tuple        # metric entries this cell reports, untraced
    per_layer: tuple         # ... and traced


def load_manifest(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT, manifest: dict | None = None,
              bench_dir: Path = BENCH_DIR) -> Cell:
    """The cell ``name`` with its configuration and mix files read."""
    manifest = manifest or load_manifest(root)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in manifest["configs"]}[w["config"]]
    with open(root / conf["file"]) as f:
        config = json.load(f)
    config["name"] = conf["name"]
    with open(bench_dir / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)
    traffic["name"] = w["traffic"]
    return Cell(name, config, traffic, int(w["chips"]),
                tuple(m for m in manifest["end_to_end"] if _reports(m, name)),
                tuple(m for m in manifest["per_layer"] if _reports(m, name)))


def _load_file(path: Path, module_name: str):
    spec = importlib.util.spec_from_file_location(module_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The module whose ``read(run)`` gives metric ``name``."""
    for stem in (name, name.split(".")[0]):
        path = bench_dir / "metrics" / f"{stem}.py"
        if path.exists():
            return _load_file(path, f"bench_metric_{stem.replace('.', '_')}")
    raise FileNotFoundError(f"no reader for metric {name!r} under "
                            f"{bench_dir / 'metrics'}")


def generator(traffic: dict):
    """The traffic generator module a mix names (``traffic/<module>.py``)."""
    return importlib.import_module(f"bench.traffic.{traffic['generator']}")

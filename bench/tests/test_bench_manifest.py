"""BENCHMARK.json keeps to the benchmark's contract, and every cell,
configuration, mix and metric it names is found by name."""
import json
import re

import pytest

from bench import manifest

M = manifest.load_manifest()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in M["workloads"]]
METRICS = M["end_to_end"] + M["per_layer"]


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["paths"] == ["bench"] and M["command"][1] == "bench/run.py"
    assert 1 <= M["run_seconds"] <= 51
    # a full check with 24 cells fits the driver's 43,200 s
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200


def test_names_units_and_keys():
    names = [x["name"] for x in M["configs"] + M["workloads"] + METRICS]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in M["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in M["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert {m["name"] for m in M["end_to_end"]} >= {"setup_s"}


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_found_by_name(cell):
    c = manifest.load_cell(cell)
    assert c.chips == 1
    assert manifest.generator(c.traffic).drive
    assert c.config["pages"] % c.config["ingest_batch"] == 0
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.metric_reader(m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in e2e


def test_configs_used_and_reduced_keys_in_their_files():
    used = {w["config"] for w in M["workloads"]}
    for conf in M["configs"]:
        assert conf["name"] in used
        assert conf["file"].startswith("bench/")
        with open(manifest.ROOT / conf["file"]) as f:
            data = json.load(f)
        assert set(conf["reduced"]) == set(data["reduced"])
        assert all(k in data for k in conf["reduced"])


def test_unknown_cell_and_metric_are_errors():
    with pytest.raises(KeyError):
        manifest.load_cell("no-such-cell")
    with pytest.raises(FileNotFoundError):
        manifest.metric_reader("no_such_metric")


def test_peaks_table_has_no_fallback():
    from bench import harness
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    for kind in ("cpu", "TPU v4"):
        with pytest.raises(LookupError):
            harness.peaks(kind)

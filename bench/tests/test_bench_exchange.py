"""The four-chip cell and its exchange reader: the cell's files are found
by name, ``exchange_ms`` sums the device time of the ops under the
program's ``cascade.exchange`` scope wherever it nests, and the stage
readers bill those ops to their stage as they did before the scope."""
import types

import pytest

from bench import harness, manifest
from bench import program_spans as PS
from bench import trace_reduce as TR

MS = 1_000_000   # ns
CELL = "colpali-98k-4chip.batch"
exchange = manifest.metric_reader("exchange_ms.4chip")
scan_stage = manifest.metric_reader("scan_stage_ms.batch")
rerank_stage = manifest.metric_reader("rerank_stage_ms.batch")
BODY = "jit(searcher)/shmap_body"
SCAN, RERANK = f"{BODY}/cascade.scan", f"{BODY}/cascade.rerank"


def _trace(exchange_scope=True):
    """Window [0, 100) ms over three chips. Chips 0 and 1 each run a scan
    and a rerank with their exchanges nested under the stage's scope
    (chip 0: 2 + 1 ms after the scan, 1 + 1 after the rerank; chip 1:
    3 + 1 and 1 + 1), and one exchange op after the window; chip 2 runs
    nothing in the window. Without ``exchange_scope`` the same ops carry
    their stage's scope alone, as before the program had the scope."""
    ex = "/cascade.exchange" if exchange_scope else ""

    def chip(t, gather):
        return [("maxsim_scores.1", t + 10 * MS, 10 * MS,
                 f"{SCAN}/pallas_call:"),
                ("all-gather.1", t + 20 * MS, gather * MS,
                 f"{SCAN}{ex}/all_gather:"),
                ("fusion.3", t + 23 * MS, 1 * MS, f"{SCAN}{ex}/top_k:"),
                ("maxsim_rerank.1", t + 30 * MS, 10 * MS,
                 f"{RERANK}/pallas_call:"),
                ("all-gather.2", t + 40 * MS, 1 * MS,
                 f"{RERANK}{ex}/all_gather:"),
                ("fusion.7", t + 41 * MS, 1 * MS, f"{RERANK}{ex}/top_k:"),
                ("all-gather.1", 120 * MS, 5 * MS,
                 f"{SCAN}{ex}/all_gather:"),
                ("copy.3", t + 50 * MS, 1 * MS, None)]

    return {"devices": [chip(0, 2), chip(MS, 3),
                        [("fusion.9", 150 * MS, 1 * MS, None)]],
            "host": [(TR.WINDOW_SPAN, 0, 100 * MS)]}


def _reduced(trace):
    """``program_spans``' view of a trace: each op's first cascade
    scope."""
    return dict(trace, devices=[[(n, s, d, None if p is None
                                  else PS.scope_of(p))
                                 for n, s, d, p in ops]
                                for ops in trace["devices"]])


def _run(tmp_path, monkeypatch, trace, dispatches=2, traced=True):
    (tmp_path / CELL).mkdir(exist_ok=True)
    (tmp_path / CELL / "t.xplane.pb").write_bytes(b"")
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    monkeypatch.setattr(exchange, "parse", lambda path: trace)
    exchange._CACHE.clear()
    return types.SimpleNamespace(
        trace=object() if traced else None,
        cell=types.SimpleNamespace(name=CELL),
        counters={"dispatches": dispatches}, note=lambda msg: None)


def test_scope_name_is_the_programs():
    from repro.retrieval import tracing
    assert exchange.SCOPE_EXCHANGE == tracing.SCOPE_EXCHANGE
    assert exchange.IN_EXCHANGE.search(
        f"{SCAN}/{tracing.SCOPE_EXCHANGE}/all_gather:")
    assert not exchange.IN_EXCHANGE.search(f"{SCAN}/all_gather:")
    assert PS.scope_of(f"{RERANK}/{tracing.SCOPE_EXCHANGE}/top_k:") \
        == PS.SCOPE_RERANK


def test_exchange_seconds_average_over_the_chips_that_ran():
    # chip 0: 2 + 1 + 1 + 1 = 5 ms, chip 1: 3 + 1 + 1 + 1 = 6 ms inside
    # the window; the op after the window and the idle chip 2 count not
    assert exchange.exchange_seconds(_trace()) == pytest.approx(0.0055)


def test_exchange_ms_reads_per_dispatch(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _trace(), dispatches=2)
    assert exchange.read(run) == pytest.approx(2.75)


def test_exchange_ms_reads_nothing_without_the_scope(tmp_path, monkeypatch):
    run = _run(tmp_path, monkeypatch, _trace(exchange_scope=False))
    assert exchange.read(run) is None
    run = _run(tmp_path, monkeypatch, _trace(), traced=False)
    assert exchange.read(run) is None
    run = _run(tmp_path, monkeypatch, _trace(), dispatches=0)
    assert exchange.read(run) is None


@pytest.mark.parametrize("reader,ms", [(scan_stage, 6.75),
                                       (rerank_stage, 6.0)])
def test_stage_readers_keep_the_exchange_in_their_stage(monkeypatch, reader,
                                                        ms):
    # per chip: scan 10 + gather + 1 ms (13 and 14), rerank 10 + 1 + 1
    readings = []
    for scoped in (True, False):
        sp = PS.summarize(_reduced(_trace(exchange_scope=scoped)))
        monkeypatch.setattr(PS, "read", lambda run, sp=sp: sp)
        readings.append(reader.read(types.SimpleNamespace(
            trace=object(), counters={"dispatches": 2})))
    assert readings == [pytest.approx(ms), pytest.approx(ms)]


def test_four_chip_cell_files_found_by_name():
    c = manifest.load_cell(CELL)
    assert c.chips == 4 and c.config["name"] == "colpali-98k"
    assert manifest.generator(c.traffic).drive
    cfg = c.config
    assert cfg["pages"] % (c.chips * cfg["ingest_batch"]) == 0
    # each chip holds the one-chip colpali cell's pages and topics
    one = manifest.load_cell("colpali-24k.batch").config
    assert cfg["pages"] // c.chips == one["pages"]
    assert cfg["pages"] // cfg["topics"] == one["pages"] // one["topics"]
    for key in ("geometry", "cascade", "frontend", "check", "store_dtype",
                "ingest_batch", "repro_config"):
        assert cfg[key] == one[key], key
    e2e = {m["name"] for m in c.end_to_end}
    assert e2e == {"qps", "setup_s"}
    assert {m["name"] for m in c.per_layer} == {
        f"{n}.4chip" for n in ("idle_share", "scan_roofline",
                               "rerank_roofline", "cascade_roofline",
                               "exchange_ms")}
    for m in c.end_to_end + c.per_layer:
        assert callable(manifest.metric_reader(m["name"]).read)
    for m in c.per_layer:
        assert m["moves"] in e2e

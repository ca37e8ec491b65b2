"""bench.program_spans and its readers on small span and op lists: the
frontend's spans take the device gaps they cover from bench.pump, cascade
scopes sum their ops' device time, and every reader divides by the
window's dispatches and reads nothing where the program wrote nothing."""
import types

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import TraceAnnotation

from bench import harness, manifest
from bench import program_spans as PS
from bench import trace_reduce as TR

MS = 1_000_000   # ns
launch_idle = manifest.metric_reader("launch_idle_ms.batch")
return_idle = manifest.metric_reader("return_idle_ms.batch")
scan_stage = manifest.metric_reader("scan_stage_ms.batch")
rerank_stage = manifest.metric_reader("rerank_stage_ms.batch")
queue_wait = manifest.metric_reader("queue_wait_p95_ms.poisson")
IDLE_READERS = [launch_idle, return_idle]
STAGE_READERS = [scan_stage, rerank_stage]


def _trace():
    # window [0, 100) ms: one pump [0, 60) holding a flush [5, 55) with
    # pad [5, 8), launch [8, 12), sync [12, 50), translate [50, 54);
    # device ops: mask [10, 11), scan [11, 40), an unscoped copy [40, 41),
    # rerank [41, 45); then an untouched op [70, 80)
    dev = [("fusion.1", 10 * MS, 1 * MS, PS.SCOPE_MASK),
           ("maxsim_scores.1", 11 * MS, 29 * MS, PS.SCOPE_SCAN),
           ("copy.18", 40 * MS, 1 * MS, None),
           ("maxsim_rerank.1", 41 * MS, 4 * MS, PS.SCOPE_RERANK),
           ("fusion.9", 70 * MS, 10 * MS, None)]
    host = [(TR.WINDOW_SPAN, 0, 100 * MS),
            ("bench.pump", 0, 60 * MS),
            (PS.FLUSH, 5 * MS, 50 * MS),
            (PS.PAD, 5 * MS, 3 * MS),
            (PS.LAUNCH, 8 * MS, 4 * MS),
            (PS.SYNC, 12 * MS, 38 * MS),
            (PS.TRANSLATE, 50 * MS, 4 * MS)]
    return {"devices": [dev], "host": host}


def _run(dispatches=2, traced=True):
    return types.SimpleNamespace(trace=object() if traced else None,
                                 counters={"dispatches": dispatches},
                                 note=lambda msg: None)


@pytest.fixture()
def spans(monkeypatch):
    sp = PS.summarize(_trace())
    monkeypatch.setattr(PS, "read",
                        lambda run: sp if run.trace is not None else None)
    return sp


def test_span_names_are_the_programs():
    from repro.retrieval import tracing
    assert (PS.FLUSH, PS.PAD, PS.LAUNCH, PS.SYNC, PS.TRANSLATE) == (
        tracing.FLUSH, tracing.PAD, tracing.LAUNCH, tracing.SYNC,
        tracing.TRANSLATE)
    assert (PS.SCOPE_MASK, PS.SCOPE_SCAN, PS.SCOPE_RERANK) == (
        tracing.SCOPE_MASK, tracing.SCOPE_SCAN, tracing.SCOPE_RERANK)
    assert all(n.startswith(PS.FRONTEND_PREFIX)
               for n in (tracing.FLUSH, tracing.PAD, tracing.LAUNCH,
                         tracing.SYNC, tracing.TRANSLATE))


def test_gaps_billed_to_the_innermost_program_span(spans):
    # gaps: [0, 5) pump; [5, 8) pad; [8, 10) launch; [45, 50) sync;
    # [50, 54) translate; [54, 55) flush's own; [55, 60) pump; [60, 70)
    # and [80, 100) no span
    assert spans.idle_s == pytest.approx({
        "bench.pump": 0.010, PS.PAD: 0.003, PS.LAUNCH: 0.002,
        PS.SYNC: 0.005, PS.TRANSLATE: 0.004, PS.FLUSH: 0.001,
        TR.NO_SPAN: 0.030})
    # the split adds up to what trace_reduce, which keeps bench spans
    # alone, bills to bench.pump
    t = _trace()
    t["devices"] = [[op[:3] for op in ops] for ops in t["devices"]]
    t["host"] = [h for h in t["host"] if h[0].startswith(TR.HOST_PREFIX)]
    pump = TR.summarize(t).idle_s["bench.pump"]
    assert sum(v for k, v in spans.idle_s.items() if k != TR.NO_SPAN) \
        == pytest.approx(pump)
    assert spans.flushes == 1


def test_scope_time_sums_the_ops_under_each_scope(spans):
    assert spans.scope_s == pytest.approx({PS.SCOPE_MASK: 0.001,
                                           PS.SCOPE_SCAN: 0.029,
                                           PS.SCOPE_RERANK: 0.004})


@pytest.mark.parametrize("reader, total_ms", [
    (launch_idle, 5.0), (return_idle, 9.0),
    (scan_stage, 29.0), (rerank_stage, 4.0)])
def test_readers_divide_by_dispatches(spans, reader, total_ms):
    assert reader.read(_run(dispatches=2)) == \
        pytest.approx(total_ms / 2)


@pytest.mark.parametrize("reader", IDLE_READERS + STAGE_READERS)
def test_readers_read_nothing_untraced_or_without_dispatches(spans,
                                                             reader):
    assert reader.read(_run(traced=False)) is None
    assert reader.read(_run(dispatches=0)) is None


@pytest.mark.parametrize("reader", IDLE_READERS + STAGE_READERS)
def test_readers_read_nothing_from_a_program_without_spans(monkeypatch,
                                                           reader):
    # a program without the frontend's spans and the cascade's scopes:
    # bench spans and unscoped ops only
    t = _trace()
    t["host"] = [h for h in t["host"] if not h[0].startswith("frontend.")]
    t["devices"] = [[(n, s, d, None) for n, s, d, _ in t["devices"][0]]]
    sp = PS.summarize(t)
    monkeypatch.setattr(PS, "read", lambda run: sp)
    assert reader.read(_run()) is None


@pytest.mark.parametrize("path, scope", [
    ("jit(local_body)/cascade.scan/jit(maxsim_scores)/pallas_call:",
     PS.SCOPE_SCAN),
    ("jit(searcher)/cascade.rerank/top_k:", PS.SCOPE_RERANK),
    ("jit(local_body)/cascade.mask/and:", PS.SCOPE_MASK),
    ("jit(local_body)/cascade.scanner/dot:", None),
    ("stores[0]['mean_pooling']:", None)])
def test_scope_read_from_the_op_path(path, scope):
    assert PS.scope_of(path) == scope


# a TPU plane as the profiler writes it: the op's scope path is a stat of
# the event's metadata, not of the event
TPU_PLANE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 2000000 duration_ps: 5000000 }
    events { metadata_id: 2 offset_ps: 8000000 duration_ps: 1000000 }
    events { metadata_id: 1 offset_ps: 9000000 duration_ps: 5000000 } }
  event_metadata { key: 1 value { id: 1
    name: "%maxsim_scores.1 = f32[384,16,64] custom-call(%copy.18)"
    stats { metadata_id: 7
            str_value: "jit(local_body)/cascade.scan/pallas_call:" } } }
  event_metadata { key: 2 value { id: 2
    name: "%copy.18 = bf16[24576,34,128] copy(%stores_0)"
    stats { metadata_id: 7 str_value: "stores[0]['mean_pooling']:" } } }
  stat_metadata { key: 7 value { id: 7 name: "tf_op" } }
}
"""


def test_device_ops_carry_the_scope_of_their_metadata(tmp_path):
    from jax.profiler import ProfileData
    (tmp_path / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(TPU_PLANE))
    t = PS.load(tmp_path)
    assert t["devices"] == [[
        ("maxsim_scores.1", 3000.0, 5000.0, PS.SCOPE_SCAN),
        ("copy.18", 9000.0, 1000.0, None),
        ("maxsim_scores.1", 10000.0, 5000.0, PS.SCOPE_SCAN)]]
    # ProfileData reads the same times and names, without the scope
    pd = TR.load(tmp_path)["devices"]
    assert pd == [[op[:3] for op in t["devices"][0]]]


def _handle(t_dispatch):
    return types.SimpleNamespace(t_dispatch=t_dispatch)


def test_queue_wait_from_the_dispatch_stamp():
    reqs = [types.SimpleNamespace(t_sched=float(i),
                                  handle=_handle(i + (i + 1) * 1e-3))
            for i in range(20)]
    reqs.append(types.SimpleNamespace(t_sched=0.0, handle=_handle(None)))
    run = types.SimpleNamespace(requests=reqs)
    assert queue_wait.read(run) == pytest.approx(19.0)   # rank 19 of 20
    # a program that stamps nothing: handles without the attribute
    run.requests = [types.SimpleNamespace(t_sched=0.0,
                                          handle=types.SimpleNamespace())]
    assert queue_wait.read(run) is None


def test_read_parses_a_recorded_trace_once(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "TRACE_DIR", tmp_path)
    cell = types.SimpleNamespace(name="cell")
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path / "cell"))
    with TraceAnnotation(TR.WINDOW_SPAN):
        for i in range(3):
            with TraceAnnotation("bench.pump"), \
                    TraceAnnotation(PS.FLUSH, dispatch=i + 1), \
                    TraceAnnotation(PS.SYNC):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    names = [n for n, _, _ in PS.load(tmp_path / "cell")["host"]]
    assert names.count(PS.FLUSH) == 3 and names.count("bench.pump") == 3
    run = types.SimpleNamespace(trace=object(), cell=cell)
    sp = PS.read(run)
    assert sp.flushes == 3 and sp.idle_s == {}   # no TPU plane on a CPU
    assert PS.read(run) is sp                    # parsed once
    run.trace = None
    assert PS.read(run) is None

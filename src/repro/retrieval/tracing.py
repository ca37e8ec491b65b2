"""Trace-count hook for the no-retrace contract, and the names of the
serving path's profiler spans.

Every repro-owned jitted function on the serving mutation/search/ingest
path calls ``record_trace()`` from inside its traced body. The call is a
Python side effect, so it fires exactly once per trace (never per
execution) — and a jit retraces per DISTINCT ARGUMENT SHAPE, so the
counter covers ALL THREE axes of the contract:

- **corpus-shape retraces** — a mutation that changes segment layout
  (new-segment allocation, ``compact()``) forces a retrace; steady-state
  upsert/delete into preallocated padding must not.
- **query-shape retraces** — a search with a new ``(B, Q)`` query shape
  forces a retrace of the same cascade body; bucketed traffic through
  ``repro.retrieval.frontend.ServingFrontend`` must not (after each
  bucket's one warm-up trace).
- **ingest-shape retraces** — the device-resident
  ``repro.retrieval.ingest.IngestPipeline`` pads batches into power-of-two
  ingest buckets; after each bucket's one warm-up trace, mixed batch
  sizes must index + write as pure dispatch.

After warm-up, a steady-state upsert/delete/search/traffic/ingest sequence
must leave the counter unchanged. Tests, ``benchmarks/run.py
dynamic_corpus``, ``serving_tail_latency`` and ``ingest_throughput``
assert ``trace_count()`` deltas == 0 (the latter two fail CI on a nonzero
steady-state count).

Thread-safety contract: callers may drive warmed executables from
multiple threads (the frontend's flush path), and JAX may trace bodies
concurrently; every mutation of the counter/log below holds ``_LOCK``,
so ``record_trace()`` is safe to call from any thread and
``trace_count()`` deltas observed around a quiesced region are exact.
``no_retrace()`` itself is a per-thread assertion idiom — run traffic
inside it, not concurrent warm-ups.

The static counterpart to this runtime counter is the contract auditor
(``python -m repro.analysis --check``): its R1 rule proves every serving
jit body actually calls ``record_trace()``, so a forgotten hook can't
make this counter silently blind.

Profiler spans. The serving path marks its own work on the profiler's
clock, the clock the device trace uses, so a trace taken with
``jax.profiler.trace`` can say which host step each device gap waits
for and which cascade stage owns each device op. The names are the
constants below, defined here once:

- ``frontend.flush`` (``FLUSH``): one cohort's dispatch through
  ``ServingFrontend``, from stamping its members to scattering their
  answers (a ``flush``, or one direct ``search``). It carries the
  dispatch's sequence number, ``stats["dispatches"]`` after the
  increment, as the argument ``dispatch``; the same number is stamped
  on each member's ``PendingResult.dispatch``. The four spans below
  nest inside it, in this order:
- ``frontend.pad`` (``PAD``): padding the cohort into its bucket block
  in NumPy;
- ``frontend.launch`` (``LAUNCH``): the host-to-device copies of the
  block and the filter triple, and the call into the compiled cascade
  up to its return (the call is asynchronous);
- ``frontend.sync`` (``SYNC``): the blocking fetch of scores and slot
  ids to the host, which waits for the device to finish;
- ``frontend.translate`` (``TRANSLATE``): slot ids to page ids and the
  masking of filler ids (on the tiered engine's path the engine
  translates inside ``frontend.launch``, and this span is absent).

Device ops carry the cascade stage that emitted them as a
``jax.named_scope`` in their op metadata (``cascade.mask`` for the
effective-validity masks, ``cascade.scan`` for stage 0 with its merge,
``cascade.rerank`` for every later stage with its top-k and take). On a
doc-sharded store the cross-chip exchange of a stage, the all-gathers
of each shard's (score, id) lists and the merge of what they gather,
also carries ``cascade.exchange`` (``SCOPE_EXCHANGE``), nested inside
its stage's scope. A scope changes op metadata only: no computation,
fusion or kernel name.

With no profiler running a span costs one inactive ``TraceMe``; no
string is built on the hot path.
"""
from __future__ import annotations

import sys
import threading
from contextlib import contextmanager

_LOCK = threading.Lock()
_TRACES = [0]
_TRACE_LOG: list = []        # qualified name per record_trace() call
_TRACE_LOG_MAX = 256         # bound the log; the count stays exact

# host spans of ServingFrontend, one set per dispatch (module docstring)
FLUSH = "frontend.flush"
PAD = "frontend.pad"
LAUNCH = "frontend.launch"
SYNC = "frontend.sync"
TRANSLATE = "frontend.translate"
# named scopes of the compiled cascade's stages
SCOPE_MASK = "cascade.mask"
SCOPE_SCAN = "cascade.scan"
SCOPE_RERANK = "cascade.rerank"
SCOPE_EXCHANGE = "cascade.exchange"


def record_trace(name: str | None = None) -> None:
    """Call from inside a traced function body (trace-time side effect).

    Records the caller's qualified name (module.function, derived from
    the calling frame when ``name`` is not given) alongside the count,
    so ``no_retrace()`` can say WHICH jit retraced, not only that one
    did."""
    if name is None:
        f = sys._getframe(1)
        name = f"{f.f_globals.get('__name__', '?')}.{f.f_code.co_name}"
    with _LOCK:
        _TRACES[0] += 1
        if len(_TRACE_LOG) < _TRACE_LOG_MAX:
            _TRACE_LOG.append(name)


def trace_count() -> int:
    with _LOCK:
        return _TRACES[0]


def traced_names(since: int = 0) -> tuple:
    """Qualified names recorded by ``record_trace()`` calls ``since`` a
    prior ``trace_count()`` snapshot (log entries past the bound are
    summarised by the callers as unattributed)."""
    with _LOCK:
        return tuple(_TRACE_LOG[since:])


def reset_trace_count() -> None:
    with _LOCK:
        _TRACES[0] = 0
        _TRACE_LOG.clear()


@contextmanager
def no_retrace(what: str = "steady state"):
    """Assert that zero serving jits are traced inside the block.

    The acceptance-test idiom for the no-retrace contract::

        frontend.warm()
        with tracing.no_retrace("ragged traffic"):
            for q, qm in traffic:
                frontend.search(q, qm)

    On failure the assertion names the jit bodies that retraced (their
    ``record_trace()`` call sites), so the report is actionable without
    re-running under a tracer.
    """
    before = trace_count()
    yield
    after = trace_count()
    delta = after - before
    if delta != 0:
        names = traced_names(since=before)
        unattributed = delta - len(names)
        who = ", ".join(sorted(set(names))) or "<log saturated>"
        if unattributed > 0 and names:
            who += f" (+{unattributed} past the log bound)"
        raise AssertionError(
            f"{what}: {delta} retrace(s) of serving jits — the "
            f"no-retrace contract is broken (retraced: {who})")

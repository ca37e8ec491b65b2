"""THE kernel dispatch registry: one policy surface for every op family.

Before this module, three copy-pasted resolve mechanisms decided where an
op family executes (``kernels/pooling/ops.resolve_impl``, the engine's
``_resolve_impl``/``_resolve_rerank_impl`` pair backed by
``kernels/maxsim/ops.resolve_rerank_impl``), and ``embed_bag`` carried a
fourth ad-hoc ``impl ==`` switch with no availability probe or counter at
all. Each re-implemented the same three decisions:

- **availability** — can the Pallas impl actually execute on this
  host/backend? Probed once (cached): on TPU by compiling the served
  widths, elsewhere by running a tiny interpreted instance.
- **routing** — Pallas natively on TPU (or an error: never a silent
  twin); off-TPU either the interpreted kernel (ops whose interpret mode
  is a validated serving path) or a fallback impl (the fused jnp twin, or
  the reference).
- **observability** — trace-time dispatch counters, the OBSERVED-routing
  signal CI gates assert on (a config-derived flag could not catch a
  silent fallback).

This registry owns all three. An op family registers a ``KernelOp`` record
(name -> probe + routing policy + which impls count as "kernel-path"), its
public wrappers call ``record(name, impl)`` at trace time, and every
consumer — the search-engine build, the ingest pipeline, benchmarks, CI
gates — resolves through ``resolve(name, use_kernel)``. Adding a fifth op
family is one ``register`` call, not a fourth mechanism.

Registered families (see each ops module): ``maxsim_scan``,
``maxsim_rerank``, ``ivf_route``, ``pooling``, ``embed_bag``.

Layering: this module imports nothing from the op packages — each ops
module imports ``dispatch`` and registers itself at import time.
``_ensure_registered`` lazily imports the known families so registry-level
consumers (benchmarks, tests) see the full table without importing every
ops module themselves.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable

import jax


def default_interpret() -> bool:
    """Pallas compiles natively on TPU; everywhere else it interprets."""
    return jax.default_backend() != "tpu"


@dataclass(frozen=True)
class KernelOp:
    """One op family's dispatch policy.

    probe         on TPU compiles the Pallas impl at the widths it serves
                  (``compile_served``), elsewhere runs a tiny interpreted
                  instance; its success defines ``available(name)`` (run
                  at most once).
    fallback      impl name served off-TPU when the interpreted kernel is
                  not a serving path: the fused jnp twin ("jnp") or the
                  reference ("ref"). Never served on TPU.
    interpret_ok  True when interpreted Pallas is a sanctioned serving
                  path off-TPU (the scan kernel's contract); False means
                  interpret mode is a correctness tool only and off-TPU
                  traffic routes to ``fallback``.
    kernel_impls  impl names that count as "routed through the fused/
                  kernel path" for ``kernel_dispatch_count`` — the CI
                  gates' observed-routing signal.
    """
    name: str
    probe: Callable[[], bool]
    fallback: str = "ref"
    interpret_ok: bool = False
    kernel_impls: frozenset = field(
        default_factory=lambda: frozenset({"pallas", "jnp"}))


_REGISTRY: dict = {}
_AVAILABLE: dict = {}            # name -> cached probe result
_COUNTS: dict = {}               # name -> {impl: trace-time dispatches}
_DISCOVERED: list = []           # registration modules found on disk


def register(op: KernelOp) -> KernelOp:
    """Add (or idempotently re-add) an op family to the registry."""
    _REGISTRY[op.name] = op
    _COUNTS.setdefault(op.name, {})
    return op


def registration_modules() -> tuple:
    """Discover the registration modules instead of hand-maintaining a
    tuple: every ``repro.kernels.<family>`` subpackage with an ``ops``
    module registers its families at import time. A new op family is a
    new subpackage — nothing to edit here, and the R2 contract lint
    (``repro.analysis``) rejects ``register()`` calls that live outside
    this pattern and so could never be discovered."""
    if not _DISCOVERED:
        import importlib.util
        import pkgutil
        import repro.kernels as _pkg
        for m in pkgutil.iter_modules(_pkg.__path__):
            if not m.ispkg:
                continue
            name = f"{_pkg.__name__}.{m.name}.ops"
            if importlib.util.find_spec(name) is not None:
                _DISCOVERED.append(name)
        _DISCOVERED.sort()
    return tuple(_DISCOVERED)


def _ensure_registered(name: str | None = None) -> None:
    if name is not None and name in _REGISTRY:
        return
    import importlib
    for mod in registration_modules():
        # a registration module that fails to import must fail LOUDLY:
        # swallowing it would silently shrink the registry and every
        # downstream resolve() would route around the missing family
        importlib.import_module(mod)


def get(name: str) -> KernelOp:
    _ensure_registered(name)
    return _REGISTRY[name]


def op_names() -> tuple:
    """Every registered op family, sorted."""
    _ensure_registered()
    return tuple(sorted(_REGISTRY))


def compile_abstract(fn: Callable, shapes, sharding=None) -> str:
    """AOT-compile ``fn`` at abstract ``shapes`` ([(shape, dtype), ...])
    for ``sharding``'s device (the default device when None) and return
    the compiled program's text. Nothing is allocated or run, so a probe
    can compile corpus-sized instances."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def compile_served(instances: dict, family: str) -> bool:
    """The TPU probe: compile every served instance of ``family`` from an
    ops module's ``served_instances()`` ({case: (family, fn, shapes)}).
    A kernel the compiler refuses raises here, so ``resolve`` raises
    before a search fn is built around it."""
    for fam, fn, shapes in instances.values():
        if fam == family:
            compile_abstract(fn, shapes)
    return True


def available(name: str) -> bool:
    """Whether ``name``'s Pallas impl executes on this host/backend.

    The probe runs at most once (cached) and its dispatches are NOT
    counted: probes trace the public wrappers, and an availability check
    must never satisfy a CI gate's "the cascade really routed through the
    kernel path" signal. The snapshot/restore lives HERE so every family
    gets that guarantee, not just the ones that remembered to implement
    it."""
    if name not in _AVAILABLE:
        op = get(name)
        snapshot = dict(_COUNTS.get(name, {}))
        try:
            _AVAILABLE[name] = bool(op.probe())
        finally:
            _COUNTS[name] = snapshot
    return _AVAILABLE[name]


def resolve(name: str, use_kernel: bool) -> tuple:
    """Pick ``(impl, interpret)`` for an op family once, at build time.

    use_kernel=False is always the reference path. Otherwise, on TPU: the
    native Pallas kernel, or ``RuntimeError`` when its probe fails — a
    chip run never serves a twin in place of the kernel it asked for.
    Off-TPU: the interpreted kernel for families whose interpret mode is
    a sanctioned serving path (``interpret_ok``), the family's
    ``fallback`` impl for the rest."""
    op = get(name)
    if not use_kernel:
        return "ref", True
    if not default_interpret():
        if not available(name):
            raise RuntimeError(
                f"{name}: the Pallas kernel's probe failed on TPU; "
                "refusing to fall back to the "
                f"{op.fallback!r} impl")
        return "pallas", False
    if available(name) and op.interpret_ok:
        return "pallas", True
    return op.fallback, True


def record(name: str, impl: str) -> None:
    """Trace-time dispatch hook: every op wrapper calls this inside its
    traced body, so counts measure TRACES THAT ROUTED to ``impl`` — the
    observational signal behind the CI routing gates."""
    counts = _COUNTS.setdefault(name, {})
    counts[impl] = counts.get(impl, 0) + 1


def reset_counts(name: str | None = None) -> None:
    """Zero the trace-time dispatch counters (one family, or all).

    ``benchmarks/run.py`` calls this between benchmark functions so a
    counter bumped by one suite can never satisfy another suite's
    observed-routing gate. Only the counters reset — the registry and
    the cached availability probes are unaffected."""
    if name is None:
        for counts in _COUNTS.values():
            counts.clear()
    else:
        _COUNTS.get(name, {}).clear()


def dispatch_count(name: str, impl: str | None = None) -> int:
    """Recorded trace-time dispatches for one impl (or all, impl=None)."""
    counts = _COUNTS.get(name, {})
    if impl is not None:
        return counts.get(impl, 0)
    return sum(counts.values())


def kernel_dispatch_count(name: str) -> int:
    """Dispatches that routed through the family's kernel/fused impls
    (``KernelOp.kernel_impls``) — what the benchmark CI gates diff."""
    op = get(name)
    counts = _COUNTS.get(name, {})
    return sum(c for i, c in counts.items() if i in op.kernel_impls)

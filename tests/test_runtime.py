"""Entry-point start-up and failure reporting: the compile-cache helper,
``chip_smoke.py`` off the chip, and ``serve.py``'s exit status when
requests error."""
import argparse
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.launch import runtime

REPO = Path(__file__).resolve().parents[1]


def test_compile_cache_honours_env(monkeypatch):
    calls = []
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(runtime.CACHE_ENV, "/elsewhere/cache")
    assert runtime.setup_compile_cache() == "/elsewhere/cache"
    assert calls == []                    # JAX reads the variable itself


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch):
    calls = []
    monkeypatch.setattr(runtime.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.delenv(runtime.CACHE_ENV, raising=False)
    first = runtime.setup_compile_cache()
    assert runtime.setup_compile_cache() == first
    assert Path(first) == REPO / ".jax_cache"
    assert calls == [("jax_compilation_cache_dir", first)] * 2
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_device_line_names_platform_kind_count():
    info = runtime.device_info()
    assert info == {"platform": jax.devices()[0].platform,
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
    assert runtime.device_line() == (
        f"device: platform={info['platform']} kind={info['kind']} "
        f"count={info['count']}")


def _run_smoke(script: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)


def test_chip_smoke_fails_without_tpu():
    out = _run_smoke(REPO / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "needs a TPU" in out.stderr


def test_chip_smoke_fails_alone(tmp_path):
    shutil.copy(REPO / "chip_smoke.py", tmp_path)
    out = _run_smoke(tmp_path / "chip_smoke.py")
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


def test_serve_traffic_counts_request_errors(monkeypatch):
    """Every micro-batch dispatch fails: _run_traffic reports each
    request as errored, and the launcher's exit status is non-zero unless
    a fault plan was armed on purpose."""
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.launch import serve
    from repro.retrieval.frontend import ServingFrontend
    from repro.retrieval.store import build_store

    cfg = get_config("colpali")
    bench = make_benchmark(cfg, (8, 8, 8), (2, 2, 2), seed=3)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))

    def broken(self, *a, **kw):
        raise RuntimeError("injected dispatch failure")

    monkeypatch.setattr(ServingFrontend, "_run_block", broken)
    args = argparse.Namespace(
        tenants=0, chunk=0, n_clusters=0, max_batch=2, flush_ms=1.0,
        result_cache=0, tenant_quota=0, deadline_ms=0.0,
        arrival_rate=2000.0, traffic=6, fault_plan="")
    errors = serve._run_traffic(args, cfg, bench, store,
                                MST.two_stage(8, 4), False)
    assert errors == 6
    assert serve._exit_status(args, errors) == 1
    assert serve._exit_status(args, 0) == 0
    args.fault_plan = "transfer_fail_rate=0.5,seed=1"
    assert serve._exit_status(args, errors) == 0


class _Device:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind", ["TPU v5 lite", "TPU v4", "cpu"])
def test_roofline_peaks_keyed_by_device_kind(monkeypatch, kind):
    """The v5e row carries its published peaks; any other device (the CPU
    included) raises instead of borrowing v5e's or timing the host."""
    sys.path.insert(0, str(REPO / "benchmarks"))
    try:
        import roofline
    finally:
        sys.path.remove(str(REPO / "benchmarks"))
    monkeypatch.setattr(jax, "devices", lambda: [_Device(kind)])
    if kind == "TPU v5 lite":
        peaks = roofline.measured_peaks()
        assert (peaks["flops"], peaks["int8_ops"], peaks["hbm_bw"]) == (
            197e12, 393e12, 819e9)
        assert peaks["source"]
    else:
        with pytest.raises(roofline.UnknownDeviceError):
            roofline.measured_peaks()

"""Roofline analysis over the dry-run artifacts (§Roofline deliverable).

Reads benchmarks/results/dryrun_single.json (written by launch/dryrun.py on
the 16x16 production mesh) and derives, per (arch x shape):

    compute term    = HLO_FLOPs_per_device / peak_FLOPs         [s]
    memory term     = HLO_bytes_per_device / HBM_bw             [s]
    collective term = collective_bytes_per_device / link_bw     [s]

(cost_analysis / HLO shapes on the partitioned module are per-device, so
dividing the per-device quantity by per-chip peaks equals the global/chips
formula.) Also reports MODEL_FLOPS / HLO_FLOPs (useful-compute fraction:
for train cells MODEL_FLOPS = 3 x 2ND (fwd+bwd); remat recompute, MoE
dense-expert waste and redundant collectives all push the compiled FLOPs
above the model's).

Also hosts the CANDIDATE-PATH analytic roofline: per-stage HBM byte bills
from ``repro.core.multistage.cascade_hbm_bytes`` (corpus read, the [B, N]
score write, the 3x-billed naive rerank gather) combined with the Eq.-1
madds into predicted two-term roofline seconds for the reference vs fused
(scan_topk + rerank_kernel) serving cascade — against the published
peaks of the device the benchmark runs on (``measured_peaks``, keyed by
``device_kind``; a device without a row raises).
``benchmarks/run.py rerank_kernel_vs_ref`` prints this predicted ratio
next to the measured one.

The TIERED roofline (``tiered_overlap_roofline`` + ``measured_h2d_bw``)
extends the same discipline across the host boundary: cold-segment
host -> device bytes (the ``tier-transfer`` entry of
``cascade_hbm_bytes``) are billed at the measured ``device_put``
bandwidth, predicting the synchronous-fetch cost (scan + transfer,
exposed) vs the prefetch-overlapped cost (max of the two, hidden);
``benchmarks/run.py tiered_qps`` prints predicted vs measured for its
budget x hit-rate ladder.

Usage: PYTHONPATH=src python -m benchmarks.roofline [--json PATH] [--md]
       PYTHONPATH=src python -m benchmarks.roofline --candidate-path \\
           [--n-docs 1000000] [--batch 16] [--prefetch-k 256] [--top-k 100]
"""
from __future__ import annotations

import argparse
import json
import os

# TPU v5e per-chip constants. The DRY-RUN analysis models the production
# TPU mesh regardless of where the script runs; the candidate-path
# roofline reads the peaks of the device underneath it (measured_peaks).
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link

RESULTS = os.path.join(os.path.dirname(__file__), "results")

_H2D_BW: float | None = None


def measured_h2d_bw(force: bool = False) -> float:
    """Best-of-3 host -> device transfer bandwidth (bytes/s) of the live
    backend, probed as a timed ``jax.device_put`` of a 64 MB numpy buffer
    — the exact operation the tiered store's promotion path performs, so
    the tiered roofline's transfer term is calibrated to what an eviction
    miss actually costs here (PCIe/DMA on accelerators, a memcpy-ish copy
    on CPU hosts). Cached per process."""
    global _H2D_BW
    if _H2D_BW is not None and not force:
        return _H2D_BW
    import time as _time
    import numpy as _np
    import jax
    a = _np.ones((16 << 20,), _np.float32)             # 64 MB
    jax.device_put(a).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t0 = _time.perf_counter()
        jax.device_put(a).block_until_ready()
        best = min(best, _time.perf_counter() - t0)
    _H2D_BW = a.nbytes / best
    return _H2D_BW


def tiered_overlap_roofline(scan_bytes: float, scan_flops: float,
                            transfer_bytes: float, hit_rate: float,
                            h2d_bw: float | None = None,
                            t_scan_s: float | None = None) -> dict:
    """Predicted per-query cost of the tiered scan, synchronous-fetch vs
    prefetch-overlapped, from first principles:

    - ``t_scan``: the device-side scan roofline ``max(bytes/bw,
      flops/peak)`` over the scanned (device-resident) bytes;
    - ``t_xfer``: the EXPECTED host->device bill per query —
      ``(1 - hit_rate) * transfer_bytes`` (the ``tier-transfer`` entry of
      ``multistage.cascade_hbm_bytes``) at the measured ``device_put``
      bandwidth.

    The synchronous baseline pays ``t_scan + t_xfer`` (the transfer sits
    exposed on the critical path); with async prefetch over a visible
    arrival queue the worker's copy lands under compute and steady state
    is ``max(t_scan, t_xfer)``. ``benchmarks/run.py tiered_qps`` prints
    this prediction next to the measured ladder.

    ``h2d_bw`` overrides the measured ``device_put`` bandwidth — pass
    the emulated link rate when the A/B runs against
    ``TieredEngine(link_bw=...)`` so the prediction models the link the
    measurement actually crossed. ``t_scan_s`` likewise substitutes a
    measured per-query scan time for the byte/flop roofline (which needs
    the device's published peaks, see ``measured_peaks``)."""
    bw = h2d_bw if h2d_bw else measured_h2d_bw()
    if t_scan_s:
        t_scan = t_scan_s
    else:
        peaks = measured_peaks()
        t_scan = max(scan_bytes / peaks["hbm_bw"],
                     scan_flops / peaks["flops"])
    t_xfer = (1.0 - hit_rate) * transfer_bytes / bw
    sync_s = t_scan + t_xfer
    overlap_s = max(t_scan, t_xfer)
    return {"t_scan_s": t_scan, "t_xfer_s": t_xfer,
            "sync_s": sync_s, "overlap_s": overlap_s,
            "speedup": sync_s / max(overlap_s, 1e-30), "h2d_bw": bw}


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``.
DEVICE_PEAKS = {
    "TPU v5 lite": {"flops": PEAK_FLOPS, "int8_ops": 393e12,
                    "hbm_bw": HBM_BW,
                    "source": "Google Cloud documentation, 'TPU v5e'"},
}


class UnknownDeviceError(LookupError):
    """The device has no row in ``DEVICE_PEAKS``: a prediction against an
    assumed or CPU-timed peak would not be a roofline of this device."""


def measured_peaks() -> dict:
    """Peak bf16 FLOP/s, int8 OP/s and HBM bandwidth of the device the
    benchmark runs on, from ``DEVICE_PEAKS`` by ``device_kind``. Raises
    ``UnknownDeviceError`` for a device not in the table (the CPU
    included): predictions belong beside chip measurements only."""
    import jax
    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise UnknownDeviceError(
            f"no published peaks for device kind {kind!r} "
            f"(known: {sorted(DEVICE_PEAKS)})")
    return DEVICE_PEAKS[kind]


def analyse(rec: dict) -> dict | None:
    if not rec.get("ok"):
        return None
    struct = rec.get("struct")
    if struct:
        # structural HLO walk: loop trip counts applied (primary source)
        flops = struct["flops"] or 0.0
        bytes_acc = 2.0 * (struct["bytes_written"] or 0.0)   # read + write
        coll = struct["collective_total"]
    else:                        # legacy records: raw cost_analysis
        flops = rec["cost"].get("flops") or 0.0
        bytes_acc = rec["cost"].get("bytes_accessed") or 0.0
        coll = rec["collectives"]["total_bytes"]
    n_dev = 512 if rec.get("mesh") == "multi" else 256
    t_c = flops / PEAK_FLOPS
    t_m = bytes_acc / HBM_BW
    t_x = coll / LINK_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dom = max(terms, key=terms.get)
    total = max(sum(terms.values()), 1e-30)
    model_flops_dev = (rec.get("model_flops") or 0.0) / n_dev
    useful = model_flops_dev / flops if flops else 0.0
    # roofline fraction: dominant-term time / perfectly-overlapped ideal
    frac = terms[dom] / total if total else 0.0
    step_bound = max(terms.values())
    return {
        "arch": rec["arch"], "shape": rec["shape"],
        "compute_s": t_c, "memory_s": t_m, "collective_s": t_x,
        "bottleneck": dom,
        "step_lower_bound_s": step_bound,
        "useful_flops_frac": useful,
        "mem_temp_gb": (rec["memory"]["temp_bytes"] or 0) / 1e9,
        "mem_args_gb": (rec["memory"]["argument_bytes"] or 0) / 1e9,
        "note": rec.get("note", ""),
    }


FIX_HINTS = {
    ("compute", True): "already compute-bound with high useful fraction: "
                       "at roofline; further wins need algorithmic change",
    ("compute", False): "compute-bound but low useful fraction: remove "
                        "redundant FLOPs (MoE ragged dispatch / less remat)",
    ("memory", True): "memory-bound: fuse ops, cast streams to bf16/int8, "
                      "re-tile to raise arithmetic intensity",
    ("memory", False): "memory-bound with FLOP waste: chunk the pipeline "
                       "and drop precision of streamed buffers",
    ("collective", True): "collective-bound: overlap collectives with "
                          "compute, reduce-scatter instead of all-reduce",
    ("collective", False): "collective-bound: change sharding so the big "
                           "tensor never crosses the interconnect",
}


def hint(row: dict) -> str:
    return FIX_HINTS[(row["bottleneck"], row["useful_flops_frac"] > 0.3)]


def candidate_path_roofline(n_docs: int, q_tokens: int, dim: int,
                            stages: tuple, store_dims: dict,
                            vec_dims: dict | None = None, *,
                            batch: int = 1,
                            bytes_per_coord: dict | None = None) -> dict:
    """Predicted roofline seconds for the serving cascade's candidate
    path, reference vs fused policy, on the device's published peaks
    (``measured_peaks``; raises ``UnknownDeviceError`` off the table).

    Bills the exact terms the fused path attacks (via
    ``repro.core.multistage.cascade_hbm_bytes``): the scan stage's
    [B, N] score write (vs the streamed top-k's O(B*k*n_chunks)) and the
    rerank stage's 3x-billed materialised gather (vs the fused kernel's
    single streamed read). Predicted time is the TWO-term roofline
    ``max(bytes / bw, flops / peak)`` — on TPU the cascade is firmly
    memory-bound and the compute term vanishes, but on a CPU host the
    madds are a real fraction of the wall clock, and since ref and fused
    perform the SAME madds the compute floor is what compresses the
    predicted ratio toward the measured one. ``byte_ratio`` preserves
    the raw bandwidth-only claim.
    """
    from repro.core import multistage as MST
    peaks = measured_peaks()
    ref_stages = MST.with_rerank_policy(
        MST.with_scan_policy(tuple(stages), scan_topk=False),
        rerank_kernel=False)
    fused_stages = MST.with_rerank_policy(
        MST.with_scan_policy(tuple(stages), scan_topk=True),
        rerank_kernel=True)
    out = {"peaks": dict(peaks)}
    for name, st in (("ref", ref_stages), ("fused", fused_stages)):
        bill = MST.cascade_hbm_bytes(n_docs, q_tokens, dim, st, store_dims,
                                     vec_dims, batch=batch,
                                     bytes_per_coord=bytes_per_coord)
        flops = 2.0 * batch * MST.qps_cost_model(n_docs, q_tokens, dim, st,
                                                 store_dims, vec_dims)
        out[name] = {"bytes": bill["total_bytes"], "flops": flops,
                     "seconds": max(bill["total_bytes"] / peaks["hbm_bw"],
                                    flops / peaks["flops"]),
                     "stages": bill["stages"]}
    out["byte_ratio"] = out["ref"]["bytes"] / max(out["fused"]["bytes"], 1)
    out["speedup"] = out["ref"]["seconds"] / max(out["fused"]["seconds"],
                                                 1e-30)
    return out


def _candidate_path_cli(args):
    """Print the predicted candidate-path roofline for a paper-scale
    ColPali-style cascade (pooled scan D'=32 @ int8-capable bf16, exact
    rerank D=1024, d=128)."""
    from repro.core import multistage as MST
    stages = MST.two_stage(args.prefetch_k, args.top_k)
    store_dims = {"mean_pooling": 32, "initial": 1024}
    rep = candidate_path_roofline(args.n_docs, args.q_tokens, 128, stages,
                                  store_dims, batch=args.batch)
    pk = rep["peaks"]
    print(f"candidate-path roofline @ N={args.n_docs} B={args.batch} "
          f"({pk['source']}: {pk['hbm_bw']/1e9:.1f} GB/s, "
          f"{pk['flops']/1e12:.2f} TFLOP/s)")
    for name in ("ref", "fused"):
        r = rep[name]
        print(f"  {name:5s}: {r['bytes']/1e9:8.3f} GB  "
              f"{r['seconds']*1e3:8.3f} ms/batch")
        for st in r["stages"]:
            print(f"         {st['kind']:6s} {st['stage']:14s} "
                  f"read={st['read_bytes']/1e6:10.2f} MB  "
                  f"score_write={st['score_write_bytes']/1e6:8.2f} MB")
    print(f"  predicted fused speedup: {rep['speedup']:.2f}x "
          f"(bandwidth-only byte ratio: {rep['byte_ratio']:.2f}x)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=os.path.join(RESULTS,
                                                   "dryrun_single.json"))
    ap.add_argument("--md", action="store_true", help="markdown table")
    ap.add_argument("--candidate-path", action="store_true",
                    help="print the analytic candidate-path roofline "
                         "(ref vs fused cascade) instead of the dry-run "
                         "analysis")
    ap.add_argument("--n-docs", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--q-tokens", type=int, default=16)
    ap.add_argument("--prefetch-k", type=int, default=256)
    ap.add_argument("--top-k", type=int, default=100)
    args = ap.parse_args()
    if args.candidate_path:
        _candidate_path_cli(args)
        return
    with open(args.json) as f:
        data = json.load(f)
    rows = [r for r in (analyse(v) for v in data.values()) if r]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    out_path = os.path.join(RESULTS, "roofline.json")
    with open(out_path, "w") as f:
        json.dump(rows, f, indent=1)

    hdr = (f"{'arch':22s} {'shape':15s} {'compute':>9s} {'memory':>9s} "
           f"{'collect':>9s} {'bound':>10s} {'useful':>7s} {'temp':>7s}")
    sep = "-" * len(hdr)
    if args.md:
        print("| arch | shape | compute s | memory s | collective s | "
              "bottleneck | useful FLOP frac | temp GB |")
        print("|---|---|---|---|---|---|---|---|")
        for r in rows:
            print(f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3g} | "
                  f"{r['memory_s']:.3g} | {r['collective_s']:.3g} | "
                  f"{r['bottleneck']} | {r['useful_flops_frac']:.2f} | "
                  f"{r['mem_temp_gb']:.1f} |")
    else:
        print(hdr)
        print(sep)
        for r in rows:
            print(f"{r['arch']:22s} {r['shape']:15s} {r['compute_s']:9.3g} "
                  f"{r['memory_s']:9.3g} {r['collective_s']:9.3g} "
                  f"{r['bottleneck']:>10s} {r['useful_flops_frac']:7.2f} "
                  f"{r['mem_temp_gb']:6.1f}G")
    print(f"\n{len(rows)} cells -> {out_path}")


if __name__ == "__main__":
    main()

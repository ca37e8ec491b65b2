import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) on the production
meshes, print memory/cost analysis, extract collective bytes from the
partitioned HLO. Results are cached to benchmarks/results/*.json so the
roofline pass and EXPERIMENTS.md generation read from disk.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --mesh single            # 16x16
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi             # 2x16x16
  PYTHONPATH=src python -m repro.launch.dryrun --arch olmoe-1b-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh tiny              # 2x4 (debug)
"""

import argparse
import json
import re
import time
import traceback

import jax

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "benchmarks", "results")

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8, "c64": 8, "c128": 16}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_COLL_RE = re.compile(
    r"=\s+(.+?)\s+(all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(-start)?\(")


def _shape_bytes(type_str: str) -> int:
    """'bf16[8,2048]' -> bytes. Tuple types handled by caller regex."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-device result-buffer bytes of every collective in partitioned HLO.

    Convention (documented in EXPERIMENTS.md): we sum RESULT shapes — for
    all-gather that equals the received bytes, for all-reduce the reduced
    buffer (ring moves ~2x this; we report the buffer), for all-to-all /
    collective-permute the transferred block.
    """
    out = {op: 0 for op in COLLECTIVE_OPS}
    counts = {op: 0 for op in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        type_str, op = m.group(1), m.group(2)
        out[op] += _shape_bytes(type_str)
        counts[op] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": sum(out.values())}


def run_cell(arch: str, shape_name: str, mesh, mesh_name: str,
             keep_hlo: bool = False, variant: str = "base") -> dict:
    from repro.launch.cells import build_cell
    t0 = time.time()
    cell = build_cell(arch, shape_name, mesh, variant=variant)
    jitted = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                     donate_argnums=cell.donate)
    lowered = jitted.lower(*cell.args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    from repro.launch.hlo_analysis import analyse_module
    struct = analyse_module(hlo)
    res = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "ok": True,
        "model_flops": cell.model_flops,
        "note": cell.note,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
            "code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
        },
        # raw XLA cost_analysis (NOTE: while bodies counted once on CPU)
        "cost": {"flops": cost.get("flops"),
                 "bytes_accessed": cost.get("bytes accessed")},
        # structural walk with loop trip counts applied (primary source)
        "struct": struct,
        "collectives": coll,
    }
    if keep_hlo:
        res["hlo_len"] = len(hlo)
    del hlo, compiled, lowered
    return res


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="single",
                    choices=["single", "multi", "tiny"])
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--family", default=None,
                    help="only archs of this family (lm|gnn|recsys|retriever)")
    ap.add_argument("--out", default=None)
    ap.add_argument("--variant", default="base",
                    help="base | opt | stage1 (see cells.build_cell)")
    ap.add_argument("--force", action="store_true",
                    help="recompute cells already in the results file")
    args = ap.parse_args()
    from repro.launch.runtime import device_line
    print(device_line(), flush=True)

    from repro.launch.mesh import make_production_mesh, make_mesh
    from repro.configs import ASSIGNED_ARCHS, PAPER_ARCHS, get_config, \
        get_shapes

    if args.mesh == "single":
        mesh = make_production_mesh(multi_pod=False)
    elif args.mesh == "multi":
        mesh = make_production_mesh(multi_pod=True)
    else:
        mesh = make_mesh((2, 4), ("data", "model"))

    os.makedirs(RESULTS_DIR, exist_ok=True)
    suffix = "" if args.variant == "base" else f"_{args.variant}"
    out_path = args.out or os.path.join(RESULTS_DIR,
                                        f"dryrun_{args.mesh}{suffix}.json")
    results = {}
    if os.path.exists(out_path):
        with open(out_path) as f:
            results = json.load(f)

    archs = ([args.arch] if args.arch else
             list(ASSIGNED_ARCHS) + list(PAPER_ARCHS))
    if args.family:
        archs = [a for a in archs if get_config(a).family == args.family]

    for arch in archs:
        shapes = ([args.shape] if args.shape else list(get_shapes(arch)))
        for shape_name in shapes:
            key = f"{arch}|{shape_name}"
            if key in results and results[key].get("ok") and not args.force:
                print(f"[skip] {key} (cached)")
                continue
            print(f"[dryrun] {arch} x {shape_name} on {args.mesh} ...",
                  flush=True)
            try:
                res = run_cell(arch, shape_name, mesh, args.mesh,
                               variant=args.variant)
                mb = (res["memory"]["argument_bytes"] or 0) / 1e6
                tb = (res["memory"]["temp_bytes"] or 0) / 1e6
                print(f"  ok: args={mb:.0f}MB temp={tb:.0f}MB "
                      f"flops/dev={res['struct']['flops']:.3g} "
                      f"coll/dev={res['struct']['collective_total']/1e6:.1f}MB"
                      f" (compile {res['compile_s']}s)", flush=True)
            except Exception as e:  # noqa: BLE001 - report per-cell failure
                res = {"arch": arch, "shape": shape_name, "mesh": args.mesh,
                       "ok": False, "error": f"{type(e).__name__}: {e}",
                       "trace": traceback.format_exc()[-2000:]}
                print(f"  FAIL: {res['error'][:200]}", flush=True)
            results[key] = res
            with open(out_path, "w") as f:
                json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    print(f"\n{n_ok}/{len(results)} cells OK -> {out_path}")


if __name__ == "__main__":
    main()

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init).  512 placeholder host devices back the production
# meshes: 16x16 single-pod and 2x16x16 multi-pod.

import argparse
import json
import sys
import time
import traceback

import jax
import numpy as np

from repro.analysis.hlo import collective_bytes
from repro.configs import ARCH_NAMES, get_config, wfa_paper
from repro.launch.lowering import build_lm_cell, build_wfa_cell, lower_cell
from repro.launch.mesh import make_production_mesh, mesh_devices
from repro.models.common import SHAPES, model_flops

RESULTS_DEFAULT = "results/dryrun/cells.jsonl"


def mesh_tag(multi_pod: bool) -> str:
    return "pod2-2x16x16" if multi_pod else "pod1-16x16"


def _leaf_device_bytes(sds, sharding) -> int:
    shard = sharding.shard_shape(sds.shape)
    return int(np.prod(shard, dtype=np.int64)) * jax.numpy.dtype(sds.dtype).itemsize


def analytic_device_bytes(cell) -> int:
    total = 0

    def walk(sds_tree, sh_tree):
        nonlocal total
        leaves_s = jax.tree.leaves(sds_tree)
        leaves_h = jax.tree.leaves(
            sh_tree, is_leaf=lambda x: hasattr(x, "shard_shape"))
        for s, h in zip(leaves_s, leaves_h):
            total += _leaf_device_bytes(s, h)

    for arg, sh in zip(cell.args, cell.in_shardings):
        walk(arg, sh)
    return total


def _compile_and_measure(cell, mesh, n_dev) -> dict:
    t0 = time.time()
    lowered, _ = lower_cell(cell, mesh)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()

    out = {"lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2)}
    cost = compiled.cost_analysis()
    out["flops_per_device"] = float(cost.get("flops", -1.0))
    out["bytes_per_device"] = float(cost.get("bytes accessed", -1.0))
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                         "temp_size_in_bytes", "generated_code_size_in_bytes",
                         "alias_size_in_bytes"):
                v = getattr(mem, attr, None)
                if v is not None:
                    out[f"mem_{attr}"] = int(v)
    except Exception as e:  # CPU backend may not implement it
        out["mem_error"] = repr(e)
    hlo = compiled.as_text()
    out["collectives"] = collective_bytes(hlo, n_dev)
    out["hlo_bytes"] = len(hlo)
    return out


def roofline_depths(cfg):
    """Three lowering depths for the per-layer extrapolation.

    Layers are identical stacked blocks, so the HLO roofline quantities are
    polynomial in depth: empirically EXACTLY quadratic (validated against a
    full 28-layer unrolled lowering to 4 significant digits — the small
    quadratic term is ~0.5% of the linear term at production depths; see
    DESIGN.md §7).  Three scan-UNROLLED shallow lowerings determine the
    quadratic, evaluated at the production depth.  Hybrids use depths
    congruent to the production depth mod the shared-block period so the
    ragged tail segment appears identically in every point; MoE keeps its
    dense head layers fixed.
    """
    if cfg.family == "hybrid" and cfg.hybrid_attn_every:
        e = cfg.hybrid_attn_every
        r = cfg.n_layers % e
        return r + e, r + 2 * e, r + 4 * e
    head = cfg.first_k_dense if cfg.is_moe else 0
    return head + 2, head + 4, head + 8


def _fit_quadratic(depths, values, L):
    """Exact quadratic through three (depth, value) points, evaluated at L."""
    a = np.array([[1.0, d, d * d] for d in depths])
    coef = np.linalg.solve(a, np.asarray(values, float))
    return float(coef @ np.array([1.0, L, L * L]))


def run_cell(arch: str, shape_name: str, multi_pod: bool, *,
             mode: str = "memory", skip_reason: str = "",
             exact_depth: bool = False) -> dict:
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_tag(multi_pod),
        "pass": mode,
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if skip_reason:
        record.update(status="skipped", reason=skip_reason)
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh_devices(mesh)
    try:
        if arch == "wfa-paper":
            ef = {"fig1_e2": 0.02, "fig1_e4": 0.04}[shape_name]
            cell = build_wfa_cell(wfa_paper, mesh, edit_frac=ef)
            record["model_flops"] = 0.0
            record.update(_compile_and_measure(cell, mesh, n_dev))
            record["analytic_arg_bytes_per_device"] = analytic_device_bytes(cell)
            record.update(status="ok", n_devices=n_dev,
                          **{f"meta_{k}": v for k, v in cell.meta.items()})
            return record

        cfg = get_config(arch)
        shape = SHAPES[shape_name]
        record["model_flops"] = model_flops(cfg, shape)
        record["param_count"] = cfg.param_count()
        record["active_param_count"] = cfg.active_param_count()

        depths = roofline_depths(cfg)
        if mode == "memory" or exact_depth or cfg.n_layers <= depths[-1]:
            cell = build_lm_cell(cfg, shape, mesh, mode=mode)
            record.update(_compile_and_measure(cell, mesh, n_dev))
            record["analytic_arg_bytes_per_device"] = analytic_device_bytes(cell)
            record.update(status="ok", n_devices=n_dev,
                          **{f"meta_{k}": v for k, v in cell.meta.items()})
            return record

        # roofline pass: three shallow scan-unrolled lowerings -> quadratic
        points = []
        for L in depths:
            cell = build_lm_cell(cfg.replace(n_layers=L), shape, mesh,
                                 mode="roofline")
            m = _compile_and_measure(cell, mesh, n_dev)
            m["n_layers"] = L
            points.append(m)
        Lf = cfg.n_layers
        record["flops_per_device"] = _fit_quadratic(
            depths, [p["flops_per_device"] for p in points], Lf)
        record["bytes_per_device"] = _fit_quadratic(
            depths, [p["bytes_per_device"] for p in points], Lf)
        keys = set()
        for p in points:
            keys |= set(p["collectives"])
        coll = {k: max(0.0, _fit_quadratic(
                    depths, [p["collectives"].get(k, 0.0) for p in points], Lf))
                for k in keys}
        record["collectives"] = coll
        record["roofline_points"] = [
            {k: v for k, v in p.items() if not isinstance(v, dict)}
            for p in points]
        record["extrapolated_from"] = list(depths)
        record["compile_s"] = round(sum(p["compile_s"] for p in points), 2)
        record["lower_s"] = round(sum(p["lower_s"] for p in points), 2)
        record.update(status="ok", n_devices=n_dev,
                      **{f"meta_{k}": v for k, v in cell.meta.items()})
    except Exception:
        record.update(status="error", error=traceback.format_exc()[-4000:])
    return record


def applicable_cells(archs, shapes, meshes, passes):
    for arch in archs:
        if arch == "wfa-paper":
            arch_shapes = ["fig1_e2", "fig1_e4"]
        else:
            arch_shapes = list(SHAPES)
        for shape_name in arch_shapes:
            if shapes and shape_name not in shapes:
                continue
            skip = ""
            if arch != "wfa-paper":
                cfg = get_config(arch)
                if (shape_name == "long_500k"
                        and not cfg.supports_long_context):
                    skip = ("quadratic full attention at 512k ctx; runs only "
                            "for ssm/hybrid archs (DESIGN.md §8)")
            for multi_pod in meshes:
                for mode in (passes if arch != "wfa-paper" else ["memory"]):
                    # roofline numbers come from the single-pod mesh only
                    if mode == "roofline" and multi_pod:
                        continue
                    yield arch, shape_name, multi_pod, mode, skip


def load_done(path):
    done = {}
    if os.path.exists(path):
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                except json.JSONDecodeError:
                    continue
                done[(r["arch"], r["shape"], r["mesh"],
                      r.get("pass", "memory"))] = r.get("status")
    return done


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", nargs="*", default=None,
                    help="arch ids (default: all 10 + wfa-paper)")
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=RESULTS_DEFAULT)
    ap.add_argument("--force", action="store_true",
                    help="re-run cells already recorded")
    ap.add_argument("--retry-errors", action="store_true")
    ap.add_argument("--pass", dest="passes", nargs="*",
                    choices=["memory", "roofline"],
                    default=["memory", "roofline"])
    args = ap.parse_args(argv)

    archs = args.arch or (ARCH_NAMES + ["wfa-paper"])
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    done = load_done(args.out)

    n_ok = n_err = n_skip = 0
    for arch, shape_name, multi_pod, mode, skip in applicable_cells(
            archs, args.shape, meshes, args.passes):
        key = (arch, shape_name, mesh_tag(multi_pod), mode)
        prev = done.get(key)
        if prev is not None and not args.force:
            if not (args.retry_errors and prev == "error"):
                continue
        print(f"[dryrun] {key} ...", flush=True)
        rec = run_cell(arch, shape_name, multi_pod, mode=mode,
                       skip_reason=skip)
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        status = rec["status"]
        n_ok += status == "ok"
        n_err += status == "error"
        n_skip += status == "skipped"
        extra = ""
        if status == "ok":
            extra = (f" flops/dev={rec['flops_per_device']:.3e}"
                     f" coll={rec['collectives']['total']:.3e}B"
                     f" compile={rec['compile_s']}s")
        elif status == "error":
            extra = " " + rec["error"].strip().splitlines()[-1][:160]
        print(f"[dryrun] {key} -> {status}{extra}", flush=True)

    print(f"[dryrun] done: ok={n_ok} err={n_err} skip={n_skip}")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: lower + compile every (architecture x input-shape x
mesh) cell with ShapeDtypeStruct inputs (no allocation), record
memory_analysis / cost_analysis / per-collective byte counts.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch glm4_9b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi    # 2x16x16 only

Artifacts: one JSON per cell under artifacts/dryrun/ (consumed by
benchmarks/roofline.py and EXPERIMENTS.md §Dry-run / §Roofline).
"""
import argparse
import dataclasses
import json
import re
import time
import traceback
from pathlib import Path

import jax

from repro.configs import registry
from repro.distributed import api as dist_api
from repro.distributed import sharding as sh
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.optim.adamw import AdamW, make_train_step

ARTIFACTS = Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"

_DTYPE_BYTES = {
    "pred": 0.125, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)


def parse_collective_bytes(hlo_text: str):
    """Sums result-shape bytes of every collective op in post-SPMD HLO.

    Accounting (per-device traffic estimate, ring algorithms):
      all-reduce       2x result bytes
      all-gather       1x result bytes
      reduce-scatter   1x operand bytes (~= result x group)
      all-to-all       1x result bytes
      collective-permute 1x result bytes
    """
    totals = {c: 0.0 for c in _COLLECTIVES}
    counts = {c: 0 for c in _COLLECTIVES}
    # e.g.:  %all-gather.3 = bf16[4,1024,512]{...} all-gather(...)
    pat = re.compile(
        r"=\s*(?:\()?\s*((?:[a-z0-9]+\[[0-9,]*\][^ ]*\s*,?\s*)+)\s*"
        r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
        r"collective-permute)(?:-start)?\(",
    )
    shape_pat = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

    for m in pat.finditer(hlo_text):
        shapes, op = m.group(1), m.group(2)
        nbytes = 0.0
        for sm in shape_pat.finditer(shapes):
            dt, dims = sm.group(1), sm.group(2)
            if dt not in _DTYPE_BYTES:
                continue
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            nbytes += n * _DTYPE_BYTES[dt]
        w = 2.0 if op == "all-reduce" else 1.0
        totals[op] += w * nbytes
        counts[op] += 1
    return totals, counts


def _spec_tree_to_json(tree):
    return jax.tree.map(
        lambda s: str(getattr(s, "spec", s)), tree,
        is_leaf=lambda x: hasattr(x, "spec"),
    )


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               seq_parallel: bool = False):
    """Lower + compile one cell; returns the result record dict."""
    cfg = registry.get_config(arch)
    ok, why = cfg.supports_shape(shape_name)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = build_model(cfg)
    kind = registry.SHAPES[shape_name]["kind"]
    t0 = time.time()

    param_specs = model.param_specs()
    p_shard, fallbacks = sh.param_shardings(mesh, param_specs, cfg)

    if kind == "train":
        opt = AdamW(lr=1e-4)
        opt_state_specs = jax.eval_shape(opt.init, param_specs)
        o_shard, _ = sh.param_shardings(mesh, opt_state_specs.m, cfg)
        opt_shard = type(opt_state_specs)(
            m=o_shard,
            v=jax.tree.map(lambda s: s, o_shard),
            step=jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        )
        batch_specs = model.input_specs(shape_name)
        b_shard = sh.batch_shardings(mesh, batch_specs)
        fn = make_train_step(model, opt)
        args = (param_specs, opt_state_specs, batch_specs)
        in_shard = (p_shard, opt_shard, b_shard)
        out_shard = (p_shard, opt_shard, None)
    elif kind == "prefill":
        batch_specs = model.input_specs(shape_name)
        b_shard = sh.batch_shardings(mesh, batch_specs)
        s = registry.SHAPES[shape_name]
        cache_spec = model.cache_specs(s["seq_len"], s["global_batch"])
        c_shard = sh.cache_shardings(mesh, cache_spec, cfg)

        def fn(params, batch):
            return model.prefill(params, batch)

        args = (param_specs, batch_specs)
        in_shard = (p_shard, b_shard)
        out_shard = (None, c_shard)
    else:  # decode
        s = registry.SHAPES[shape_name]
        dspec = model.input_specs(shape_name)
        tok_shard = sh.batch_shardings(mesh, dspec["token"])
        c_shard = sh.cache_shardings(mesh, dspec["cache"], cfg)

        def fn(params, token, cache):
            return model.decode_step(params, token, cache)

        args = (param_specs, dspec["token"], dspec["cache"])
        in_shard = (p_shard, tok_shard, c_shard)
        out_shard = (None, c_shard)

    rules = sh.activation_rule_table(mesh, cfg, seq_parallel=seq_parallel)
    with jax.set_mesh(mesh), dist_api.activation_rules(
        rules, mesh=mesh, dp_axes=sh.dp_axes(mesh), ep_axis="model"
    ):
        jfn = jax.jit(fn, in_shardings=in_shard, out_shardings=out_shard)
        lowered = jfn.lower(*args)
        compiled = lowered.compile()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll_bytes, coll_counts = parse_collective_bytes(hlo)
    t1 = time.time()

    n_dev = mesh.size
    record = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "n_devices": n_dev,
        "seq_parallel": seq_parallel,
        "status": "ok",
        "compile_seconds": round(t1 - t0, 1),
        "memory": {
            k: int(getattr(mem, k))
            for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
            ) if hasattr(mem, k)
        },
        "cost": {
            "flops_per_device": float(cost.get("flops", 0.0)),
            "bytes_accessed_per_device": float(
                cost.get("bytes accessed", 0.0)
            ),
            "transcendentals": float(cost.get("transcendentals", 0.0)),
        },
        "collectives": {
            "bytes": coll_bytes,
            "counts": coll_counts,
            "total_bytes": sum(coll_bytes.values()),
        },
        "sharding_fallbacks": [
            {"path": p, "dim": d, "axis": str(a)} for p, d, a in fallbacks
        ][:40],
        "model": {
            "total_params": cfg.total_params(),
            "active_params": cfg.active_params(),
        },
    }
    return record


def cell_path(arch, shape_name, mesh_tag, seq_parallel=False):
    sp = "__sp" if seq_parallel else ""
    return ARTIFACTS / f"{arch}__{shape_name}__{mesh_tag}{sp}.json"


# ---------------------------------------------------------------------------
# Cost calibration: XLA's HloCostAnalysis counts a while-loop body ONCE, so
# scanned layer stacks under-report flops/bytes/collective-bytes by the trip
# count. We compile fully-unrolled 1-unit and 2-unit variants (unit = layer,
# hybrid super-block, or enc+dec layer pair) and extrapolate affinely:
#     cost(L) = cost(1) + (cost(2) - cost(1)) * (L - 1)
# which is exact for homogeneous stacks (embeddings/CE live in the
# intercept). Verified against the calibration identity in tests.
# ---------------------------------------------------------------------------

def _reduced_cfg(cfg, units: int):
    if cfg.family == "hybrid":
        return dataclasses.replace(
            cfg, n_layers=units * cfg.attn_every, unroll_scans=True
        )
    if cfg.is_encoder_decoder:
        return dataclasses.replace(
            cfg, n_layers=units, n_encoder_layers=units, unroll_scans=True
        )
    return dataclasses.replace(cfg, n_layers=units, unroll_scans=True)


def _full_units(cfg) -> int:
    if cfg.family == "hybrid":
        return cfg.n_layers // cfg.attn_every
    return cfg.n_layers


def _cell_costs(cfg, shape_name: str, multi_pod: bool,
                seq_parallel: bool = False):
    """Compile one (possibly reduced) config variant; return raw costs."""
    from repro.models.model import Model

    mesh = make_production_mesh(multi_pod=multi_pod)
    model = Model(cfg)
    kind = registry.SHAPES[shape_name]["kind"]
    param_specs = model.param_specs()
    p_shard, _ = sh.param_shardings(mesh, param_specs, cfg)

    if kind == "train":
        opt = AdamW(lr=1e-4)
        opt_state_specs = jax.eval_shape(opt.init, param_specs)
        o_shard, _ = sh.param_shardings(mesh, opt_state_specs.m, cfg)
        opt_shard = type(opt_state_specs)(
            m=o_shard, v=o_shard,
            step=jax.sharding.NamedSharding(mesh,
                                            jax.sharding.PartitionSpec()),
        )
        batch_specs = model.input_specs(shape_name)
        b_shard = sh.batch_shardings(mesh, batch_specs)
        fn = make_train_step(model, opt)
        args = (param_specs, opt_state_specs, batch_specs)
        in_shard = (p_shard, opt_shard, b_shard)
        out_shard = (p_shard, opt_shard, None)
    elif kind == "prefill":
        batch_specs = model.input_specs(shape_name)
        b_shard = sh.batch_shardings(mesh, batch_specs)
        s = registry.SHAPES[shape_name]
        cache_spec = model.cache_specs(s["seq_len"], s["global_batch"])
        c_shard = sh.cache_shardings(mesh, cache_spec, cfg)

        def fn(params, batch):
            return model.prefill(params, batch)

        args = (param_specs, batch_specs)
        in_shard = (p_shard, b_shard)
        out_shard = (None, c_shard)
    else:
        dspec = model.input_specs(shape_name)
        tok_shard = sh.batch_shardings(mesh, dspec["token"])
        c_shard = sh.cache_shardings(mesh, dspec["cache"], cfg)

        def fn(params, token, cache):
            return model.decode_step(params, token, cache)

        args = (param_specs, dspec["token"], dspec["cache"])
        in_shard = (p_shard, tok_shard, c_shard)
        out_shard = (None, c_shard)

    rules = sh.activation_rule_table(mesh, cfg, seq_parallel=seq_parallel)
    with jax.set_mesh(mesh), dist_api.activation_rules(
        rules, mesh=mesh, dp_axes=sh.dp_axes(mesh), ep_axis="model"
    ):
        compiled = jax.jit(
            fn, in_shardings=in_shard, out_shardings=out_shard
        ).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll_bytes, _ = parse_collective_bytes(compiled.as_text())
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collective_bytes": sum(coll_bytes.values()),
        "collective_by_op": coll_bytes,
    }


def calibrate_cell(arch: str, shape_name: str, multi_pod: bool,
                   seq_parallel: bool = False):
    cfg = registry.get_config(arch)
    ok, _ = cfg.supports_shape(shape_name)
    if not ok:
        return None
    L = _full_units(cfg)
    c1 = _cell_costs(_reduced_cfg(cfg, 1), shape_name, multi_pod,
                     seq_parallel)
    c2 = _cell_costs(_reduced_cfg(cfg, 2), shape_name, multi_pod,
                     seq_parallel)
    # per-unit deltas clamped at 0: XLA occasionally optimizes the 2-unit
    # module harder than the 1-unit one (CSE across layers), which would
    # extrapolate negative -- physically impossible.
    corrected = {
        k: c1[k] + max(c2[k] - c1[k], 0.0) * (L - 1)
        for k in ("flops", "bytes", "collective_bytes")
    }
    corrected["collective_by_op"] = {
        op: c1["collective_by_op"][op]
        + max(c2["collective_by_op"][op] - c1["collective_by_op"][op], 0.0)
        * (L - 1)
        for op in c1["collective_by_op"]
    }
    corrected["units_full"] = L
    corrected["nonmonotone"] = bool(
        any(c2[k] < 0.98 * c1[k] for k in ("flops", "bytes"))
    )
    corrected["per_unit"] = {
        k: max(c2[k] - c1[k], 0.0)
        for k in ("flops", "bytes", "collective_bytes")
    }
    return corrected


def run_calibration(archs, shapes, meshes, force=False,
                    seq_parallel=False):
    n = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = "multi" if multi else "single"
                out = cell_path(arch, shape_name, tag, seq_parallel)
                if not out.exists():
                    continue
                rec = json.loads(out.read_text())
                if rec.get("status") != "ok":
                    continue
                if "cost_corrected" in rec and not force:
                    continue
                try:
                    corrected = calibrate_cell(arch, shape_name, multi,
                                               seq_parallel)
                except Exception as e:
                    print(f"[cal-FAIL] {arch} x {shape_name} x {tag}: "
                          f"{str(e)[:200]}")
                    continue
                if corrected is None:
                    continue
                rec["cost_corrected"] = corrected
                out.write_text(json.dumps(rec, indent=2))
                n += 1
                print(f"[cal] {arch} x {shape_name} x {tag}: "
                      f"{corrected['flops']/1e12:.2f} TF/dev, "
                      f"{corrected['bytes']/1e9:.1f} GB/dev, "
                      f"coll {corrected['collective_bytes']/1e9:.2f} GB")
    print(f"calibrated {n} cells")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--calibrate", action="store_true",
                    help="add loop-corrected cost numbers to existing cells")
    ap.add_argument("--seq-parallel", action="store_true",
                    help="sequence-parallel activation sharding (artifacts "
                         "suffixed __sp)")
    ap.add_argument("--print-hlo-collectives", action="store_true")
    args = ap.parse_args()

    ARTIFACTS.mkdir(parents=True, exist_ok=True)
    archs = registry.ARCH_IDS if args.arch == "all" else [args.arch]
    shapes = list(registry.SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    if args.calibrate:
        run_calibration(archs, shapes, meshes, force=args.force,
                        seq_parallel=args.seq_parallel)
        return 0

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape_name in shapes:
            for multi in meshes:
                tag = "multi" if multi else "single"
                out = cell_path(arch, shape_name, tag, args.seq_parallel)
                if out.exists() and not args.force:
                    rec = json.loads(out.read_text())
                    print(f"[cached] {arch} x {shape_name} x {tag}: "
                          f"{rec['status']}")
                    n_ok += rec["status"] == "ok"
                    n_skip += rec["status"] == "skipped"
                    n_fail += rec["status"] == "failed"
                    continue
                try:
                    rec = lower_cell(arch, shape_name, multi,
                                     seq_parallel=args.seq_parallel)
                except Exception as e:  # a failure here is a sharding bug
                    rec = {
                        "arch": arch, "shape": shape_name, "mesh": tag,
                        "status": "failed", "error": str(e)[:2000],
                        "traceback": traceback.format_exc()[-4000:],
                    }
                out.write_text(json.dumps(rec, indent=2))
                status = rec["status"]
                n_ok += status == "ok"
                n_skip += status == "skipped"
                n_fail += status == "failed"
                if status == "ok":
                    mem_gb = rec["memory"].get("temp_size_in_bytes", 0) / 1e9
                    print(
                        f"[ok] {arch} x {shape_name} x {tag}: "
                        f"compile {rec['compile_seconds']}s, "
                        f"temp {mem_gb:.2f} GB/dev, "
                        f"coll {rec['collectives']['total_bytes']/1e9:.2f} GB"
                    )
                elif status == "skipped":
                    print(f"[skip] {arch} x {shape_name} x {tag}: "
                          f"{rec['reason']}")
                else:
                    print(f"[FAIL] {arch} x {shape_name} x {tag}: "
                          f"{rec['error'][:200]}")
    print(f"\ndry-run summary: {n_ok} ok, {n_skip} skipped, {n_fail} failed")
    return 0 if n_fail == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())

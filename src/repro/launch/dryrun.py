import os
# detlint: allow[ENV001] launcher-side bootstrap: XLA_FLAGS must be in
# the environment before any jax import locks the device count
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).

"""Multi-pod dry-run: lower + compile every (architecture × input shape ×
mesh) cell and extract the roofline terms from the compiled artifact.

Per cell, three cheap compiles (instead of one expensive 64-layer unroll):

  1. full model, scan-over-layers     -> exact per-device memory_analysis()
     (weights fully resident; activations bounded by the scan body);
  2. depth-1 unrolled                 -> base FLOPs/bytes/collective bytes;
  3. depth-2 unrolled                 -> per-layer increment.

Totals = base + (depth-1)·increment. This is exact for homogeneous stacks
(all layers identical shapes) and sidesteps XLA's cost_analysis not
multiplying while-loop trip counts (verified experimentally; see
EXPERIMENTS.md §Dry-run). ``--mode unroll`` cross-checks with a full unroll.

Collective bytes are parsed from the post-SPMD compiled HLO text: operand
payloads of all-reduce / all-gather / reduce-scatter / all-to-all /
collective-permute (async -start counted once, -done skipped).
"""
import argparse
import dataclasses
import json
import re
import traceback

import numpy as np

import jax
import jax.numpy as jnp

from repro.config import (
    ShapeConfig,
    ShardingPlan,
    TPU_V5E,
)
from repro.configs import ASSIGNED, get_arch
from repro.launch import partitioning as parts
from repro.launch.hostenv import (enable_compile_cache, host_timer,
                                  maybe_preload_tcmalloc)
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.serve import make_serve_step
from repro.launch.train import jit_train_step
from repro.models import registry as models
from repro.optim import adamw

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_OP_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\w+)\[([\d,]*)\][^\s]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> dict:
    """Per-device payload bytes by collective kind (result-shape payloads)."""
    out = {k: 0 for k in _COLL_KINDS}
    counts = {k: 0 for k in _COLL_KINDS}
    for m in _OP_RE.finditer(hlo_text):
        tuple_shapes, dtype, dims, kind, _start = m.groups()
        if tuple_shapes is not None:
            nb = sum(_shape_bytes(dt, dm)
                     for dt, dm in _SHAPE_RE.findall(tuple_shapes))
        else:
            nb = _shape_bytes(dtype, dims)
        out[kind] += nb
        counts[kind] += 1
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


# Ops whose bytes are dtype/layout *plumbing*: on TPU they fuse into their
# consumers (bf16 is MXU-native; converts/copies/selects around sharded
# dynamic-update-slice become masked in-place writes). The XLA *CPU*
# backend materializes them at top level (it upcasts bf16 dots to f32),
# inflating "bytes accessed". memory_adjusted subtracts operand+result
# (≈2× result) bytes of *top-level* plumbing ops — ops inside fusion bodies
# are already free in cost_analysis. The raw spec-faithful term is always
# reported alongside.
_PLUMB_RE = re.compile(
    r"(%?[\w.-]*)\s*=\s*(\w+)\[([\d,]*)\][^\s]*\s+"
    r"(convert|copy|transpose|select|dynamic-update-slice|fusion)\(")
_PLUMB_NAMES = ("convert", "copy", "transpose", "select",
                "dynamic-update-slice", "dynamic_update_slice")


def plumbing_bytes(hlo_text: str) -> int:
    total = 0
    in_fusion = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.endswith("{"):           # computation header
            in_fusion = "fused_computation" in stripped
        if in_fusion:
            continue
        m = _PLUMB_RE.search(line)
        if not m:
            continue
        name, dtype, dims, op = m.groups()
        if op == "fusion" and not any(k in name for k in _PLUMB_NAMES):
            continue                          # real compute fusion
        total += 2 * _shape_bytes(dtype, dims)
    return int(total)


# ---------------------------------------------------------------------------
# Cell compilation
# ---------------------------------------------------------------------------

def _depth_knobs(cfg) -> dict[str, tuple[int, int]]:
    """knob -> (base_depth, full_depth); increments are one base unit."""
    knobs = {}
    if cfg.is_encdec:
        knobs["n_layers"] = (1, cfg.n_layers)
        knobs["encoder_layers"] = (1, cfg.encoder_layers)
    elif cfg.family == "hybrid":
        knobs["n_layers"] = (cfg.attn_every, cfg.n_layers)
    else:
        knobs["n_layers"] = (1, cfg.n_layers)
    return knobs


def _build_target(cfg, shape: ShapeConfig, mesh, plan: ShardingPlan):
    """Returns (lower_fn, example_args) for the cell's step function."""
    if shape.kind == "train":
        optimizer = adamw(1e-4)
        p_sds = models.param_specs(cfg)
        o_sds = jax.eval_shape(optimizer.init, p_sds)
        jitted = jit_train_step(cfg, shape, mesh, plan, optimizer, o_sds)
        b_sds = models.input_specs(cfg, shape)
        return jitted, (p_sds, o_sds, b_sds)

    serve_cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)
    p_sds = models.param_specs(serve_cfg)

    if shape.kind == "prefill":
        b_specs = parts.batch_pspecs(serve_cfg, shape, mesh)
        p_specs = parts.param_pspecs(serve_cfg, mesh, plan)

        def fwd(params, batch):
            return models.forward(params, serve_cfg, batch)

        jitted = jax.jit(
            fwd,
            in_shardings=(parts.to_named(mesh, p_specs),
                          parts.to_named(mesh, b_specs)))
        b_sds = models.input_specs(serve_cfg, shape)
        return jitted, (p_sds, b_sds)

    # decode
    ins = models.input_specs(serve_cfg, shape)
    jitted = make_serve_step(serve_cfg, shape, mesh, ins["cache"], plan)
    return jitted, (p_sds, ins["tokens"], ins["cache"])


def compile_cell(cfg, shape: ShapeConfig, mesh, plan: ShardingPlan):
    """lower().compile() one cell; returns (compiled, lowered)."""
    from repro.models import meshctx
    with meshctx.use_mesh(mesh):
        jitted, args = _build_target(cfg, shape, mesh, plan)
        lowered = jitted.lower(*args)
        return lowered.compile(), lowered


def _cost(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, list):
        ca = ca[0]
    return {"flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0))}


def _memory(compiled) -> dict:
    ma = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    return {k: int(getattr(ma, k, 0)) for k in keys}


def analyze_cell(arch_id: str, shape: ShapeConfig, mesh, mesh_name: str,
                 plan: ShardingPlan, mode: str = "scan2",
                 verbose: bool = True,
                 cfg_overrides: dict | None = None) -> dict:
    """Compile + roofline-term extraction for one (arch, shape, mesh)."""
    spec = get_arch(arch_id)
    cfg = spec.model
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    t0 = host_timer()

    # --- 1. full-depth scan compile: memory analysis + proof it compiles ---
    cfg_scan = dataclasses.replace(cfg, scan_layers=True)
    compiled, lowered = compile_cell(cfg_scan, shape, mesh, plan)
    mem = _memory(compiled)
    scan_cost = _cost(compiled)
    scan_coll = collective_bytes(compiled.as_text())
    if verbose:
        print(f"    memory_analysis: {compiled.memory_analysis()}")
        print(f"    cost_analysis(scan): flops={scan_cost['flops']:.3e} "
              f"bytes={scan_cost['bytes']:.3e}")

    if mode == "scan":
        flops, bytes_, coll = (scan_cost["flops"], scan_cost["bytes"],
                               scan_coll)
        plumb = plumbing_bytes(compiled.as_text())
    elif mode == "unroll":
        cfg_u = dataclasses.replace(cfg, scan_layers=False,
                                    unroll_scans=True)
        compiled_u, _ = compile_cell(cfg_u, shape, mesh, plan)
        cu = _cost(compiled_u)
        flops, bytes_ = cu["flops"], cu["bytes"]
        txt_u = compiled_u.as_text()
        coll = collective_bytes(txt_u)
        plumb = plumbing_bytes(txt_u)
    else:  # scan2: depth-1 + depth-2 unrolled, scale per-layer increments
        knobs = _depth_knobs(cfg)
        base_over = {k: b for k, (b, _) in knobs.items()}
        cfg_b = dataclasses.replace(cfg, scan_layers=False,
                                    unroll_scans=True, **base_over)
        comp_b, _ = compile_cell(cfg_b, shape, mesh, plan)
        cost_b = _cost(comp_b)
        txt_b = comp_b.as_text()
        coll_b = collective_bytes(txt_b)
        plumb_b = plumbing_bytes(txt_b)
        flops, bytes_ = cost_b["flops"], cost_b["bytes"]
        plumb = plumb_b
        coll_total = dict(coll_b["bytes"])
        coll_counts = dict(coll_b["counts"])
        for k, (b, full) in knobs.items():
            reps = (full - b) // b          # additional base-units
            if reps <= 0:
                continue
            cfg_k = dataclasses.replace(cfg, scan_layers=False,
                                        unroll_scans=True,
                                        **{**base_over, k: 2 * b})
            comp_k, _ = compile_cell(cfg_k, shape, mesh, plan)
            cost_k = _cost(comp_k)
            txt_k = comp_k.as_text()
            coll_k = collective_bytes(txt_k)
            plumb += reps * (plumbing_bytes(txt_k) - plumb_b)
            flops += reps * (cost_k["flops"] - cost_b["flops"])
            bytes_ += reps * (cost_k["bytes"] - cost_b["bytes"])
            for kind in _COLL_KINDS:
                coll_total[kind] += reps * (coll_k["bytes"][kind]
                                            - coll_b["bytes"][kind])
                coll_counts[kind] += reps * (coll_k["counts"][kind]
                                             - coll_b["counts"][kind])
        coll = {"bytes": coll_total, "counts": coll_counts,
                "total_bytes": int(sum(coll_total.values()))}

    # --- roofline terms (per-device quantities; v5e constants) -------------
    hw = TPU_V5E
    n_chips = int(np.prod(mesh.devices.shape))
    compute_s = flops / hw.peak_flops_bf16
    memory_s = bytes_ / hw.hbm_bw
    memory_adj_s = max(0.0, bytes_ - plumb) / hw.hbm_bw
    collective_s = coll["total_bytes"] / hw.ici_bw
    dominant = max((("compute", compute_s), ("memory", memory_s),
                    ("collective", collective_s)), key=lambda kv: kv[1])[0]
    mf = models.model_flops(cfg, shape)
    mf_per_dev = mf / n_chips
    useful = mf_per_dev / flops if flops else 0.0

    result = {
        "arch": arch_id, "shape": shape.name, "mesh": mesh_name,
        "mesh_shape": list(mesh.devices.shape), "n_chips": n_chips,
        "plan": dataclasses.asdict(plan), "mode": mode,
        "kind": shape.kind,
        "compile_s": round(host_timer() - t0, 1),
        "memory": mem,
        "hbm_per_device_gb": round((mem["argument_size_in_bytes"]
                                    + mem["temp_size_in_bytes"]) / 2**30, 3),
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "plumbing_bytes_per_device": plumb,
        "collectives": coll,
        "terms_s": {"compute": compute_s, "memory": memory_s,
                    "collective": collective_s,
                    "memory_adjusted": memory_adj_s},
        "dominant": dominant,
        "model_flops_total": mf,
        "useful_flops_ratio": useful,
        "scan_cost_raw": scan_cost,
    }
    return result


# ---------------------------------------------------------------------------
# Main sweep
# ---------------------------------------------------------------------------

def iter_cells(arch_ids=None):
    for spec in ASSIGNED:
        if arch_ids and spec.arch_id not in arch_ids:
            continue
        for shape, ok, why in spec.cells():
            yield spec.arch_id, shape, ok, why


def main(argv=None):
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", nargs="*", default=None)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both", "tiny"])
    ap.add_argument("--plan", default="zero1",
                    choices=["none", "zero1", "zero3"])
    ap.add_argument("--mode", default="scan2",
                    choices=["scan2", "scan", "unroll"])
    ap.add_argument("--partition", default="balanced")
    ap.add_argument("--out", default="dryrun_results")
    ap.add_argument("--set", nargs="*", default=[],
                    help="ModelConfig overrides key=value for every cell")
    ap.add_argument("--stop_on_error", action="store_true")
    args = ap.parse_args(argv)

    overrides = {}
    for kv in args.set:
        k, v = kv.split("=", 1)
        overrides[k] = {"True": True, "False": False}.get(v, v)

    meshes = []
    if args.mesh in ("single", "both"):
        meshes.append(("single_pod_16x16", make_production_mesh()))
    if args.mesh in ("multi", "both"):
        meshes.append(("multi_pod_2x16x16",
                       make_production_mesh(multi_pod=True)))
    if args.mesh == "tiny":
        meshes.append(("tiny_2x2x2", make_mesh((2, 2, 2),
                                               ("pod", "data", "model"))))

    plan = ShardingPlan(grad_sharding=args.plan, partition=args.partition)
    os.makedirs(args.out, exist_ok=True)
    summary = []
    n_ok = n_skip = n_fail = 0
    for mesh_name, mesh in meshes:
        for arch_id, shape, ok, why in iter_cells(args.arch):
            if args.shape and shape.name not in args.shape:
                continue
            cell = f"{arch_id} x {shape.name} x {mesh_name}"
            if not ok:
                print(f"[SKIP] {cell}: {why}")
                summary.append({"arch": arch_id, "shape": shape.name,
                                "mesh": mesh_name, "status": "skip",
                                "reason": why})
                n_skip += 1
                continue
            print(f"[CELL] {cell} (plan={args.plan}, mode={args.mode})")
            try:
                r = analyze_cell(arch_id, shape, mesh, mesh_name, plan,
                                 args.mode, cfg_overrides=overrides or None)
                r["status"] = "ok"
                t = r["terms_s"]
                print(f"    terms: compute={t['compute']*1e3:.2f}ms "
                      f"memory={t['memory']*1e3:.2f}ms "
                      f"collective={t['collective']*1e3:.2f}ms "
                      f"dominant={r['dominant']} "
                      f"useful={r['useful_flops_ratio']:.2f} "
                      f"hbm={r['hbm_per_device_gb']:.2f}GB "
                      f"({r['compile_s']}s)")
                fn = os.path.join(
                    args.out,
                    f"{mesh_name}__{arch_id}__{shape.name}__{args.plan}.json")
                with open(fn, "w") as f:
                    json.dump(r, f, indent=1)
                summary.append(r)
                n_ok += 1
            except Exception as e:
                n_fail += 1
                print(f"[FAIL] {cell}: {type(e).__name__}: {e}")
                traceback.print_exc()
                summary.append({"arch": arch_id, "shape": shape.name,
                                "mesh": mesh_name, "status": "fail",
                                "error": f"{type(e).__name__}: {e}"})
                if args.stop_on_error:
                    raise

    with open(os.path.join(args.out, f"summary_{args.mesh}_{args.plan}.json"),
              "w") as f:
        json.dump(summary, f, indent=1)
    print(f"\n[dryrun] ok={n_ok} skip={n_skip} fail={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    maybe_preload_tcmalloc()
    enable_compile_cache()
    raise SystemExit(main())

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this lowers the appropriate step (train_step for train shapes,
prefill_step for prefill, serve_step for decode/long shapes) against
ShapeDtypeStruct inputs on the production mesh, compiles it, and records:

  * memory_analysis()  — per-device bytes (proves the plan fits),
  * cost_analysis()    — HLO FLOPs / bytes accessed,
  * the collective-byte breakdown parsed from the compiled HLO,

into a JSON artifact consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out f.json]
"""
import argparse
import json
import re
import sys
import time
import traceback
from collections import Counter

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import (ASSIGNED_ARCHS, SHAPES, cell_is_runnable,
                                get_config)
from repro.launch.mesh import make_production_mesh, mesh_dims
from repro.parallel import specs as SP
from repro.parallel.runner import (Cell, batch_struct, make_prefill_step,
                                   make_serve_step, make_train_step,
                                   resolve_cell, _serve_state)


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStructs — no allocation)
# ---------------------------------------------------------------------------


def input_specs(cell: Cell, mesh):
    """ShapeDtypeStruct stand-ins + NamedShardings for one step's inputs."""
    bstruct, bspecs = batch_struct(cell)
    shard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}
    return bstruct, shard


def param_specs(cell: Cell, mesh):
    struct, spec = SP.param_struct_and_specs(
        cell.mdef, cell.plan.pp, cell.data_size, cell.dtype)
    shards = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec)
    return struct, shards


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of every collective op in the (compiled) HLO."""
    kinds = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
    out = {k: 0 for k in kinds}
    counts = Counter()
    dt_bytes = {"f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f64": 8,
                "s8": 1, "u8": 1, "pred": 1, "s64": 8, "u64": 8, "f8e4m3": 1,
                "f8e5m2": 1, "s16": 2, "u16": 2}
    shape_re = re.compile(r"(\w+)\[([\d,]*)\]")
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.*)", ls)
        body = m.group(1) if m else ls
        for k in kinds:
            if f"{k}-start" in body or re.search(rf"\b{k}\b", body.split("(")[0]):
                # output shape(s) at the head of the instruction
                shapes = shape_re.findall(body.split("(")[0])
                b = 0
                for dt, dims in shapes:
                    if dt not in dt_bytes:
                        continue
                    n = 1
                    for dd in dims.split(","):
                        if dd:
                            n *= int(dd)
                    b += n * dt_bytes[dt]
                if b:
                    out[k] += b
                    counts[k] += 1
                break
    out["counts"] = dict(counts)
    return out


def run_cell(arch: str, shape_name: str, mesh, *, verbose=True):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_is_runnable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "status": "skipped",
                "reason": why}
    dims = mesh_dims(mesh)
    t0 = time.time()
    cell = resolve_cell(arch, shape, data_size=dims["data"],
                        model_size=dims["model"], pods=dims["pods"])
    pstruct, pshard = param_specs(cell, mesh)
    bstruct, bshard = input_specs(cell, mesh)

    kind = shape.kind
    if kind == "train":
        from repro.optim import adamw
        step = make_train_step(cell, mesh)
        opt_dtype = jnp.bfloat16 if cell.plan.opt_dtype == "bfloat16" else jnp.float32
        ostruct = jax.eval_shape(
            lambda p: adamw.init_state(
                p, opt_dtype, offload_moments=cell.plan.offload_moments),
            pstruct)
        oshard_specs = SP.opt_specs(
            {"stages": SP.stage_specs(cell.mdef, cell.plan.pp),
             "globals": SP.globals_specs(cell.mdef)},
            zero1_pod=cell.plan.zero1 and dims["pods"] > 1,
            param_struct=pstruct, model_size=dims["model"],
            pods=dims["pods"])
        # plan-driven host residency (DESIGN.md §11): the big-model plans
        # set offload_moments, and the dry-run prices the same placement
        # the executed path deploys — pinned_host, the kind every backend
        # (TPU, GPU and the CPU) exposes, exactly as init_state does
        moment_shard = SP.moment_shardings(
            mesh, oshard_specs,
            offload_moments=cell.plan.offload_moments)
        oshard = type(ostruct)(step=NamedSharding(mesh, P()),
                               m=moment_shard, v=moment_shard)
        args = (pstruct, ostruct, bstruct)
        shards = (pshard, oshard, bshard)
        fn = step
    elif kind == "prefill":
        fn, sstruct, sspecs = make_prefill_step(cell, mesh)
        args = (pstruct, bstruct)
        shards = (pshard, bshard)
    else:  # decode
        fn, _, _ = make_serve_step(cell, mesh)
        _, sstruct_g, sspecs_g = _serve_state(cell)
        sshard = jax.tree_util.tree_map(
            lambda s: NamedSharding(mesh, s), sspecs_g)
        args = (pstruct, sstruct_g, bstruct)
        shards = (pshard, sshard, bshard)

    rec = {"arch": arch, "shape": shape_name,
           "mesh": f"{dims['pods']}x{dims['data']}x{dims['model']}"
           if dims["pods"] > 1 else f"{dims['data']}x{dims['model']}",
           "plan": {"dp": cell.plan.dp, "pp": cell.plan.pp,
                    "sp": cell.plan.sp, "n_chunks": cell.sched.n,
                    "grad_accum": cell.plan.grad_accum,
                    "offload": cell.plan.offload,
                    "offload_mode": cell.plan.offload_mode,
                    "prefetch": cell.plan.prefetch},
           "alphas": list(cell.alphas)}
    donate = (0, 1) if kind == "train" else ((1,) if kind == "decode" else ())
    try:
        # jaxpr-level collective accounting: dtype-faithful and scan-exact
        # (compiled-HLO numbers suffer two XLA-CPU artifacts — see
        # launch/jaxpr_cost.py)
        from repro.launch.jaxpr_cost import collective_bytes as _jc
        jc = _jc(fn, *args, axis_sizes={
            "model": dims["model"], "data": dims["data"],
            "pod": dims["pods"]})
        lowered = jax.jit(fn, in_shardings=shards,
                          donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_comp = time.time() - t0 - t_lower
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis() or {}
        txt = compiled.as_text()
        from repro.launch import hlo_cost
        hc = hlo_cost.analyze(txt)
        # the f32-upcast artifact cannot exceed ~3x the per-device bf16
        # param bytes (f32 copy = 2x + one layout copy) — cap the textual
        # estimate so big f32 activations are never misattributed
        import numpy as _np
        pdev = (sum(int(_np.prod(l.shape)) * l.dtype.itemsize
                    for l in jax.tree_util.tree_leaves(pstruct["stages"]))
                / (dims["data"] * dims["model"])
                + sum(int(_np.prod(l.shape)) * l.dtype.itemsize
                      for l in jax.tree_util.tree_leaves(pstruct["globals"]))
                / dims["model"])
        hc["cpu_upcast_artifact_bytes"] = min(
            hc["cpu_upcast_artifact_bytes"], 3.0 * pdev)
        coll = {k: v for k, v in hc["collectives"].items()}
        rec.update({
            "status": "ok",
            "lower_s": round(t_lower, 1),
            "compile_s": round(t_comp, 1),
            "memory": {
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "host_temp_bytes": ma.host_temp_size_in_bytes,
                "host_argument_bytes": ma.host_argument_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            # raw module-level numbers (scan bodies counted ONCE — see
            # launch/hlo_cost.py for why these undercount)
            "flops_module_raw": ca.get("flops", 0.0),
            "bytes_module_raw": ca.get("bytes accessed", 0.0),
            # trip-count-corrected (the roofline inputs)
            "dot_flops": hc["dot_flops"],
            "dot_bytes": hc["dot_bytes"],
            # compiled-HLO collective view (CPU-promoted dtypes)
            "collectives": coll,
            "collective_bytes_hlo": hc["collective_bytes_total"],
            # jaxpr view: dtype-faithful + exact scan trips (roofline input)
            "collectives_jaxpr": jc["kinds"],
            "collective_bytes": jc["total"],
            # XLA-CPU bf16->f32 weight upcasts (absent on TPU): subtract for
            # the TPU-projected device memory (see launch/hlo_cost.py)
            "cpu_upcast_artifact_bytes": hc["cpu_upcast_artifact_bytes"],
        })
        if verbose:
            dev_gb = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                      + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 2**30
            print(f"  OK  lower {t_lower:5.1f}s compile {t_comp:6.1f}s  "
                  f"dot-flops {hc['dot_flops']:.3e}  dev-mem {dev_gb:5.2f} GiB  "
                  f"coll {hc['collective_bytes_total']/2**20:8.1f} MiB")
    except Exception as e:  # noqa
        rec.update({"status": "fail", "error": f"{type(e).__name__}: {e}"})
        if verbose:
            print(f"  FAIL {type(e).__name__}: {str(e)[:300]}")
            traceback.print_exc(limit=8)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    args = ap.parse_args()

    records = []
    meshes = []
    if args.both_meshes:
        meshes = [False, True]
    else:
        meshes = [args.multi_pod]

    for mp in meshes:
        mesh = make_production_mesh(multi_pod=mp)
        label = "multi-pod 2x16x16" if mp else "single-pod 16x16"
        print(f"== mesh {label} ==")
        if args.all:
            cells = [(a, s) for a in ASSIGNED_ARCHS for s in SHAPES]
        else:
            cells = [(args.arch, args.shape)]
        for arch, shape in cells:
            print(f"[{label}] {arch} x {shape}")
            rec = run_cell(arch, shape, mesh)
            records.append(rec)

    with open(args.out, "w") as f:
        json.dump(records, f, indent=1)
    n_ok = sum(r["status"] == "ok" for r in records)
    n_skip = sum(r["status"] == "skipped" for r in records)
    n_fail = sum(r["status"] == "fail" for r in records)
    print(f"\n{n_ok} ok / {n_skip} skipped / {n_fail} FAILED -> {args.out}")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

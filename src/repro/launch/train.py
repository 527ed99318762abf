"""End-to-end training driver.

The same code path drives a reduced config on CPU (the quickstart / CI run)
and a full config on a real TPU mesh — only the mesh and config change.

  PYTHONPATH=src python -m repro.launch.train \
      --arch qwen2-7b --reduced --steps 50 --mesh 2x2 \
      --seq 256 --batch 8 --ckpt-dir /tmp/ckpt --resume auto

Features exercised: SPPO chunked pipeline with adaptive offload, AdamW with
ZeRO-1/bf16 knobs, async sharded checkpointing + auto-resume, straggler
watchdog, TGS/MFU metering.  ``--trace-dir DIR`` writes a profiler trace of
``--trace-steps`` steps (after step 0): device operations named by the
program's layer scopes (runtime/scopes.py) and host spans ``sppo.step``
with ``sppo.batch``, ``sppo.dispatch``, ``sppo.block`` and ``sppo.ckpt``
inside it.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ShapeConfig, get_config
from repro.data.pipeline import SyntheticLM, make_context_stub, shard_batch
from repro.launch.mesh import make_test_mesh, mesh_dims
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.parallel import specs as SP
from repro.parallel.runner import (batch_struct, make_loss_step,
                                   make_train_step, resolve_cell)
from repro.runtime import hostmem
from repro.runtime.fault_tolerance import RestartSupervisor, StepWatchdog
from repro.runtime.metrics import (Meter, attention_flops, compile_stats,
                                   peak_flops)

log = logging.getLogger("repro.train")

# <repo>/.jax_cache: a fixed path, so a second identical run finds its
# compiled programs (the path is part of the cache key)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), *[os.pardir] * 3, ".jax_cache")


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in $JAX_COMPILATION_CACHE_DIR
    when that is set (JAX reads it itself), else in DEFAULT_CACHE_DIR."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.normpath(DEFAULT_CACHE_DIR))
    return jax.config.jax_compilation_cache_dir


def build_params(cell, mesh):
    """Initialize real parameters laid out per specs (stage-major stacking)."""
    mdef, plan = cell.mdef, cell.plan
    dims = mesh_dims(mesh)
    key = jax.random.PRNGKey(0)
    stages = [mdef.init_stage_params(key, s, plan.pp, cell.dtype)
              for s in range(plan.pp)]
    stacked = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([ls[i % plan.pp] for i in range(dims["data"])]),
        *stages)
    params = {"stages": stacked, "globals": mdef.init_globals(key, cell.dtype)}
    _, pspecs = SP.param_struct_and_specs(mdef, plan.pp, dims["data"],
                                          cell.dtype)
    shard = jax.tree_util.tree_map(lambda s: NamedSharding(mesh, s), pspecs)
    # transfer-lint: ok (initial param placement onto the mesh)
    params = jax.tree_util.tree_map(jax.device_put, params, shard)
    return params, pspecs, shard


def main(argv=None, *, report=None, loss_only=False):
    """Train; returns the per-step history.  A `report` dict, when given,
    receives what the run set up: the plan, the host kind, the compiled
    step's compile time, Pallas kernel count and memory analysis, the
    moments' memory kinds and the device's peak memory after the last step.
    ``loss_only`` returns instead the forward-only loss of step 0's batch on
    the initial parameters (the loss step 0 reports, without a backward)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the model's depth to N layers; every other "
                         "width stays as configured")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 4x2")
    ap.add_argument("--pp", type=int, default=None)
    ap.add_argument("--n-chunks", type=int, default=None)
    ap.add_argument("--no-offload", action="store_true")
    ap.add_argument("--offload-moments", action="store_true",
                    help="keep AdamW m/v host-resident (executed "
                         "ZeRO-Offload analogue, DESIGN.md §11)")
    ap.add_argument("--offload-dtype", default=None,
                    choices=["none", "fp8", "int8"],
                    help="compress the act_off host rows (DESIGN.md §14): "
                         "quantize on D2H to fp8_e4m3/int8 with per-row "
                         "fp32 scales, dequantize inside the backward")
    ap.add_argument("--moments-dtype", default=None,
                    choices=["none", "fp8", "int8"],
                    help="compressed host residency for the AdamW moments "
                         "(implies --offload-moments): host leaves become "
                         "(payload, per-row scale)")
    ap.add_argument("--prefetch", default=None, choices=["ahead", "sync"],
                    help="backward-reload placement on the explicit offload "
                         "path (DESIGN.md §12): ahead = one-chunk-ahead H2D "
                         "via the tick-level custom_vjp seam (default); "
                         "sync = autodiff placement, each chunk reloads at "
                         "its own backward")
    ap.add_argument("--attn-mode", default=None,
                    choices=["gather_q", "gather_kv", "auto", "ring",
                             "local"],
                    help="distributed attention schedule (DESIGN.md §15): "
                         "gather_q = flash-decoding merge (default); "
                         "gather_kv = all-gather the KV shard; auto = "
                         "byte-count switch; ring = rotate KV blocks via "
                         "ppermute (beyond-one-stage contexts); local = no "
                         "attention collectives (model axis 1 only)")
    ap.add_argument("--msp", action="store_true",
                    help="multiplexed sequence partitioning (pp > 1 only). "
                         "NOTE: on the lock-step SPMD runner the ramp "
                         "sub-chunks recompute their full chunk, so this "
                         "validates the schedule but costs extra compute "
                         "per step (DESIGN.md §2)")
    ap.add_argument("--msp-split", type=int, default=2,
                    help="sub-chunks per MSP ramp chunk")
    ap.add_argument("--audit", action="store_true",
                    help="statically audit the resolved cell before "
                         "training (analysis/audit.py, DESIGN.md §17): "
                         "trace the step over ShapeDtypeStructs and prove "
                         "the offload/pipeline contracts R1-R5; exit 2 on "
                         "any finding")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", default="none", choices=["none", "auto"])
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--trace-dir", "--trace_dir", dest="trace_dir",
                    default=None,
                    help="write a jax.profiler trace of --trace-steps "
                         "steps, from the step after the first, to DIR")
    ap.add_argument("--trace-steps", "--trace_steps", dest="trace_steps",
                    type=int, default=2)
    args = ap.parse_args(argv)
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    use_compile_cache()
    data_size, model_size = (int(x) for x in args.mesh.split("x"))
    mesh = make_test_mesh(data_size, model_size)
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.layers is not None:
        log.info("depth cut: n_layers %d -> %d", cfg.n_layers, args.layers)
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    mdef = build_model(cfg)
    shape = ShapeConfig("cli_train", args.seq, args.batch, "train")
    overrides = {}
    if args.pp:
        overrides["pp"] = args.pp
        overrides["dp"] = data_size // args.pp
    if args.n_chunks:
        overrides["n_chunks"] = args.n_chunks
    if args.no_offload:
        overrides["offload"] = False
    if args.offload_moments:
        overrides["offload_moments"] = True
    if args.prefetch:
        overrides["prefetch"] = args.prefetch
    if args.offload_dtype:
        overrides["offload_dtype"] = args.offload_dtype
    if args.moments_dtype:
        overrides["moments_dtype"] = args.moments_dtype
        if args.moments_dtype != "none":
            # compressed moments imply the host-residency path
            overrides.setdefault("offload_moments", True)
    if args.attn_mode:
        overrides["attn_mode"] = args.attn_mode
    if args.msp:
        overrides["msp"] = True
        overrides["msp_split"] = args.msp_split
        log.warning("msp: ramp sub-chunks recompute their full chunk on the "
                    "SPMD runner — schedule validation mode, expect extra "
                    "compute per step (DESIGN.md §2)")
    cell = resolve_cell(mdef, shape, data_size=data_size,
                        model_size=model_size, overrides=overrides or None)
    if args.msp and cell.plan.pp == 1:
        ap.error("--msp needs a pipeline (resolved plan has pp=1); "
                 "pass --pp > 1 or a mesh/shape that maps to pp > 1")
    log.info("plan: %s  chunks=%s alphas=%s", cell.plan, cell.sched.lengths,
             [round(a, 3) for a in cell.alphas])
    report = {} if report is None else report
    report.update(chunks=list(cell.sched.lengths), alphas=list(cell.alphas))

    if args.audit:
        # preflight contract audit (DESIGN.md §17): trace-only, so a broken
        # offload/pipeline dataflow fails here before any memory is spent
        from repro.analysis.audit import audit_cell
        from repro.analysis.report import format_report

        rep = audit_cell(cell, data_size=data_size, model_size=model_size,
                         name=f"{args.arch}/cli_train")
        print(format_report(rep))
        if not rep.clean:
            raise SystemExit(2)
        log.info("audit clean: %s", ", ".join(rep.traces))

    params, pspecs, pshard = build_params(cell, mesh)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch)
    bstruct, bspecs = batch_struct(cell)
    bshard = {k: NamedSharding(mesh, s) for k, s in bspecs.items()}

    nctx_pad = None
    if cfg.cross_attn is not None:
        n_ctx = (cfg.n_frames if cfg.encoder_layers
                 else cfg.cross_attn.n_context_tokens)
        nctx_pad = -(-n_ctx // cell.plan.sp) * cell.plan.sp

    def make_batch(step: int):
        tokens, labels = data.sample_step(step)
        batch = shard_batch(tokens, labels, pods=cell.pods,
                            data_size=data_size, pp=cell.plan.pp)
        if nctx_pad is not None:
            batch["context"] = make_context_stub(
                batch, b_loc=cell.b_loc, pods=cell.pods,
                data_size=data_size, n_ctx_pad=nctx_pad,
                d_model=cfg.d_model, seed=step,
                dtype=np.float32).astype(jnp.bfloat16
                                         if cell.dtype == jnp.bfloat16
                                         else np.float32)
        # transfer-lint: ok (train batch staging onto the mesh)
        return {k: jax.device_put(v, bshard[k]) for k, v in batch.items()}

    if loss_only:
        loss = float(jax.jit(make_loss_step(cell, mesh))(params, make_batch(0)))
        log.info("forward-only loss of batch 0: %.4f", loss)
        return [{"step": 0, "loss": loss}]

    opt_dtype = (jnp.bfloat16 if cell.plan.opt_dtype == "bfloat16"
                 else jnp.float32)
    # moments are born in host memory when the plan offloads them — no
    # device-side opt_dtype copy of the params ever materializes at init
    opt_state = adamw.init_state(
        params, opt_dtype, offload_moments=cell.plan.offload_moments,
        moments_dtype=cell.plan.moments_dtype)
    if cell.plan.offload_moments:
        report["host_kind"] = hostmem.host_memory_kind()
        log.info("optimizer moments host-resident (kind=%s, dtype=%s)",
                 report["host_kind"], cell.plan.moments_dtype)
    step_fn = jax.jit(
        make_train_step(cell, mesh,
                        lr_kwargs=dict(peak=args.lr, warmup=20,
                                       total=max(args.steps, 100))),
        donate_argnums=(0, 1))

    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    start = 0
    if ckpt and args.resume == "auto" and ckpt.latest_step() is not None:
        (params, opt_state), start, extra = ckpt.restore((params, opt_state))
        data.load_state_dict(extra.get("data", data.state_dict()))
        log.info("resumed from step %d", start)

    meter = Meter(n_chips=data_size * model_size,
                  tokens_per_step=args.batch * args.seq,
                  n_active_params=SP.count_matmul_params(mdef, cell.plan.pp,
                                                         data_size),
                  peak_flops=peak_flops(mesh.devices.flat[0]),
                  attn_flops_per_step=attention_flops(
                      cfg, batch=args.batch, seq=args.seq))
    watchdog = StepWatchdog()

    # compile up front (jit dispatch reuses this executable): set-up time,
    # reported apart from the steps.  The state is then placed where the
    # step returns it, so step 0's inputs match every later step's and
    # nothing compiles inside the loop: the fresh step counter is
    # uncommitted, and XLA's CPU backend returns host-annotated moments in
    # device memory.
    n0, t0 = compile_stats(), time.perf_counter()
    batch0 = make_batch(start)
    compiled = step_fn.lower(params, opt_state, batch0).compile()
    out_shard = tuple(compiled.output_shardings[:2])
    if jax.tree.map(lambda a: a.sharding, (params, opt_state)) != out_shard:
        # transfer-lint: ok (state placed into the step's output shardings)
        params, opt_state = jax.device_put((params, opt_state), out_shard)
        compiled = step_fn.lower(params, opt_state, batch0).compile()
    n1 = compile_stats()
    report.update(compile_s=time.perf_counter() - t0,
                  compiles=n1[0] - n0[0], cache_hits=n1[1] - n0[1],
                  pallas_kernels=compiled.as_text().count("tpu_custom_call"),
                  memory_analysis=compiled.memory_analysis())
    log.info("step compiled in %.1fs (%d programs, %d of them from the "
             "persistent cache; %d Pallas kernels)", report["compile_s"],
             report["compiles"], report["cache_hits"],
             report["pallas_kernels"])

    # --trace-dir: a profiler trace of --trace-steps steps from the step
    # after the first (the first may still load or compile)
    trace_from = start + 1
    trace_to = min(trace_from + args.trace_steps, args.steps)

    def loop(resume_step: int):
        nonlocal params, opt_state
        data.state.step = resume_step
        for step in range(resume_step, args.steps):
            if args.trace_dir and step == trace_from < trace_to:
                jax.profiler.start_trace(args.trace_dir)
            with jax.profiler.StepTraceAnnotation("sppo.step",
                                                  step_num=step):
                with jax.profiler.TraceAnnotation("sppo.batch"):
                    batch = make_batch(step)
                meter.start()
                with jax.profiler.TraceAnnotation("sppo.dispatch"):
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch)
                with jax.profiler.TraceAnnotation("sppo.block"):
                    jax.block_until_ready((params, opt_state))
                    loss = float(metrics["loss"])
                rec = meter.stop(step, loss)
                watchdog.observe(step, rec["dt"])
                if step % args.log_every == 0 or step == args.steps - 1:
                    mfu = ("n/a" if rec["mfu"] is None
                           else f"{rec['mfu']:.3f}")
                    log.info("step %4d  loss %.4f  %.3fs  tgs %.1f  mfu %s  "
                             "gnorm %.3f  compiles %d", step, loss, rec["dt"],
                             rec["tgs"], mfu, float(metrics["grad_norm"]),
                             rec["compiles"])
                if ckpt and ((step + 1) % args.ckpt_every == 0
                             or step == args.steps - 1):
                    with jax.profiler.TraceAnnotation("sppo.ckpt"):
                        ckpt.save(step + 1, (params, opt_state),
                                  extra={"data": data.state_dict()})
            if args.trace_dir and step + 1 == trace_to > trace_from:
                jax.profiler.stop_trace()
                log.info("trace of steps %d-%d written to %s", trace_from,
                         trace_to - 1, args.trace_dir)
        if ckpt:
            ckpt.wait()

    sup = RestartSupervisor(checkpointer=ckpt) if ckpt else None
    if sup:
        sup.install_signal_handlers()
        sup.run(loop, start)
    else:
        loop(start)
    report["moment_kinds"] = sorted(
        {hostmem.memory_kind_of(leaf)
         for leaf in jax.tree_util.tree_leaves((opt_state.m, opt_state.v))})
    stats = mesh.devices.flat[0].memory_stats() or {}
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    if args.metrics_out:
        meter.dump(args.metrics_out)
    log.info("done: final loss %.4f (first %.4f)",
             meter.history[-1]["loss"], meter.history[0]["loss"])
    return meter.history


if __name__ == "__main__":
    main()

"""The distributed execution engine: SPPO pipeline inside shard_map.

Builds the three step functions per (arch x shape x mesh) cell:

  train_step(params, opt_state, batch)  -> (params', opt_state', metrics)
  prefill_step(params, batch)           -> (caches, last_hidden)
  serve_step(params, caches, batch)     -> (caches', next_tokens)

Everything distributed runs in one ``shard_map`` over the production mesh;
the optimizer applies outside shard_map on the global (sharded) arrays so
moment host-offload / ZeRO-1 shardings are plain GSPMD annotations.

Pipeline semantics (DESIGN.md §2/§4): at tick t, stage s = data_idx % pp
processes chunk c = t − s; hand-off by ppermute along the data axis within
dp groups; the backward pipeline comes from differentiating the tick loop.
pp == 1 uses exact FLOPs-balanced variable-length chunks with per-chunk
offload ratios; pp > 1 uses equal chunks (lock-step SPMD) with tick-aligned
ratios.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ParallelPlan, ShapeConfig
from repro.core import costmodel as cm
from repro.core import mutation
from repro.core import offload as ofl
from repro.core import partition as part
from repro.core import schedule as sched_mod
from repro.core import simulate as sim_mod
from repro.models import attention as A
from repro.models.model_zoo import ModelDef, build_model
from repro.models.transformer import ChunkMeta
from repro.parallel import specs as SP
from repro.parallel.ctx import Ctx
from repro.parallel.plans import resolve_plan
from repro.runtime import scopes


def shard_map(f, mesh, in_specs, out_specs):
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


DECODE_BUDGET = 128  # extra decode slots beyond the shape's cache length


# ---------------------------------------------------------------------------
# Cell: one fully-resolved (arch x shape x mesh) configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Cell:
    mdef: ModelDef
    plan: ParallelPlan
    shape: ShapeConfig
    pods: int
    data_size: int
    model_size: int
    sched: part.ChunkSchedule
    alphas: tuple
    dtype: Any = jnp.bfloat16
    # document lengths of the packed variable-length batch (empty = the
    # classic uniform layout).  When set, the batch carries a ``doc_start``
    # array and the attention path masks cross-document visibility
    # (DESIGN.md §13).
    doc_lens: tuple = ()

    @property
    def cfg(self) -> ModelConfig:
        return self.mdef.cfg

    @property
    def varlen(self) -> bool:
        return bool(self.doc_lens)

    @property
    def b_loc(self) -> int:
        return max(1, self.shape.global_batch // (self.pods * self.plan.dp))

    @property
    def cache_loc(self) -> int:
        s = self.shape.seq_len
        # prefill leaves room for subsequent decode appends (same geometry,
        # so a prefill cache feeds serve_step directly)
        extra = (DECODE_BUDGET * self.plan.sp
                 if self.shape.kind in ("decode", "prefill") else 0)
        return (s + extra) // self.plan.sp

    def ctx(self) -> Ctx:
        return Ctx(model_axis="model", data_axis="data",
                   pod_axis="pod" if self.pods > 1 else None,
                   sp=self.plan.sp, dp=self.plan.dp, pp=self.plan.pp,
                   pods=self.pods,
                   attn_mode=self.plan.attn_mode,
                   merge_bf16=self.plan.merge_bf16,
                   grad_compress=self.plan.grad_compress)


def resolve_cell(arch, shape_cfg: ShapeConfig, *, data_size=16, model_size=16,
                 pods=1, overrides=None, hw=cm.V5E, doc_lens=None) -> Cell:
    mdef = arch if isinstance(arch, ModelDef) else build_model(arch)
    cfg = mdef.cfg
    plan = resolve_plan(cfg, shape_cfg, data_size=data_size,
                        model_size=model_size, pods=pods, overrides=overrides)
    n = plan.n_chunks
    doc_lens = tuple(int(x) for x in
                     (doc_lens if doc_lens is not None else ()))
    if shape_cfg.kind == "decode":
        assert not doc_lens, "packed varlen layouts are train/prefill-only"
        # decode has no backward pass: there is no reload window to hide a
        # transfer under, so an offloaded residual could only ever be paid
        # for, never redeemed.  resolve_plan pins offload off for decode
        # shapes; reject overrides that try to turn it back on.
        assert not plan.offload, (
            "decode plans must not offload: a decode step has no backward, "
            "so offloaded activations are never reloaded (DESIGN.md §4)")
        # compressed residency rides the offload channels; with offload
        # pinned off on decode a codec could only quantize tensors that are
        # never offloaded in the first place — reject it as a config error
        # rather than silently ignoring the knob (DESIGN.md §14)
        assert plan.offload_dtype == "none" and plan.moments_dtype == "none", (
            "decode plans must not request compressed residency: with "
            "offload disabled there is no host channel to compress "
            f"(offload_dtype={plan.offload_dtype!r}, "
            f"moments_dtype={plan.moments_dtype!r})")
        sched = part.ChunkSchedule((1,), (0,), 1, "decode")
        alphas = (0.0,)
    else:
        mult = max(model_size, 128) if plan.pp == 1 else model_size
        policy = plan.partition if plan.pp == 1 else "length"
        r = part.flops_per_token_ratio(cfg)
        profile = None
        if doc_lens:
            # histogram-driven packed layout: the cost profile sums the
            # per-row causal sawtooth (cost restarts at every document
            # boundary) over the whole global batch, so chunk boundaries
            # and offload ratios below see the *actual* token/FLOPs mix.
            rows = part.pack_lengths(list(doc_lens), shape_cfg.seq_len)
            row_lens = [[doc_lens[i] for i in row] for row in rows]
            assert len(row_lens) <= shape_cfg.global_batch, (
                f"packing needs {len(row_lens)} rows > global_batch "
                f"{shape_cfg.global_batch}")
            # filler rows up to the global batch are all-padding but still
            # ride the dense matmuls: linear-only cost
            row_lens += [[] for _ in
                         range(shape_cfg.global_batch - len(row_lens))]
            profile = part.packed_cost_profile(row_lens, shape_cfg.seq_len, r)
        if plan.pp > 1:
            assert shape_cfg.seq_len % (n * model_size) == 0
            if plan.msp:
                # ramp sub-chunk loss regions must tile the chunk evenly
                assert (shape_cfg.seq_len // n) % plan.msp_split == 0, (
                    f"chunk len {shape_cfg.seq_len // n} not divisible by "
                    f"msp_split {plan.msp_split}")
                # sub-events recompute their full chunk; that is idempotent
                # for the position-tagged KV cache but NOT for SSM/RWKV
                # recurrent state, which would be advanced `split` times
                # (DESIGN.md §2) — reject stateful-recurrence families
                assert not cfg.sub_quadratic, (
                    f"msp unsupported for family {cfg.family!r}: recurrent "
                    "state updates are not idempotent under full-chunk "
                    "recompute")
            sched = part.partition_length(shape_cfg.seq_len, n)
        elif profile is not None and policy == "flops":
            # Seq1F1B-style FLOPs balance over the packed profile, snapping
            # to aligned document boundaries where one is nearby
            sched = part.partition_profile(
                profile, n, multiple=mult,
                doc_bounds=part.aligned_doc_bounds(row_lens,
                                                   shape_cfg.seq_len))
        else:
            sched = part.partition(shape_cfg.seq_len, n, cfg, policy,
                                   multiple=mult)
        # sequence-aware offload ratios from the cost model (§5.2); packed
        # cells use the measured per-chunk profile sums (already summed over
        # the batch rows), uniform cells the analytic single-sequence costs
        n_params = SP.count_active_params(mdef, plan.pp, data_size)
        if profile is not None:
            costs = [c / max(1, shape_cfg.global_batch)
                     for c in part.profile_chunk_costs(profile, sched)]
        else:
            costs = part.chunk_costs(sched, r)
        scale = (6 * n_params * shape_cfg.global_batch * shape_cfg.seq_len
                 / sum(costs) / (plan.sp * plan.pp * hw.peak_flops_bf16))
        # the §5.2 hiding window is the next chunk's *forward* compute —
        # the same fwd/bwd split the solver plans with (cm.BWD_RATIO); the
        # two sides still differ in launch-overhead and grad-accum terms
        times = [c * scale / (1.0 + cm.BWD_RATIO) for c in costs]
        b_loc = max(1, shape_cfg.global_batch // (pods * plan.dp))
        acts = cm.chunk_act_bytes(cfg, sched.lengths, batch=b_loc,
                                  pp=plan.pp, sp=plan.sp,
                                  grad_accum=plan.grad_accum)
        # compressed residency crosses the link at wire_ratio·A bytes per
        # offloaded row-set, so the α solver sees the effective raw-bytes
        # link rate and can offload more per hiding window (DESIGN.md §14)
        bw_eff = hw.d2h_bw / cm.offload_wire_ratio(plan.offload_dtype)
        alphas = ofl.sequence_aware_alphas(acts, times, bw_eff).alphas
        if not plan.offload:
            alphas = tuple(0.0 for _ in alphas)
    return Cell(mdef=mdef, plan=plan, shape=shape_cfg, pods=pods,
                data_size=data_size, model_size=model_size,
                sched=sched, alphas=alphas, doc_lens=doc_lens)


# ---------------------------------------------------------------------------
# The pipeline forward (shared by train loss / prefill)
# ---------------------------------------------------------------------------


def _squeeze_lead(tree, n: int):
    return jax.tree_util.tree_map(
        lambda a: a.reshape(a.shape[n:]), tree)


def chunk_tag(cell: Cell, chunk: int, *, suffix: str, train: bool):
    """(tag, names) for one tick/chunk of the pipeline loops.

    Executed offloading (plan.offload_mode == 'explicit', DESIGN.md §10)
    routes the act_off rows through host memory inside the differentiated
    train loops; prefill has no backward — nothing is ever reloaded — so it
    keeps the plain named tags.  The names are suffix-qualified so the
    memledger can attribute each tick's saved bytes from the traced jaxpr."""
    names = ofl.chunk_names(suffix)
    alpha = cell.alphas[chunk]
    plan = cell.plan
    if train and plan.offload and plan.offload_mode == "explicit":
        return ofl.make_exec_tag(alpha, names=names,
                                 codec=plan.offload_dtype), names
    return ofl.make_tag(alpha, names=names), names


def use_ahead_prefetch(plan: ParallelPlan, *, train: bool) -> bool:
    """Whether a loop iteration goes through the prefetch='ahead' seam
    (DESIGN.md §12): only the differentiated explicit-offload path has a
    backward reload to place — prefill/decode and the remat ablations keep
    their existing structure."""
    return (train and plan.offload and plan.offload_mode == "explicit"
            and plan.remat == "sppo" and plan.prefetch == "ahead")


def prefetch_chunk(cell: Cell, ctx: Ctx, *, alpha: float, names: tuple,
                   q_pos, cache_off, kv_view: int, q_start=None):
    """The prefetch='ahead' seam for one tick/chunk (DESIGN.md §12).

    Returns ``run(stage_p, g, state, x, link_in) -> (y, state', aux,
    link_out)`` — a ``jax.custom_vjp`` above the per-slot ``jax.checkpoint``:

    * **forward** runs the chunk with the capture tag and saves the
      *host-resident* off-row residuals (one D2H per tag site over the
      slot-stacked rows, carrying the tick-qualified ``act_off`` name the
      memledger counts) plus the device-resident keep rows.  The host set
      is returned as ``link_out`` — a handle threaded to the *next*
      chunk's seam, never consumed by forward math.
    * **backward** receives its own staged reloads as the cotangent of
      ``link_out`` (issued by the next chunk's backward, i.e. one event
      ahead), issues the H2D for the *previous* chunk's ``link_in`` — a
      dataflow-independent copy XLA can overlap with this chunk's backward
      compute — and replays the chunk through the inject tag over the
      staged residuals.  The single in-flight link cotangent is the
      one-slot staging buffer that keeps the backward peak bounded by the
      forward peak (the simulator's memory-mirror rule, §3.2)."""
    from repro.runtime import hostmem

    mdef = cell.mdef
    off_name, keep_name = names
    codec = cell.plan.offload_dtype
    meta = ChunkMeta(q_pos=q_pos, cache_off=cache_off, kv_view=kv_view,
                     tag=None, names=names, q_start=q_start)

    def capture(stage_p, g, state, x):
        y, s2, aux, off_acts, keep_acts, scales = mdef.stage_apply_capture(
            stage_p, state, x, ctx, meta, g, alpha=alpha,
            offload_dtype=codec)
        # Compressed residency (DESIGN.md §14): the captured off rows are
        # already the codec's wire payloads; int8 crosses the link bitcast
        # into an fp8 byte container because the reloads ride custom_vjp
        # *cotangents* (integer outputs have float0 tangents — nothing to
        # carry the bytes).  Same byte count either way, so the ledger's
        # act_off accounting is unchanged by the transport view.
        with jax.named_scope(scopes.OFFLOAD):
            off_host = tuple(
                checkpoint_name(
                    hostmem.to_host(hostmem.to_transport(t, codec)),
                    off_name)
                for t in off_acts)
            if mutation.active("double-d2h"):
                off_host = tuple(hostmem.to_host(t) for t in off_host)
        keep_dev = tuple(checkpoint_name(t, keep_name) for t in keep_acts)
        if mutation.active("scale-offloaded"):
            scales = tuple(hostmem.to_host(s) for s in scales)
        if mutation.active("unnamed-scale"):
            scale_dev = tuple(scales)
        else:
            scale_dev = tuple(
                checkpoint_name(s, ofl.scale_name_for(off_name))
                for s in scales)
        return y, s2, aux, off_host, keep_dev, scale_dev

    @jax.custom_vjp
    def run(stage_p, g, state, x, link_in):
        y, s2, aux, off_host, _, _ = capture(stage_p, g, state, x)
        return y, s2, aux, off_host

    def run_fwd(stage_p, g, state, x, link_in):
        y, s2, aux, off_host, keep_dev, scale_dev = capture(stage_p, g,
                                                            state, x)
        return ((y, s2, aux, off_host),
                (stage_p, g, state, x, link_in, keep_dev, scale_dev))

    def run_bwd(res, cts):
        stage_p, g, state, x, link_in, keep_dev, scale_dev = res
        ct_y, ct_s2, ct_aux, staged_off = cts
        # one-chunk-ahead H2D: reload the *previous* chunk's host residuals
        # now; the copy has no data dependency on this chunk's backward
        # compute below, so it overlaps it, and the result rides the link
        # cotangent to the previous chunk's seam.  Reloads stay in wire
        # form across the link — dequantization belongs to the chunk that
        # owns the scales (its own backward, below).
        with jax.named_scope(scopes.OFFLOAD):
            staged_prev = jax.tree_util.tree_map(hostmem.to_device, link_in)
            staged_off = tuple(hostmem.from_transport(t, codec)
                               for t in staged_off)
        if mutation.active("scale-offloaded"):
            # the hosted scales must come back before dequantize can use them
            scale_dev = tuple(hostmem.to_device(s) for s in scale_dev)

        def replay(stage_p, g, state, x):
            return mdef.stage_apply_inject(
                stage_p, state, x, ctx, meta, g, alpha=alpha,
                off_acts=staged_off, keep_acts=keep_dev,
                offload_dtype=codec, scales=scale_dev)

        _, vjp = jax.vjp(replay, stage_p, g, state, x)
        gp, gg, gs, gx = vjp((ct_y, ct_s2, ct_aux))
        return gp, gg, gs, gx, staged_prev

    run.defvjp(run_fwd, run_bwd)
    return run


def link_drain(y, link):
    """Terminal consumer of the last chunk's link: identity on `y`, with a
    hand-written backward that issues the final (first-to-run) H2D as soon
    as the backward pass reaches `y`'s cotangent — the seam's hand-off for
    the chunk with no later backward to hide under (why reserve_last pins
    its α to 0, core/offload.py)."""
    if not link:
        return y
    from repro.runtime import hostmem

    @jax.custom_vjp
    def attach(y, link):
        return y

    def attach_fwd(y, link):
        return y, link

    def attach_bwd(link_res, ct_y):
        with jax.named_scope(scopes.OFFLOAD):
            staged = jax.tree_util.tree_map(hostmem.to_device, link_res)
        return ct_y, staged

    attach.defvjp(attach_fwd, attach_bwd)
    return attach(y, link)


def pipeline_feed_events(plan: ParallelPlan, n_chunks: int):
    """The (chunk, sub, n_sub) feed sequence the pp>1 tick loop executes.

    This is the runner's side of the runner-vs-simulator contract: the
    event-driven simulator (core/simulate.py) plays out exactly this
    sequence, and tests assert the two agree (DESIGN.md §2/§3)."""
    if plan.msp and plan.pp > 1:
        return sched_mod.msp_ramp_schedule(n_chunks, plan.pp, plan.msp_split)
    return sim_mod.plain_events(n_chunks)


def pipeline_tick_trace(cell: Cell):
    """Static per-tick trace of the pp>1 loop: one dict per tick with the
    feed event entering stage 0 and the drain event leaving stage pp−1."""
    plan = cell.plan
    events = pipeline_feed_events(plan, cell.sched.n)
    n_ticks = len(events) + plan.pp - 1
    trace = []
    for t in range(n_ticks):
        feed = events[t] if t < len(events) else None
        e_last = t - (plan.pp - 1)
        drain = events[e_last] if 0 <= e_last < len(events) else None
        trace.append(dict(tick=t, feed=feed, drain=drain))
    return trace


def run_pipeline(cell: Cell, ctx: Ctx, stage_p, g, tokens, labels, context,
                 *, with_loss: bool, collect_state: bool = False,
                 ledger=None, doc_start=None):
    """tokens/labels: [B_loc, S] local; context: [B_loc, Nctx_loc, d] or None.

    doc_start: optional [B_loc, S] int32 — global start position of the
    document containing each token (PAD_START on padding) for packed
    variable-length batches; threaded to attention as the per-query segment
    window so packed documents never attend across boundaries.  Loss tokens
    are selected by the label sentinel (labels < 0 carry zero weight).

    ledger: optional runtime.memledger.MemLedger — inserts per-tick probes
    (fwd/bwd wall-clock + execution order) on the compute path.

    Returns dict(loss_sum, denom, aux, state, last_x)."""
    mdef, cfg, plan = cell.mdef, cell.cfg, cell.plan
    sp, pp = plan.sp, plan.pp
    N = cell.sched.n
    S = cell.shape.seq_len
    B = tokens.shape[0]
    d = cfg.d_model

    ctxt = None
    if cfg.encoder_layers:
        ctxt = mdef.encode(g, context, ctx)
    elif cfg.cross_attn is not None:
        ctxt = context
    state = mdef.init_state(stage_p, g, ctx, B, cell.cache_loc, cell.dtype,
                            context=ctxt)
    rank = ctx.model_index()
    stage = ctx.stage_index()
    loss_acc = jnp.float32(0.0)
    den_acc = jnp.float32(0.0)
    aux_acc = jnp.float32(0.0)

    def chunk_positions(off, lloc):
        return off + rank * lloc + jnp.arange(lloc, dtype=jnp.int32)

    if pp == 1:
        x_last = None
        ahead = use_ahead_prefetch(plan, train=with_loss)
        link = ()
        for c in range(N):
            off, ln = cell.sched.offsets[c], cell.sched.lengths[c]
            lloc = ln // sp
            ids = jax.lax.slice_in_dim(tokens, off, off + ln, axis=1)
            q_pos = chunk_positions(off, lloc)
            ds_loc = None
            if doc_start is not None:
                # local shard of the chunk's segment window: embed's
                # reduce-scatter makes the local rows the rank's contiguous
                # [off + rank*lloc, off + (rank+1)*lloc) slice, so slice the
                # per-token doc_start the same way
                ds_chunk = jax.lax.slice_in_dim(doc_start, off, off + ln,
                                                axis=1)
                ds_loc = jax.lax.dynamic_slice_in_dim(
                    ds_chunk, rank * lloc, lloc, axis=1)
            x = mdef.embed(g, ids, q_pos, ctx)
            if ahead:
                run = prefetch_chunk(cell, ctx, alpha=cell.alphas[c],
                                     names=ofl.chunk_names(f"@c{c}"),
                                     q_pos=q_pos, cache_off=off // sp,
                                     kv_view=(off + ln) // sp,
                                     q_start=ds_loc)
                x, state, aux, link = run(stage_p, g, state, x, link)
            else:
                tag, names = chunk_tag(cell, c, suffix=f"@c{c}",
                                       train=with_loss)
                meta = ChunkMeta(q_pos=q_pos, cache_off=off // sp,
                                 kv_view=(off + ln) // sp,
                                 tag=tag, names=names, q_start=ds_loc)
                x, state, aux = mdef.stage_apply(
                    stage_p, state, x, ctx, meta, g,
                    offload=plan.offload, remat=plan.remat,
                    offload_mode=plan.offload_mode,
                    offload_dtype=plan.offload_dtype if with_loss else "none")
            if ledger is not None:
                from repro.runtime import memledger as _ml
                x = _ml.tick_probe(x, ledger, c)
            aux_acc = aux_acc + aux
            if with_loss:
                lab = jax.lax.slice_in_dim(labels, off, off + ln, axis=1)
                # the label sentinel (<0) zero-weights padding and each
                # document's last token; uniform batches have no sentinel
                # labels, so this is the same all-ones weighting as before
                wts = (lab >= 0).astype(jnp.float32)
                ls, cnt = mdef.head_loss(g, x, lab, wts, ctx)
                loss_acc, den_acc = loss_acc + ls, den_acc + cnt
            x_last = x
        loss_acc = link_drain(loss_acc, link)
        return dict(loss=loss_acc, denom=den_acc, aux=aux_acc, state=state,
                    last_x=x_last)

    # ---- pp > 1: lock-step tick pipeline -----------------------------------
    # The tick loop executes the feed-event schedule (plain, or the MSP ramp
    # when plan.msp): at tick t, stage s handles event t−s.  An MSP sub-event
    # recomputes its *full* chunk (lock-step SPMD needs uniform shapes —
    # DESIGN.md §2); the KV-cache rewrite is idempotent (same tokens, same
    # positions, same weights) and the loss mask restricts each sub-event to
    # its own sub-chunk region, so every token is counted exactly once and
    # the loss equals the plain schedule's bit-for-bit function of params.
    #
    # Warmup and drain ticks are NOT idempotent: they clamp e_my to a real
    # event but feed it garbage (stage 0 embeds zeros once t >= E; later
    # stages consume a stale drain carry), so their cache rewrite clobbers
    # the event's kv with junk.  A warmup write is repaired by the stage's
    # first valid tick, but a drain write on any stage except the last is
    # final — the returned prefill state would hand the decode loop a
    # zeroed cache.  The state update below is therefore masked to valid
    # ticks; training is bit-unaffected (state is re-initialised per call
    # and each stage's garbage writes land after its last valid read).
    clen = S // N
    lloc = clen // sp
    events = pipeline_feed_events(plan, N)
    E = len(events)
    chunk_arr = jnp.array([ev[0] for ev in events], jnp.int32)
    inv_ns = jnp.array([1.0 / ev[2] for ev in events], jnp.float32)
    carry = jnp.zeros((B, lloc, d), cell.dtype)
    x_out = carry
    ahead = use_ahead_prefetch(plan, train=with_loss)
    link = ()
    for t in range(E + pp - 1):
        e_new = min(t, E - 1)
        if t < E:
            off_new = events[t][0] * clen
            ids = jax.lax.slice_in_dim(tokens, off_new, off_new + clen,
                                       axis=1)
            x0 = mdef.embed(g, ids, chunk_positions(off_new, lloc), ctx)
        else:
            x0 = jnp.zeros((B, lloc, d), cell.dtype)
        h = jnp.where(stage == 0, x0, carry)
        e_my = jnp.clip(t - stage, 0, E - 1)
        c_my = chunk_arr[e_my]
        off_my = c_my * clen
        q_pos = chunk_positions(off_my, lloc)
        ds_loc = None
        if doc_start is not None:
            # this stage's chunk offset is traced (off_my), so take the
            # local segment window with a dynamic slice; drain ticks clamp
            # harmlessly (their output is masked out below)
            ds_loc = jax.lax.dynamic_slice_in_dim(
                doc_start, off_my + rank * lloc, lloc, axis=1)
        valid = (t - stage >= 0) & (t - stage < E)
        prev_state = state
        # tick-aligned offload ratio: the SPMD program is uniform across
        # stages, so every stage tags with the fed event's deployed alpha
        if ahead:
            run = prefetch_chunk(cell, ctx, alpha=cell.alphas[events[e_new][0]],
                                 names=ofl.chunk_names(f"@t{t}"),
                                 q_pos=q_pos, cache_off=c_my * lloc,
                                 kv_view=min(events[e_new][0] + 1, N) * lloc,
                                 q_start=ds_loc)
            x_out, state, aux, link = run(stage_p, g, state, h, link)
        else:
            tag, names = chunk_tag(cell, events[e_new][0], suffix=f"@t{t}",
                                   train=with_loss)
            meta = ChunkMeta(q_pos=q_pos, cache_off=c_my * lloc,
                             kv_view=min(events[e_new][0] + 1, N) * lloc,
                             tag=tag, names=names, q_start=ds_loc)
            x_out, state, aux = mdef.stage_apply(
                stage_p, state, h, ctx, meta, g,
                offload=plan.offload, remat=plan.remat,
                offload_mode=plan.offload_mode,
                offload_dtype=plan.offload_dtype if with_loss else "none")
        # drop warmup/drain rewrites (see the block comment above)
        if not mutation.active("drain-tick-write"):
            state = jax.tree_util.tree_map(
                lambda old, new: jnp.where(valid, new, old),
                prev_state, state)
        if ledger is not None:
            from repro.runtime import memledger as _ml
            x_out = _ml.tick_probe(x_out, ledger, t)
        # sub-events of one chunk run identical compute; scale aux (MoE
        # balance) by 1/n_sub so each chunk contributes once in total
        aux_acc = aux_acc + jnp.where(valid, aux * inv_ns[e_my], 0.0)
        e_last = t - (pp - 1)
        if with_loss and 0 <= e_last < E:
            c_l, sub_l, ns_l = events[e_last]
            lab = jax.lax.slice_in_dim(labels, c_l * clen,
                                       (c_l + 1) * clen, axis=1)
            sublen = clen // ns_l
            pos_in = jnp.arange(clen)
            mask = ((pos_in >= sub_l * sublen)
                    & (pos_in < (sub_l + 1) * sublen)).astype(jnp.float32)
            wts = (jnp.broadcast_to(mask[None, :], lab.shape)
                   * (lab >= 0).astype(jnp.float32))
            ls, cnt = mdef.head_loss(g, x_out, lab, wts, ctx)
            is_last = (stage == pp - 1).astype(jnp.float32)
            loss_acc = loss_acc + is_last * ls
            den_acc = den_acc + is_last * cnt
        carry = ctx.ppermute_stage(x_out, ctx.next_stage_perm())
    # the final tick's link drains at backward start; SPMD: every stage
    # attaches its own last-tick residuals to its (psum-connected) loss term
    loss_acc = link_drain(loss_acc, link)
    return dict(loss=loss_acc, denom=den_acc, aux=aux_acc, state=state,
                last_x=x_out)


# ---------------------------------------------------------------------------
# Batch structs + shardings
# ---------------------------------------------------------------------------


def batch_struct(cell: Cell):
    """ShapeDtypeStructs + PartitionSpecs for one step's inputs."""
    B_loc, S = cell.b_loc, cell.shape.seq_len
    pods, data = cell.pods, cell.data_size
    cfg = cell.cfg
    lead = (pods, data)
    st: Dict[str, Any] = {}
    sp_: Dict[str, Any] = {}
    if cell.shape.kind == "decode":
        st["tokens"] = jax.ShapeDtypeStruct(lead + (B_loc, 1), jnp.int32)
        sp_["tokens"] = P("pod", "data") if pods > 1 else P(None, "data")
        st["pos"] = jax.ShapeDtypeStruct((), jnp.int32)
        sp_["pos"] = P()
    else:
        st["tokens"] = jax.ShapeDtypeStruct(lead + (B_loc, S), jnp.int32)
        st["labels"] = jax.ShapeDtypeStruct(lead + (B_loc, S), jnp.int32)
        tok_spec = P("pod", "data") if pods > 1 else P(None, "data")
        sp_["tokens"] = tok_spec
        sp_["labels"] = tok_spec
        if cell.varlen:
            st["doc_start"] = jax.ShapeDtypeStruct(lead + (B_loc, S),
                                                   jnp.int32)
            sp_["doc_start"] = tok_spec
    if cfg.cross_attn is not None:
        n_ctx = (cfg.n_frames if cfg.encoder_layers
                 else cfg.cross_attn.n_context_tokens)
        n_pad = -(-n_ctx // cell.plan.sp) * cell.plan.sp
        st["context"] = jax.ShapeDtypeStruct(
            lead + (B_loc, n_pad, cfg.d_model), cell.dtype)
        sp_["context"] = (P("pod", "data", None, "model")
                          if pods > 1 else P(None, "data", None, "model"))
    return st, sp_


def _in_specs_for_params(cell: Cell):
    return {"stages": SP.stage_specs(cell.mdef, cell.plan.pp),
            "globals": SP.globals_specs(cell.mdef)}


# ---------------------------------------------------------------------------
# train_step
# ---------------------------------------------------------------------------


def _step_inputs(stage_p, batch):
    """Per-device views of one step's inputs inside shard_map: the stage
    params and (tokens, labels, context, doc_start) with their mesh-lead
    axes squeezed (context / doc_start are None when the batch has none)."""
    def opt(k):
        return _squeeze_lead(batch[k], 2) if k in batch else None

    return (_squeeze_lead(stage_p, 1), _squeeze_lead(batch["tokens"], 2),
            _squeeze_lead(batch["labels"], 2), opt("context"),
            opt("doc_start"))


def _make_loss_fn(cell: Cell, ctx: Ctx, ledger=None):
    """The scalar loss of one (micro)batch that train_step differentiates:
    (stage_p, g, tokens, labels, context, doc_start) -> loss."""
    def loss_fn(stage_p, g, tok, lab, ctxt, ds):
        out = run_pipeline(cell, ctx, stage_p, g, tok, lab, ctxt,
                           with_loss=True, ledger=ledger,
                           doc_start=ds if cell.varlen else None)
        den = jnp.maximum(ctx.psum_loss_all(out["denom"]), 1.0)
        share = out["loss"] / den
        if cell.cfg.moe is not None:
            share = share + 0.01 * out["aux"] / (
                cell.data_size * cell.pods * cell.plan.sp * cell.sched.n
                * max(1, cell.mdef.n_slots))
        return share, ctx.psum_loss_all(share)

    return loss_fn


def make_loss_step(cell: Cell, mesh):
    """Forward-only ``loss_step(params, batch) -> loss``: train_step's loss
    with no backward and no update (with grad_accum 1 the same number
    train_step reports for that batch) — a reference that needs no
    activation memory for the backward."""
    pspecs = _in_specs_for_params(cell)
    _, bspecs = batch_struct(cell)

    def smap_body(stage_p, g, batch):
        sp, tok, lab, ctxt, ds = _step_inputs(stage_p, batch)
        return _make_loss_fn(cell, cell.ctx())(sp, g, tok, lab, ctxt, ds)[1]

    smapped = shard_map(
        smap_body, mesh,
        in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
        out_specs=P())
    return lambda params, batch: smapped(params["stages"], params["globals"],
                                         batch)


def make_grad_step(cell: Cell, mesh, *, ledger=None):
    """``grad_step(params, batch) -> (loss, grads, grad_norm)``: the step's
    mean loss, its gradient laid out like the params (each stage's entries
    identical across its dp replicas) and the global norm of that gradient
    counting every parameter once."""
    from repro.optim import adamw

    plan = cell.plan
    pspecs = _in_specs_for_params(cell)
    _, bspecs = batch_struct(cell)

    def smap_body(stage_p, g, batch):
        ctx = cell.ctx()
        stage_p, tokens, labels, context, doc_start = _step_inputs(stage_p,
                                                                   batch)
        loss_fn = _make_loss_fn(cell, ctx, ledger)

        A = plan.grad_accum
        if A > 1:
            Bm = tokens.shape[0] // A
            tks = tokens.reshape(A, Bm, -1)
            lbs = labels.reshape(A, Bm, -1)
            cxs = (context.reshape((A, Bm) + context.shape[1:])
                   if context is not None else None)
            dss = (doc_start.reshape(A, Bm, -1)
                   if doc_start is not None else None)

            def acc_step(carry, xs):
                gsum, lsum = carry
                tok, lab, cx, ds = xs
                (_, l), gr = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                                has_aux=True)(
                    stage_p, g, tok, lab, cx, ds)
                gsum = jax.tree_util.tree_map(
                    lambda a, b: a + b.astype(a.dtype), gsum, gr)
                return (gsum, lsum + l), None

            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), (stage_p, g))
            (grads, loss), _ = jax.lax.scan(
                acc_step, (zeros, jnp.float32(0.0)),
                (tks, lbs, cxs if cxs is not None else jnp.zeros((A, Bm)),
                 dss if dss is not None else jnp.zeros((A, Bm))))
            loss = loss / A
            grads = jax.tree_util.tree_map(lambda a: a / A, grads)
        else:
            (_, loss), grads = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(
                stage_p, g, tokens, labels, context, doc_start)
        # a param replicated over the model axis saw only this rank's
        # sequence shard: sum the shards (model-sharded params were
        # gathered for compute, so their grads came back reduce-scattered)
        grads = jax.tree.map(
            lambda gr, spec: gr if "model" in spec else ctx.psum_model(gr),
            grads, (pspecs["stages"], pspecs["globals"]),
            is_leaf=lambda x: isinstance(x, P))
        # stage grads reduce over dp replicas; global grads over all stages
        g_stage = ctx.psum_grads(grads[0])
        g_glob = ctx.psum_globals(grads[1])
        g_st = jax.tree_util.tree_map(lambda a: a[None], g_stage)
        return loss, g_st, g_glob

    smapped = shard_map(
        smap_body, mesh,
        in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
        out_specs=(P(), pspecs["stages"], pspecs["globals"]))

    def grad_step(params, batch):
        loss, gs, gg = smapped(params["stages"], params["globals"], batch)
        # the stacked stage grads hold each stage once per dp replica
        with jax.named_scope(scopes.ADAMW):
            gnorm = jnp.sqrt(jnp.square(adamw.global_norm(gs)) / plan.dp
                             + jnp.square(adamw.global_norm(gg)))
        return loss, {"stages": gs, "globals": gg}, gnorm

    return grad_step


def make_train_step(cell: Cell, mesh, *, lr_kwargs=None, ledger=None):
    from repro.optim import adamw

    plan = cell.plan
    grad_step = make_grad_step(cell, mesh, ledger=ledger)
    lr_kwargs = lr_kwargs or {}

    def train_step(params, opt_state, batch):
        loss, grads, gnorm = grad_step(params, batch)
        lr = adamw.cosine_lr(opt_state.step, **lr_kwargs)
        new_p, new_o, met = adamw.apply_update(
            params, grads, opt_state, lr=lr,
            offload_moments=plan.offload_moments,
            moments_dtype=plan.moments_dtype, grad_norm=gnorm)
        met["loss"] = loss
        return new_p, new_o, met

    return train_step


# ---------------------------------------------------------------------------
# prefill_step / serve_step
# ---------------------------------------------------------------------------


def make_prefill_step(cell: Cell, mesh):
    pspecs = _in_specs_for_params(cell)
    bstruct, bspecs = batch_struct(cell)
    _, sstruct, sspecs = _serve_state(cell)

    def smap_body(stage_p, g, batch):
        ctx = cell.ctx()
        stage_p = _squeeze_lead(stage_p, 1)
        tokens = _squeeze_lead(batch["tokens"], 2)
        context = (_squeeze_lead(batch["context"], 2)
                   if "context" in batch else None)
        out = run_pipeline(cell, ctx, stage_p, g, tokens, tokens, context,
                           with_loss=False)
        state = jax.tree_util.tree_map(lambda a: a[None], out["state"])
        return state, out["last_x"][None]

    last_spec = P("data", None, None, None)
    smapped = shard_map(
        smap_body, mesh,
        in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
        out_specs=(sspecs, last_spec))

    def prefill_step(params, batch):
        return smapped(params["stages"], params["globals"], batch)

    return prefill_step, sstruct, sspecs


def max_decode_steps(cell: Cell) -> int:
    """Longest decode run the striped cache can absorb: token S + i lands at
    local slot base + i // sp, and the buffer holds DECODE_BUDGET slots past
    base — so step DECODE_BUDGET * sp is the first to fall off the end."""
    return DECODE_BUDGET * cell.plan.sp


def make_serve_step(cell: Cell, mesh, *, decode_steps=None):
    """Build the static lock-step decode step.

    decode_steps: when given, the number of steps the caller intends to run;
    rejected at construction if it exceeds the cache's decode budget —
    beyond it ``my_slot`` runs past ``cache_loc`` and the clamped
    dynamic-update would silently overwrite the last slot, corrupting every
    later logit with no error.
    """
    if decode_steps is not None and decode_steps > max_decode_steps(cell):
        raise ValueError(
            f"decode_steps={decode_steps} exceeds the cache's decode budget "
            f"of {max_decode_steps(cell)} steps (DECODE_BUDGET={DECODE_BUDGET}"
            f" slots x sp={cell.plan.sp}); the striped write would silently "
            "wrap onto the last cache slot")
    pspecs = _in_specs_for_params(cell)
    bstruct, bspecs = batch_struct(cell)
    _, sstruct, sspecs_g = _serve_state(cell)
    sspecs = sspecs_g

    plan = cell.plan
    S = cell.shape.seq_len
    sp = plan.sp

    def smap_body(stage_p, g, state, batch):
        ctx = cell.ctx()
        stage_p = _squeeze_lead(stage_p, 1)
        state = _squeeze_lead(state, 1)
        tokens = _squeeze_lead(batch["tokens"], 2)   # [B_loc, 1]
        pos = batch["pos"]                            # [] global position
        rank = ctx.model_index()
        base = S // sp
        idx = pos - S
        my_slot = jnp.where((idx % sp) == rank, base + idx // sp, -1)
        meta = ChunkMeta(
            q_pos=jnp.full((1,), pos, jnp.int32), cache_off=0,
            kv_view=cell.cache_loc, tag=ofl.null_tag, decode=True,
            my_slot=my_slot)

        # Decode consumes the plan like every other loop.  resolve_plan pins
        # offload=False / remat="none" for decode shapes (and resolve_cell
        # asserts it): a decode step has no backward, so there is no reload
        # to hide and no residual worth evicting — offloading here would be
        # pure added H2D latency on the critical path (DESIGN.md §4).
        def one_micro(state_m, tok_m):
            x = cell.mdef.embed(g, tok_m, jnp.full((1,), pos, jnp.int32),
                                ctx, decode=True)
            x, state_m, _ = cell.mdef.stage_apply(
                stage_p, state_m, x, ctx, meta, g, offload=plan.offload,
                remat=plan.remat, offload_mode=plan.offload_mode)
            return state_m, x

        if plan.pp == 1:
            state, x = one_micro(state, tokens)
            logits = cell.mdef.head_logits(g, x, ctx)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            # Microbatch pipeline over the batch dim, as a lax.scan over
            # ticks so the per-stage cache is threaded (double-buffered)
            # instead of copied once per unrolled tick.
            M = plan.decode_microbatch
            Bm = tokens.shape[0] // M
            stage = ctx.stage_index()
            n_ticks = M + plan.pp - 1

            def tick(carry_t, t):
                state, carry, nxt = carry_t
                m_my = jnp.clip(t - stage, 0, M - 1)
                boff = m_my * Bm
                state_m = jax.tree_util.tree_map(
                    lambda a: (jax.lax.dynamic_slice_in_dim(a, boff, Bm,
                                                            axis=1)
                               if a.ndim >= 3 else a), state)
                tok_m = jax.lax.dynamic_slice_in_dim(
                    tokens, jnp.clip(t, 0, M - 1) * Bm, Bm, axis=0)
                x0 = cell.mdef.embed(g, tok_m,
                                     jnp.full((1,), pos, jnp.int32),
                                     ctx, decode=True)
                h = jnp.where(stage == 0, x0, carry)
                # plan-driven like one_micro above: decode never offloads
                # (no backward, nothing to hide under — DESIGN.md §4)
                x, state_m, _ = cell.mdef.stage_apply(
                    stage_p, state_m, h, ctx, meta, g, offload=plan.offload,
                    remat=plan.remat, offload_mode=plan.offload_mode)
                state = jax.tree_util.tree_map(
                    lambda a, am: (jax.lax.dynamic_update_slice_in_dim(
                        a, am, boff, axis=1) if a.ndim >= 3 else am),
                    state, state_m)
                logits = cell.mdef.head_logits(g, x, ctx)
                tok_new = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                # only the last stage's sample on a valid drain tick is real
                m_last = t - (plan.pp - 1)
                valid = (m_last >= 0) & (stage == plan.pp - 1)
                off_l = jnp.clip(m_last, 0, M - 1) * Bm
                cur = jax.lax.dynamic_slice_in_dim(nxt, off_l, Bm, axis=0)
                nxt = jax.lax.dynamic_update_slice_in_dim(
                    nxt, jnp.where(valid, tok_new, cur), off_l, axis=0)
                carry = ctx.ppermute_stage(x, ctx.next_stage_perm())
                return (state, carry, nxt), None

            carry0 = jnp.zeros((Bm, 1, cell.cfg.d_model), cell.dtype)
            nxt0 = jnp.zeros((tokens.shape[0], 1), jnp.int32)
            (state, _, nxt), _ = jax.lax.scan(
                tick, (state, carry0, nxt0),
                jnp.arange(n_ticks, dtype=jnp.int32))
            # only the last stage sampled real tokens; replicate them to
            # every stage row of the dp group so callers can thread nxt
            # straight back in as the next step's tokens (no host gather)
            nxt = ctx.psum_stages(
                jnp.where(stage == plan.pp - 1, nxt, 0))
        state = jax.tree_util.tree_map(lambda a: a[None], state)
        return state, nxt[None]

    tok_out_spec = P("data", None, None)
    smapped = shard_map(
        smap_body, mesh,
        in_specs=(pspecs["stages"], pspecs["globals"], sspecs, bspecs),
        out_specs=(sspecs, tok_out_spec))

    def serve_step(params, state, batch):
        return smapped(params["stages"], params["globals"], state, batch)

    return serve_step, sstruct, sspecs


def _serve_state(cell: Cell):
    """State struct/specs for decode (global arrays passed between steps)."""
    ctx = Ctx(sp=cell.plan.sp, dp=cell.plan.dp, pp=cell.plan.pp)

    def f(k):
        stage_p = cell.mdef.init_stage_params(k, 0, cell.plan.pp, cell.dtype)
        g = cell.mdef.init_globals(k, cell.dtype)
        cfgc = cell.cfg
        ctxt = None
        if cfgc.cross_attn is not None:
            n_ctx = (cfgc.n_frames if cfgc.encoder_layers
                     else cfgc.cross_attn.n_context_tokens)
            n_loc = (-(-n_ctx // cell.plan.sp) * cell.plan.sp) // cell.plan.sp
            ctxt = jnp.zeros((cell.b_loc, n_loc, cfgc.d_model), cell.dtype)
            if cfgc.encoder_layers:
                ctxt = cell.mdef.encode(g, ctxt, ctx)
        return cell.mdef.init_state(stage_p, g, ctx, cell.b_loc,
                                    cell.cache_loc, cell.dtype, context=ctxt)

    local = jax.eval_shape(f, jax.ShapeDtypeStruct((2,), jnp.uint32))
    struct = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((cell.data_size,) + s.shape, s.dtype),
        local)
    specs = jax.tree_util.tree_map(
        lambda s: P(*(("data",) + (None,) * s.ndim)), local)
    return local, struct, specs


# ---------------------------------------------------------------------------
# Paged-pool continuous-batching decode (DESIGN.md §16)
# ---------------------------------------------------------------------------


def _assert_pool_cell(cell: Cell, geo):
    assert cell.plan.pp == 1, "paged decode pool requires pp == 1"
    assert cell.pods == 1, "paged decode pool is single-pod"
    cfg = cell.cfg
    assert (cfg.family == "dense" and cfg.cross_attn is None
            and cfg.mla is None), (
        f"paged decode pool supports dense GQA families only, got "
        f"family={cfg.family!r}")
    assert cell.plan.sp == geo.sp, (cell.plan.sp, geo.sp)
    assert cell.b_loc == geo.n_slots, (
        f"cell batch/shard {cell.b_loc} != pool slots {geo.n_slots}")


def _pool_specs():
    spec = P("data", None, None, None, None)
    return {"kv": A.PooledKV(k=spec, v=spec)}


def make_pool_state(cell: Cell, geo, mesh):
    """Zero-initialized paged KV pool for ``cell`` (global arrays + specs).

    One [P_loc, Hkv, hd] block buffer per layer-slot per (data, model) rank;
    the spec claims model-axis replication like ``_serve_state`` does (the
    shard_map wrapper disables replication checks), so each model rank keeps
    its own sequence shard of the pool.
    """
    _assert_pool_cell(cell, geo)
    spp = cell.mdef.slots_per_stage(cell.plan.pp)
    cfg = cell.cfg
    shape = (cell.data_size, spp, geo.p_loc, cfg.n_kv_heads, cfg.hd)
    spec = P("data", None, None, None, None)

    def arr():
        # transfer-lint: ok (pool init placement, device memory only)
        return jax.device_put(jnp.zeros(shape, cell.dtype),
                              jax.sharding.NamedSharding(mesh, spec))

    return {"kv": A.PooledKV(k=arr(), v=arr())}, _pool_specs()


def make_pool_ingest(pre_cell: Cell, geo, mesh):
    """Copy an admission wave's prefilled caches into the pool.

    Identity slot mapping: the engine prefills each admitted request in the
    batch row of its target pool slot, so prefill cache row b of a data
    shard feeds pool slot b of the same shard, and the first ``base``
    logical slots of the prefill cache are exactly the right-aligned prompt
    bucket.  Rows outside the admit mask scatter to an out-of-bounds
    sentinel and drop.
    """
    _assert_pool_cell(pre_cell, geo)
    assert pre_cell.shape.seq_len == geo.s_bucket, (
        pre_cell.shape.seq_len, geo.s_bucket)
    assert pre_cell.cache_loc >= geo.base
    _, _, sspecs = _serve_state(pre_cell)
    pool_specs = _pool_specs()
    bt, p_loc = geo.block_tokens, geo.p_loc
    io = P(None, "data")

    def smap_body(state_pre, pool, btab, admit):
        state_pre = _squeeze_lead(state_pre, 1)
        pool = _squeeze_lead(pool, 1)
        btab = _squeeze_lead(btab, 2)                    # [K, max_blocks]
        admit = _squeeze_lead(admit, 2)                  # [K] bool
        jlog = jnp.arange(geo.base)
        blk = btab[:, jlog // bt]                        # [K, base]
        phys = jnp.where(admit[:, None] & (blk >= 0),
                         blk * bt + jlog % bt, p_loc)

        def copy(pool_a, cache_a):
            # pool_a: [spp, P_loc, Hkv, hd]; cache_a: [spp, K, C_loc, ...]
            vals = cache_a[:, :, :geo.base]

            def one(pa, va):
                return pa.at[phys].set(va.astype(pa.dtype), mode="drop")

            return jax.vmap(one)(pool_a, vals)

        kv, pkv = state_pre["kv"], pool["kv"]
        new = {"kv": A.PooledKV(k=copy(pkv.k, kv.k), v=copy(pkv.v, kv.v))}
        return jax.tree_util.tree_map(lambda a: a[None], new)

    smapped = shard_map(smap_body, mesh,
                        in_specs=(sspecs, pool_specs, io, io),
                        out_specs=pool_specs)

    def ingest(state_pre, pool, btab, admit):
        return smapped(state_pre, pool, btab, admit)

    return ingest


def make_pool_serve_step(cell: Cell, geo, mesh, pos_map):
    """One continuous-batching decode step against the paged pool.

    Unlike ``make_serve_step`` there is no global position scalar: every
    request slot carries its own feed position (``q_pos``; 0 = inactive
    slot), its own block-table row, and its own sampled-token carry, so
    requests at different decode depths step together and the host never
    syncs mid-loop.  Admission folds in on device: rows under ``admit``
    take ``admit_tok`` (the request's last prompt token) instead of the
    carried sample.

    batch keys (lead dims (1, data), spec P(None, "data")):
      tokens    [1, D, K, 1]  carried sampled tokens (device-resident)
      q_pos     [1, D, K]     per-slot global feed position, 0 = inactive
      btab      [1, D, K, max_blocks] block table (host-pushed, -1 = unset)
      admit     [1, D, K]     bool: overwrite the carry with admit_tok
      admit_tok [1, D, K, 1]  first decode token of newly admitted rows
    Returns (pool', nxt [D, K, 1]).
    """
    _assert_pool_cell(cell, geo)
    import numpy as _np
    pos_map = _np.asarray(pos_map)
    assert pos_map.shape == (geo.sp, geo.l_loc), pos_map.shape
    pspecs = _in_specs_for_params(cell)
    pool_specs = _pool_specs()
    plan = cell.plan
    io = P(None, "data")
    bspecs = {"tokens": io, "q_pos": io, "btab": io, "admit": io,
              "admit_tok": io}

    def smap_body(stage_p, g, pool, batch):
        ctx = cell.ctx()
        stage_p = _squeeze_lead(stage_p, 1)
        pool = _squeeze_lead(pool, 1)
        tokens = _squeeze_lead(batch["tokens"], 2)       # [K, 1]
        qpos = _squeeze_lead(batch["q_pos"], 2)          # [K]
        btab = _squeeze_lead(batch["btab"], 2)           # [K, max_blocks]
        admit = _squeeze_lead(batch["admit"], 2)         # [K] bool
        atok = _squeeze_lead(batch["admit_tok"], 2)      # [K, 1]
        tokens = jnp.where(admit[:, None], atok, tokens)
        rank = ctx.model_index()
        paged = A.PagedMeta(q_pos=qpos, btab=btab,
                            pos_map=jnp.asarray(pos_map)[rank],
                            base=geo.base, s_bucket=geo.s_bucket,
                            block_tokens=geo.block_tokens)
        meta = ChunkMeta(q_pos=qpos, cache_off=0, kv_view=geo.l_loc,
                         tag=ofl.null_tag, decode=True, paged=paged)
        x = cell.mdef.embed(g, tokens, qpos[:, None], ctx, decode=True)
        x, pool, _ = cell.mdef.stage_apply(
            stage_p, pool, x, ctx, meta, g, offload=plan.offload,
            remat=plan.remat, offload_mode=plan.offload_mode)
        logits = cell.mdef.head_logits(g, x, ctx)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pool = jax.tree_util.tree_map(lambda a: a[None], pool)
        return pool, nxt[None]

    smapped = shard_map(
        smap_body, mesh,
        in_specs=(pspecs["stages"], pspecs["globals"], pool_specs, bspecs),
        out_specs=(pool_specs, P("data", None, None)))

    def pool_step(params, pool, batch):
        return smapped(params["stages"], params["globals"], pool, batch)

    return pool_step

"""Attention blocks: GQA, MLA (DeepSeek), cross-attention — chunk-native.

Distribution recipe (DESIGN.md §4): activations and the KV cache are
*sequence-sharded* over the `model` axis.  For a chunk of queries we
all-gather q (cheap — chunk-sized), run partial flash attention against the
device-local KV shard, and merge the partial softmax statistics with one
pmax + two psum_scatters.  This is flash-decoding generalized to chunks; it
is head-count agnostic (the paper's §7.3 criticism of Ulysses does not apply)
and it keeps the paper's Type-0 "skeletal" KV memory balanced across devices.

The KV cache is position-tagged: every slot carries its global token
position (PAD = 2**30 for empty slots), so causality across subsequence
chunks, decode steps, and bidirectional encoder attention are all the same
kernel invocation.

Differentiability: the whole merge is training-grade on both kernel
backends.  The partial (o, l) outputs differentiate in (q, k, v) — via the
fused Pallas backward kernels' custom_vjp or the jnp scan's autodiff — and
every max statistic is gradient-frozen before the pmax/psum merge (pmax has
no VJP; the m-dependence cancels exactly in the o/l ratio, see
kernels/ref.py), so ∂loss/∂{q,k,v} flow through the exp-rescaled o and l
psums alone.  Training on the Pallas backend therefore runs the identical
code path as serve.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops as kops
from repro.models import layers as L
from repro.parallel.ctx import Ctx

PAD = jnp.int32(2**30)


class KVCache(NamedTuple):
    """Sequence-sharded, position-tagged KV cache (one layer)."""

    k: jax.Array        # [B, S_loc, Hkv, hd_k]
    v: jax.Array        # [B, S_loc, Hkv, hd_v]  (may alias k for MLA)
    pos: jax.Array      # [S_loc] int32 global positions (PAD = empty)


def init_cache(batch: int, s_local: int, h_kv: int, hd_k: int, hd_v: int,
               dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, s_local, h_kv, hd_k), dtype),
        v=jnp.zeros((batch, s_local, h_kv, hd_v), dtype),
        pos=jnp.full((s_local,), PAD, jnp.int32),
    )


def cache_append(cache: KVCache, k_new, v_new, pos_new, offset) -> KVCache:
    """Write this rank's shard of a chunk's KV at local slot `offset`
    (static int for chunked training, traced for decode)."""
    off = jnp.asarray(offset, jnp.int32)
    z = jnp.int32(0)
    return KVCache(
        k=jax.lax.dynamic_update_slice(cache.k, k_new.astype(cache.k.dtype),
                                       (z, off, z, z)),
        v=jax.lax.dynamic_update_slice(cache.v, v_new.astype(cache.v.dtype),
                                       (z, off, z, z)),
        pos=jax.lax.dynamic_update_slice(cache.pos,
                                         pos_new.astype(jnp.int32), (off,)),
    )


def _pick_mode(ctx: Ctx, q, k_loc, kv_view) -> str:
    """Byte-count switch (the §Perf 'auto' mode): gathering the KV shard
    costs ~(k+v) bytes; the gather-q merge moves q (bf16) + o (f32) + stats.
    GQA makes KV far narrower than q x heads, so short-chunk training cells
    prefer gather_kv, while decode/long-cache cells prefer gather_q."""
    if ctx.attn_mode != "auto":
        return ctx.attn_mode
    B, Tq, H, hdk = q.shape
    Hkv = k_loc.shape[2]
    kv_len = kv_view if kv_view is not None else k_loc.shape[1]
    kv_bytes = 2 * kv_len * Hkv * k_loc.shape[-1] * 2
    q_bytes = Tq * H * hdk * (2 + 4)  # q bf16 out f32 (per merge step)
    return "gather_kv" if kv_bytes < q_bytes else "gather_q"


def dist_attention(q, k_loc, v_loc, q_pos, kv_pos, ctx: Ctx, *, causal=True,
                   scale=None, kv_view: Optional[int] = None, q_start=None):
    """q: [B, Tq_loc, H, hd] this rank's query shard (all heads).
    k_loc/v_loc/kv_pos: the local KV shard (cache view).
    kv_view: static number of leading cache slots to attend over (compile-time
    truncation for chunked training; None = full buffer).
    q_start: optional [B, Tq_loc] int32 segment window for packed batches —
    each query sees only kv slots with kv_pos >= its document start, so
    packed documents never attend across boundaries (PAD on padding rows).
    Returns the attention output for this rank's query shard
    [B, Tq_loc, H, hd_v].
    """
    if kv_view is not None:
        k_loc, v_loc, kv_pos = (k_loc[:, :kv_view], v_loc[:, :kv_view],
                                kv_pos[:kv_view])
    mode = _pick_mode(ctx, q, k_loc, kv_view)
    if mode == "ring" and ctx.sp > 1:
        # rotate the KV shard around the model axis (DESIGN.md §15): no
        # device ever materializes more than two KV blocks, so the chunk's
        # visible extent is no longer bounded by one stage's HBM.  q, q_pos
        # and q_start are query-side and stay local.
        from repro.parallel import ring as _ring
        return _ring.ring_attention(q, k_loc, v_loc, q_pos, kv_pos, ctx,
                                    causal=causal, scale=scale,
                                    q_start=q_start)
    if mode == "gather_kv" and ctx.sp > 1:
        # gather the (narrow, GQA) KV shard; attention is then fully local
        # to this rank's query rows — zero merge collectives.  q_start is
        # query-side, so the local shard passes straight through.
        k_full = ctx.all_gather_model(k_loc, axis=1)
        v_full = ctx.all_gather_model(v_loc, axis=1)
        kp_full = ctx.all_gather_model(kv_pos, axis=0)
        qp = q_pos if q_pos.ndim == 1 else q_pos[0]
        o, m, l = kops.attention_partial(q, k_full, v_full, qp, kp_full,
                                         causal=causal, scale=scale,
                                         q_start=q_start)
        out = o / jnp.maximum(l, 1e-30)[..., None]
        return out.astype(q.dtype)

    q_full = ctx.all_gather_model(q, axis=1)
    if q_pos.ndim == 1:
        qp_full = ctx.all_gather_model(q_pos, axis=0)
    else:
        qp_full = ctx.all_gather_model(q_pos, axis=1)
    qs_full = (None if q_start is None
               else ctx.all_gather_model(q_start, axis=1))
    o, m, l = kops.attention_partial(q_full, k_loc, v_loc, qp_full, kv_pos,
                                     causal=causal, scale=scale,
                                     q_start=qs_full)
    # cross-shard softmax merge; scatter back to this rank's query rows.
    # max stats are gradient-frozen (see kernels/ref.py).
    m = jax.lax.stop_gradient(m)
    m_g = jax.lax.stop_gradient(ctx.pmax_model(m))            # [B, Tq, H]
    alpha = jnp.exp(m - m_g)
    o_s = o * alpha[..., None]
    if ctx.merge_bf16:
        o_s = o_s.astype(jnp.bfloat16)
    o = ctx.reduce_scatter_model(o_s, axis=1).astype(jnp.float32)
    l = ctx.reduce_scatter_model(l * alpha, axis=1)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# GQA self-attention block (dense / vlm self / zamba shared / whisper)
# ---------------------------------------------------------------------------


def gqa_self_attention(x, p, cfg, ctx: Ctx, cache: KVCache, q_pos,
                       cache_offset, kv_view, *, name_tag=None,
                       q_start=None):
    """x: [B, T_loc, d]; returns (attn_out [B, T_loc, d], new cache).

    q_pos: [T_loc] global positions of this rank's tokens in the chunk.
    cache_offset: local cache slot where this chunk's shard is written.
    kv_view: static visible cache length after the append.
    q_start: optional [B, T_loc] packed-document window (see dist_attention).
    """
    B, Tl, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, Tl, H, hd)
    k = k.reshape(B, Tl, Hkv, hd)
    v = v.reshape(B, Tl, Hkv, hd)
    if cfg.rope:
        q = L.apply_rope(q, q_pos, cfg.rope_theta, cfg.rope_fraction)
        k = L.apply_rope(k, q_pos, cfg.rope_theta, cfg.rope_fraction)
    if name_tag is not None:
        q, k, v = name_tag(q), name_tag(k), name_tag(v)
    cache = cache_append(cache, k, v, q_pos, cache_offset)
    out = dist_attention(q, cache.k, cache.v, q_pos, cache.pos, ctx,
                         causal=True, kv_view=kv_view, q_start=q_start)
    out = out.reshape(B, Tl, H * hd)
    if name_tag is not None:
        out = name_tag(out)
    y = out @ p["wo"]
    return y, cache


def gqa_decode_attention(x, p, cfg, ctx: Ctx, cache: KVCache, step_pos,
                         my_slot):
    """Single-token decode. x: [B_loc, 1, d]; step_pos: [] int32 global pos.

    Cache layout is striped: token t lives on rank (t % sp) at slot (t // sp).
    `my_slot` is this rank's write slot or -1 (no write this step) — computed
    by the caller from step_pos and the rank index.
    """
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"])
    k = (x @ p["wk"])
    v = (x @ p["wv"])
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, H, hd)
    k = k.reshape(B, 1, Hkv, hd)
    v = v.reshape(B, 1, Hkv, hd)
    pos_arr = jnp.full((1,), step_pos, jnp.int32)
    if cfg.rope:
        q = L.apply_rope(q, pos_arr, cfg.rope_theta, cfg.rope_fraction)
        k = L.apply_rope(k, pos_arr, cfg.rope_theta, cfg.rope_fraction)
    # conditional striped write: write at my_slot if it's mine, else write a
    # PAD entry into a scratch tail slot (slot S_loc-1 reserved... instead we
    # mask by writing the same values but position PAD, which the kernel
    # ignores). Simpler: select on position tag only.
    slot = jnp.maximum(my_slot, 0)
    mine = my_slot >= 0
    new_pos = jnp.where(mine, step_pos, cache.pos[slot])
    k_old = jax.lax.dynamic_slice(cache.k, (0, slot, 0, 0),
                                  (B, 1, Hkv, hd))
    v_old = jax.lax.dynamic_slice(cache.v, (0, slot, 0, 0),
                                  (B, 1, Hkv, hd))
    k_w = jnp.where(mine, k.astype(cache.k.dtype), k_old)
    v_w = jnp.where(mine, v.astype(cache.v.dtype), v_old)
    cache = cache_append(cache, k_w, v_w, new_pos[None], slot)
    # q is identical on every model rank (x replicated for decode), so no
    # gather: run the partial kernel directly and merge.
    o, m, l = kops.attention_partial(q, cache.k, cache.v, pos_arr, cache.pos,
                                     causal=True)
    m = jax.lax.stop_gradient(m)
    m_g = jax.lax.stop_gradient(ctx.pmax_model(m))
    alpha = jnp.exp(m - m_g)
    o = ctx.psum_model(o * alpha[..., None])
    l = ctx.psum_model(l * alpha)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return y, cache


class PooledKV(NamedTuple):
    """Paged KV pool (one layer, one rank): physical block storage shared by
    every request slot through a block table (runtime/kvpool.py).  Unlike
    KVCache there is no batch dim and no position array — logical slot j has
    the static per-rank position ``pos_map[j]`` for every request."""

    k: jax.Array        # [P_loc, Hkv, hd]
    v: jax.Array        # [P_loc, Hkv, hd]


class PagedMeta(NamedTuple):
    """Per-step paged-decode metadata (ChunkMeta.paged).

    q_pos is per-request: slot b feeds its token at global position q_pos[b]
    (0 marks an inactive slot — its write is dropped and its output is
    discarded by the scheduler).  btab maps logical blocks to physical pool
    blocks (-1 = unallocated; such slots are causally masked because their
    pos_map position exceeds the request's horizon).  base / s_bucket /
    block_tokens are static geometry (PoolGeometry).
    """

    q_pos: Any          # [B] int32 per-request global feed position
    btab: Any           # [B, max_blocks] int32 block table
    pos_map: Any        # [L_loc] int32 static positions of logical slots
    base: int           # prefill logical slots per rank (static)
    s_bucket: int       # padded prompt bucket length (static)
    block_tokens: int   # logical slots per block (static)


def gqa_paged_decode_attention(x, p, cfg, ctx: Ctx, pool: PooledKV,
                               pg: PagedMeta):
    """Single-token decode against the paged pool. x: [B, 1, d].

    Every request slot carries its *own* position (pg.q_pos), so rows at
    different decode depths batch together.  The write is striped like the
    static path — decode token d lives on rank (d % sp) at logical slot
    (base + d // sp) — routed through the block table to a physical slot;
    non-owning ranks and inactive slots write to an out-of-bounds sentinel
    that scatter-drops (never -1: jnp wraps negative indices).
    """
    B = x.shape[0]
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, H, hd)
    k = k.reshape(B, 1, Hkv, hd)
    v = v.reshape(B, 1, Hkv, hd)
    qpos = pg.q_pos[:, None]                     # [B, 1] per-row positions
    if cfg.rope:
        q = L.apply_rope(q, qpos, cfg.rope_theta, cfg.rope_fraction)
        k = L.apply_rope(k, qpos, cfg.rope_theta, cfg.rope_fraction)

    sp, rank = ctx.sp, ctx.model_index()
    bt = pg.block_tokens
    p_loc = pool.k.shape[0]
    l_loc = pg.pos_map.shape[0]
    d = pg.q_pos - pg.s_bucket                   # [B] decode index (<0: none)
    mine = (d >= 0) & (d % sp == rank)
    j_w = jnp.clip(pg.base + d // sp, 0, l_loc - 1)
    blk = jnp.take_along_axis(pg.btab, (j_w // bt)[:, None], axis=1)[:, 0]
    phys_w = jnp.where(mine & (blk >= 0), blk * bt + j_w % bt, p_loc)
    pool = PooledKV(
        k=pool.k.at[phys_w].set(k[:, 0].astype(pool.k.dtype), mode="drop"),
        v=pool.v.at[phys_w].set(v[:, 0].astype(pool.v.dtype), mode="drop"))

    # per-request gather in logical-slot order: identical kv ordering to the
    # static cache, so a solo request decodes bit-identically to the static
    # lock-step loop regardless of which physical blocks it landed in
    jlog = jnp.arange(l_loc)
    blk_g = pg.btab[:, jlog // bt]               # [B, L_loc]
    phys_g = jnp.clip(blk_g, 0) * bt + jlog % bt
    k_g = pool.k[phys_g]                         # [B, L_loc, Hkv, hd]
    v_g = pool.v[phys_g]
    o, m, l = kops.attention_partial(q, k_g, v_g, qpos, pg.pos_map,
                                     causal=True)
    m = jax.lax.stop_gradient(m)
    m_g = jax.lax.stop_gradient(ctx.pmax_model(m))
    alpha = jnp.exp(m - m_g)
    o = ctx.psum_model(o * alpha[..., None])
    l = ctx.psum_model(l * alpha)
    out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    y = out.reshape(B, 1, H * hd) @ p["wo"]
    return y, pool


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3 multi-head latent attention), absorbed form
# ---------------------------------------------------------------------------


def mla_attention(x, p, cfg, ctx: Ctx, cache: KVCache, q_pos, cache_offset,
                  kv_view, *, name_tag=None, decode=False, my_slot=None,
                  q_start=None):
    """Multi-head latent attention.  The cache stores the compressed latent
    kv = [c_kv (kv_lora) | k_rope (rope_hd)] per token — MLA's memory edge.
    Scores use the absorbed form: q_eff = [q_nope @ W_uk | q_rope], shared
    single KV "head"; values are the latent, up-projected after attention.
    """
    mla = cfg.mla
    B, Tl, _ = x.shape
    H = cfg.n_heads
    dn, dr, dv, dc = mla.nope_head_dim, mla.rope_head_dim, mla.v_head_dim, mla.kv_lora_rank

    # --- queries (LoRA down/up), rope/nope split
    cq = L.rms_norm(x @ p["wq_a"], p["q_norm"])           # [B,T,q_lora]
    q = (cq @ p["wq_b"]).reshape(B, Tl, H, dn + dr)
    q_nope, q_rope = q[..., :dn], q[..., dn:]
    # --- latent kv
    ckv_full = x @ p["wkv_a"]                              # [B,T,dc+dr]
    c_kv = L.rms_norm(ckv_full[..., :dc], p["kv_norm"])
    k_rope = ckv_full[..., None, dc:]                      # [B,T,1,dr]
    pos_arr = q_pos if q_pos.ndim == 1 else q_pos[0]
    q_rope = L.apply_rope(q_rope, q_pos, cfg.rope_theta)
    k_rope = L.apply_rope(k_rope, q_pos, cfg.rope_theta)
    # absorbed q: [B,T,H,dn] @ [H,dn,dc] -> [B,T,H,dc]
    q_abs = jnp.einsum("bthn,hnc->bthc", q_nope, p["w_uk"])
    q_eff = jnp.concatenate([q_abs, q_rope], axis=-1)      # [B,T,H,dc+dr]
    k_eff = jnp.concatenate([c_kv[:, :, None, :], k_rope], axis=-1)
    if name_tag is not None:
        q_eff, k_eff = name_tag(q_eff), name_tag(k_eff)
    scale = 1.0 / ((dn + dr) ** 0.5)

    if decode:
        slot = jnp.maximum(my_slot, 0)
        mine = my_slot >= 0
        new_pos = jnp.where(mine, pos_arr[0], cache.pos[slot])
        k_old = jax.lax.dynamic_slice(cache.k, (0, slot, 0, 0),
                                      (B, 1, 1, dc + dr))
        k_w = jnp.where(mine, k_eff.astype(cache.k.dtype), k_old)
        cache = KVCache(
            k=jax.lax.dynamic_update_slice(cache.k, k_w, (0, slot, 0, 0)),
            v=cache.v,
            pos=jax.lax.dynamic_update_slice(cache.pos, new_pos[None], (slot,)))
        kv = cache.k
        o, m, l = kops.attention_partial(q_eff, kv, kv[..., :dc], pos_arr,
                                         cache.pos, causal=True, scale=scale)
        m = jax.lax.stop_gradient(m)
        m_g = jax.lax.stop_gradient(ctx.pmax_model(m))
        alpha = jnp.exp(m - m_g)
        o = ctx.psum_model(o * alpha[..., None])
        l = ctx.psum_model(l * alpha)
        out = (o / jnp.maximum(l, 1e-30)[..., None]).astype(x.dtype)
    else:
        cache = KVCache(
            k=jax.lax.dynamic_update_slice(
                cache.k, k_eff.astype(cache.k.dtype),
                (jnp.int32(0), jnp.asarray(cache_offset, jnp.int32),
                 jnp.int32(0), jnp.int32(0))),
            v=cache.v,
            pos=jax.lax.dynamic_update_slice(
                cache.pos, pos_arr.astype(jnp.int32),
                (jnp.asarray(cache_offset, jnp.int32),)))
        kv = cache.k[:, :kv_view]
        out = dist_attention(q_eff, kv, kv[..., :dc], q_pos,
                             cache.pos[:kv_view], ctx, causal=True,
                             scale=scale, q_start=q_start)
    # up-project latent values per head then output proj
    o_v = jnp.einsum("bthc,hcv->bthv", out, p["w_uv"])     # [B,T,H,dv]
    if name_tag is not None:
        o_v = name_tag(o_v)
    y = o_v.reshape(B, Tl, H * dv) @ p["wo"]
    return y, cache


# ---------------------------------------------------------------------------
# Cross-attention (vlm image layers / whisper decoder) — chunk-invariant KV
# ---------------------------------------------------------------------------


def cross_attention(x, p, cfg, ctx: Ctx, xkv, *, name_tag=None):
    """x: [B, T_loc, d]; xkv: precomputed context KV
    (k [B, Nctx_loc, Hkv, hd], v ..., pos [Nctx_loc]) sharded over `model`.
    Bidirectional over the context (causal=False)."""
    B, Tl, _ = x.shape
    H, Hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = (x @ p["wq"]).reshape(B, Tl, H, hd)
    if name_tag is not None:
        q = name_tag(q)
    q_pos = jnp.zeros((Tl,), jnp.int32)  # positions unused when causal=False
    out = dist_attention(q, xkv["k"], xkv["v"], q_pos, xkv["pos"], ctx,
                         causal=False)
    out = out.reshape(B, Tl, H * hd)
    if name_tag is not None:
        out = name_tag(out)
    return out @ p["wo"]


def make_cross_kv(context, p, cfg, ctx: Ctx, n_valid: int):
    """context: [B, Nctx_loc, d] sequence-sharded stub embeddings.
    n_valid: global count of real (non-padded) context tokens."""
    B, Nl, _ = context.shape
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    k = (context @ p["wk"]).reshape(B, Nl, Hkv, hd)
    v = (context @ p["wv"]).reshape(B, Nl, Hkv, hd)
    gidx = ctx.model_index() * Nl + jnp.arange(Nl, dtype=jnp.int32)
    pos = jnp.where(gidx < n_valid, gidx, PAD)
    return {"k": k, "v": v, "pos": pos}

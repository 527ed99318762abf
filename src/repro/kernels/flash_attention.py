"""Pallas TPU kernels: chunked-causal flash attention, forward + backward.

This is the compute hot-spot of SPPO's subsequence processing: the attention
of one subsequence (chunk) of queries against the device-local shard of the
accumulated KV cache (all previous chunks + the current one).  Causality
across chunks is positional: visibility is ``q_pos >= kv_pos`` on *global*
token positions, so the same kernel serves intra-chunk causal attention,
cross-chunk cache attention, decode (Tq == 1 padded to a block) and
bidirectional encoder attention (causal=False).

TPU mapping (target: v5e — MXU 128x128, 16 MiB scoped VMEM/core):
  grid = (B * Hkv, Tq // bq, S // bk) with the KV dimension innermost
  ("arbitrary" semantics) so the (m, l, acc) accumulators live in VMEM
  scratch across KV steps.  A block holds G * bq query rows — q rows for
  all G grouped query heads of one KV head are folded into the q-block row
  dimension, so GQA costs no extra KV traffic: the [bk, hd] KV block is
  streamed once per q block for all G heads.

Tiles (``_tiles``) are chosen from the call's static shapes, not fixed:
  bk is the largest of 1024/512/256/128 dividing S rounded up to 128 (no
  padding beyond 128's), bq the largest power of two dividing Tq rounded up
  to 128 with G * bq <= 512 rows, and lengths below a tile take the length
  rounded up to 8 (decode: bq = 8).  Most of a kernel's time at small
  tiles is per grid step and per row (the online-softmax row updates, the
  lane-padded [G*bq, 1] position and statistic blocks), so larger tiles
  amortise it: on a v5e at the 16K cell's chunks, 512 x 512 and
  512 x 1024 run the three kernels about 5x faster than 128 x 128
  (PERF.md §6).

VMEM budget (``_vmem_bytes``, everything counted as fp32): three
  [G*bq, bk] score tiles, the upcast q/do/k/v blocks, double-buffered
  input blocks and the larger accumulator side; bk, then bq, halves until
  it is under the 16 MiB default.  512 x 1024 at hd 128 estimates 15.6 MiB
  where the TPU compiler needs 10.2 (dkv, the largest pass there).

Outputs are the *partial* (o, m, l) triple (see kernels/ref.py) so the
cross-device softmax merge (psum over the `model` axis) composes with the
kernel unchanged.

Backward (SPPO trains — the kernel must differentiate).  The public entry
``flash_attention_partial`` carries a ``jax.custom_vjp``:

  * residuals are (q, k, v, positions, o, m, l) — exactly the per-chunk
    tensors the two-level activation plan (core/offload.py) already budgets:
    q/k/v are recomputed-or-saved Type-1 rows and the (o, m, l) triple is the
    Type-1 attention output.  Nothing quadratic is ever saved.
  * the backward recomputes p = exp(s − m) from the saved per-row logsumexp
    statistic m inside two fused Pallas kernels (DESIGN.md §8):
      - dq:  the forward's grid (B·Hkv, nq, nk), KV innermost, dq accumulated
        in VMEM scratch across KV steps;
      - dkv: the transposed grid (B·Hkv, nk, nq), q innermost, dk/dv
        accumulated in VMEM scratch across q steps (the GQA head fold makes
        the sum over grouped heads implicit in the row reduction).
  * the max statistic m is gradient-frozen (matching kernels/ref.py): its
    contribution cancels exactly in the o/l ratio downstream, and dropping
    its cotangent keeps the cross-device pmax merge differentiable.

Because (o, l) are *un-normalized*, the quotient rule of out = o/l lives in
jnp-land outside the kernel; the kernel backward only needs the cotangents
(do, dl) and never the D = rowsum(do∘out) term of the fused-normalization
formulation.
"""
from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
PAD_POS = 2**30

log = logging.getLogger(__name__)


def _flash_partial_kernel(qpos_ref, kpos_ref, qstart_ref,  # position blocks
                          q_ref, k_ref, v_ref,    # [bq*G, hd] / [bk, hd] blocks
                          o_ref, m_ref, l_ref,    # outputs
                          acc_ref, mm_ref, ll_ref,  # VMEM scratch
                          *, causal: bool, scale: float, nk: int):
    ks = pl.program_id(2)

    @pl.when(ks == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        mm_ref[...] = jnp.full_like(mm_ref, NEG_INF)
        ll_ref[...] = jnp.zeros_like(ll_ref)

    q = q_ref[...].astype(jnp.float32)          # [G*bq, hd]
    k = k_ref[...].astype(jnp.float32)          # [bk, hd]
    v = v_ref[...].astype(jnp.float32)          # [bk, hv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # [G*bq, bk]

    s = jnp.where(_visible(qpos_ref, kpos_ref, qstart_ref, causal),
                  s, NEG_INF)

    m_prev = mm_ref[...]                        # [G*bq, 1]
    m_blk = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    safe = m_new > NEG_INF / 2
    alpha = jnp.where(safe, jnp.exp(m_prev - m_new), 0.0)
    p = jnp.where(safe, jnp.exp(s - m_new), 0.0)
    ll_ref[...] = ll_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    mm_ref[...] = m_new

    @pl.when(ks == nk - 1)
    def _fin():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)
        m_ref[...] = mm_ref[...].astype(m_ref.dtype)
        l_ref[...] = ll_ref[...].astype(l_ref.dtype)


def _visible(qpos_ref, kpos_ref, qstart_ref, causal: bool):
    """[G*bq, bk] visibility mask — identical in forward and backward.
    Query positions arrive already folded like the q rows ([G*bq, 1]
    columns, see ``_fold_rows``) and kv positions as a lane-dense [1, bk]
    row, so the mask is a plain broadcast compare with no in-kernel
    relayout.  ``qstart_ref`` is the per-query segment window
    (packed-document blocking): a kv slot is visible only when
    kv_pos >= q_start.  Zeros degenerate to the plain positional mask;
    PAD_POS marks dead (padding) query rows — no real kv slot reaches
    2**30, so those rows mask fully."""
    qpos = qpos_ref[...]                        # [G*bq, 1] int32
    kpos = kpos_ref[...]                        # [1, bk] int32
    valid = (kpos != PAD_POS) & (kpos >= qstart_ref[...])
    if causal:
        valid = valid & (qpos >= kpos)
    return valid


def _recompute_p_ds(qpos_ref, kpos_ref, qstart_ref, q, k, v, do, m, dl,
                    *, causal: bool, scale: float):
    """Shared backward block math: recompute p from the saved logsumexp row
    statistic, then dS = P ∘ (dO·Vᵀ + dl).  m is treated as a constant (the
    gradient-frozen max statistic, see module docstring)."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    s = jnp.where(_visible(qpos_ref, kpos_ref, qstart_ref, causal),
                  s, NEG_INF)
    # fully-masked rows carry m == NEG_INF; exp(NEG_INF - NEG_INF) would be 1
    safe = m > NEG_INF / 2                       # [G*bq, 1]
    p = jnp.where(safe, jnp.exp(s - m), 0.0)     # [G*bq, bk]
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ()))) + dl
    return p, p * dp


def _flash_bwd_dq_kernel(qpos_ref, kpos_ref, qstart_ref, q_ref, k_ref, v_ref,
                         do_ref, m_ref, dl_ref,
                         dq_ref, dq_acc,
                         *, causal: bool, scale: float, nk: int):
    ks = pl.program_id(2)

    @pl.when(ks == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    _, ds = _recompute_p_ds(qpos_ref, kpos_ref, qstart_ref, q, k, v, do,
                            m_ref[...], dl_ref[...],
                            causal=causal, scale=scale)
    dq_acc[...] += jax.lax.dot_general(
        ds, k, (((1,), (0,)), ((), ()))) * scale

    @pl.when(ks == nk - 1)
    def _fin():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qpos_ref, kpos_ref, qstart_ref, q_ref, k_ref, v_ref,
                          do_ref, m_ref, dl_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc,
                          *, causal: bool, scale: float, nq: int):
    qs = pl.program_id(2)

    @pl.when(qs == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[...].astype(jnp.float32)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    do = do_ref[...].astype(jnp.float32)
    p, ds = _recompute_p_ds(qpos_ref, kpos_ref, qstart_ref, q, k, v, do,
                            m_ref[...], dl_ref[...],
                            causal=causal, scale=scale)
    # row reductions over the G*bq folded q rows sum the GQA group for free
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dk_acc[...] += jax.lax.dot_general(
        ds, q, (((0,), (0,)), ((), ()))) * scale

    @pl.when(qs == nq - 1)
    def _fin():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# Geometry helpers shared by forward and backward
# ---------------------------------------------------------------------------


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


KV_TILES = (1024, 512, 256, 128)            # bk candidates, largest first
Q_TILES = tuple(2 ** i for i in range(10, 2, -1))   # bq: 1024 ... 8
ROW_CAP = 512           # folded q rows (G·bq) a block may hold
VMEM_BUDGET = 16 << 20  # the scoped-VMEM default of a v5e core


def _vmem_bytes(rows: int, bk: int, hd_k: int, hd_v: int) -> int:
    """Estimated live VMEM of the larger backward pass at ``rows`` = G·bq
    folded q rows, every element counted as fp32: three [rows, bk] tiles
    (p, dp, ds; s dies into p), the upcast q/do/k/v blocks, the
    double-buffered input blocks (the [rows, 1] position and statistic
    columns pad to 128 lanes, the [1, bk] kv positions to 8 sublanes), and
    the accumulator with its double-buffered output block — dq on the q
    side in the dq pass, dk/dv on the kv side in the dkv pass."""
    hd = hd_k + hd_v
    tiles = 3 * rows * bk
    upcast = (rows + bk) * hd
    blocks = (rows + bk) * hd + 4 * rows * 128 + 8 * bk
    acc = 3 * max(rows * hd_k, bk * hd)
    return 4 * (tiles + upcast + 2 * blocks + acc)


def _tiles(G: int, Tq: int, S: int, hd_k: int, hd_v: int):
    """Block geometry (bq, bk) of the forward, dq and dkv grids, from the
    call's static shapes.

    bk is the largest of ``KV_TILES`` that divides S rounded up to 128, so
    no padding is streamed beyond 128's; bq the largest power of two from
    1024 down to 8 that divides Tq rounded up to 128 with G·bq at most
    ``ROW_CAP`` (8 where even that exceeds it).  While the estimated VMEM
    exceeds ``VMEM_BUDGET``, bk halves down to 128, then bq down to 8.  A
    length below its tile takes the length rounded up to 8 (decode keeps
    bq = 8)."""
    bk = next(t for t in KV_TILES if _round_up(S, 128) % t == 0)
    bq = next((t for t in Q_TILES
               if G * t <= ROW_CAP and _round_up(Tq, 128) % t == 0), 8)
    while _vmem_bytes(G * bq, bk, hd_k, hd_v) > VMEM_BUDGET:
        if bk > KV_TILES[-1]:
            bk //= 2
        elif bq > Q_TILES[-1]:
            bq //= 2
        else:
            break
    return min(bq, _round_up(Tq, 8)), min(bk, _round_up(S, 8))


@functools.lru_cache(maxsize=None)     # once per attention shape
def _log_tiles(G, Tq, S, hd_k, hd_v, bq, bk, nq, nk):
    log.info("flash tiles: G %d Tq %d S %d hd %d/%d -> bq %d bk %d, "
             "%d x %d grid steps per KV head", G, Tq, S, hd_k, hd_v, bq, bk,
             nq, nk)


def _geometry(G, Tq, S, hd_k, hd_v, block_q, block_k):
    """(bq, bk, Tqp, Sp, nq, nk); a block left None takes ``_tiles``'s."""
    auto_q, auto_k = _tiles(G, Tq, S, hd_k, hd_v)
    bq = min(block_q or auto_q, _round_up(Tq, 8))
    bk = min(block_k or auto_k, _round_up(S, 8))
    Tqp, Sp = _round_up(Tq, bq), _round_up(S, bk)
    nq, nk = Tqp // bq, Sp // bk
    if block_q is None or block_k is None:
        _log_tiles(G, Tq, S, hd_k, hd_v, bq, bk, nq, nk)
    return bq, bk, Tqp, Sp, nq, nk


def _pad_inputs(q, k, v, q_pos, kv_pos, q_start, Tqp, Sp):
    Tq, S = q.shape[1], k.shape[1]
    if Tqp != Tq:
        q = jnp.pad(q, ((0, 0), (0, Tqp - Tq), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, Tqp - Tq)), constant_values=-1)
        # block-padding query rows are dead: q_start = PAD_POS masks them
        q_start = jnp.pad(q_start, ((0, 0), (0, Tqp - Tq)),
                          constant_values=PAD_POS)
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, (0, Sp - S), constant_values=PAD_POS)
    return q, k, v, q_pos, kv_pos, q_start


def _fold_q_like(x, B, Hkv, G, nq, bq, last):
    """[B, Tqp, H, last] -> [B*Hkv, nq, G*bq, last] (GQA head fold)."""
    return (x.reshape(B, nq, bq, Hkv, G, last)
             .transpose(0, 3, 1, 4, 2, 5)
             .reshape(B * Hkv, nq, G * bq, last))


def _unfold_q_like(x, B, Hkv, G, nq, bq, last, Tq):
    x = x.reshape(B, Hkv, nq, G, bq, last).transpose(0, 2, 4, 1, 3, 5)
    return x.reshape(B, nq * bq, Hkv * G, last)[:, :Tq]


def _fold_kv(x, B, Hkv, Sp, last):
    return x.transpose(0, 2, 1, 3).reshape(B * Hkv, Sp, last)


def _fold_rows(x, G, nq, bq):
    """Per-query int32 [B, Tqp] -> [B, nq, G*bq, 1]: the value of folded q
    row ``g*bq + t`` (``_fold_q_like``'s row order), one column per q block,
    so the kernel reads it in the same [G*bq, 1] layout as its m/l rows."""
    B = x.shape[0]
    x = jnp.broadcast_to(x.reshape(B, nq, 1, bq), (B, nq, G, bq))
    return x.reshape(B, nq, G * bq, 1)


def _pos_specs(G, bq, bk, Hkv, kv_inner: bool):
    """BlockSpecs of (q_pos, kv_pos, q_start) for a grid whose axes are
    (B*Hkv, q block, kv block) when `kv_inner`, else (B*Hkv, kv block,
    q block).  q positions vary per batch row (paged decode gives every row
    its own position; packed layouts differ row to row): grid axis 0 is
    B*Hkv, so row = b // Hkv.  kv positions are one lane-dense [1, Sp] row."""
    if kv_inner:
        qmap = lambda b, i, j: (b // Hkv, i, 0, 0)
        kmap = lambda b, i, j: (0, j)
    else:
        qmap = lambda b, j, i: (b // Hkv, i, 0, 0)
        kmap = lambda b, j, i: (0, j)
    qspec = pl.BlockSpec((None, None, G * bq, 1), qmap)
    return [qspec, pl.BlockSpec((1, bk), kmap), qspec]


# ---------------------------------------------------------------------------
# Forward / backward pallas_call wrappers
# ---------------------------------------------------------------------------


def _fwd_impl(q, k, v, q_pos, kv_pos, q_start, causal, scale, block_q,
              block_k, interpret):
    B, Tq, H, hdk = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // Hkv
    bq, bk, Tqp, Sp, nq, nk = _geometry(G, Tq, S, hdk, hdv, block_q,
                                        block_k)
    q, k, v, q_pos, kv_pos, q_start = _pad_inputs(
        q, k, v, q_pos, kv_pos, q_start, Tqp, Sp)

    qg = _fold_q_like(q, B, Hkv, G, nq, bq, hdk)
    kg = _fold_kv(k, B, Hkv, Sp, hdk)
    vg = _fold_kv(v, B, Hkv, Sp, hdv)
    pos = (_fold_rows(q_pos, G, nq, bq), kv_pos.reshape(1, Sp),
           _fold_rows(q_start, G, nq, bq))

    grid = (B * Hkv, nq, nk)
    kern = functools.partial(_flash_partial_kernel, causal=causal,
                             scale=scale, nk=nk)
    o, m, l = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=_pos_specs(G, bq, bk, Hkv, kv_inner=True) + [
            pl.BlockSpec((None, None, G * bq, hdk), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, bk, hdk), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hdv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, G * bq, hdv), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, nq, G * bq, hdv), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, nq, G * bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, nq, G * bq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((G * bq, hdv), jnp.float32),   # acc
            pltpu.VMEM((G * bq, 1), jnp.float32),     # running max
            pltpu.VMEM((G * bq, 1), jnp.float32),     # running sum
        ],
        interpret=interpret,
    )(*pos, qg, kg, vg)

    o = _unfold_q_like(o, B, Hkv, G, nq, bq, hdv, Tq)
    m = _unfold_q_like(m, B, Hkv, G, nq, bq, 1, Tq)[..., 0]
    l = _unfold_q_like(l, B, Hkv, G, nq, bq, 1, Tq)[..., 0]
    return o, m, l


def _bwd_impl(q, k, v, q_pos, kv_pos, q_start, do, m, dl, causal, scale,
              block_q, block_k, interpret):
    """dq/dk/dv via the two fused backward grids; all accumulation fp32."""
    B, Tq, H, hdk = q.shape
    S, Hkv = k.shape[1], k.shape[2]
    hdv = v.shape[-1]
    G = H // Hkv
    bq, bk, Tqp, Sp, nq, nk = _geometry(G, Tq, S, hdk, hdv, block_q,
                                        block_k)
    # fully-masked rows (m == NEG_INF) have o == l == 0 identically; their
    # cotangents are meaningless and can be inf/NaN (the 1/l² of the
    # downstream quotient rule overflows fp32) — zero them so 0·NaN can't
    # poison dq/dk through the p·dS products
    live = (m > NEG_INF / 2)
    do = jnp.where(live[..., None], do, 0.0)
    dl = jnp.where(live, dl, 0.0)
    q, k, v, q_pos, kv_pos, q_start = _pad_inputs(
        q, k, v, q_pos, kv_pos, q_start, Tqp, Sp)
    if Tqp != Tq:
        do = jnp.pad(do, ((0, 0), (0, Tqp - Tq), (0, 0), (0, 0)))
        # padded rows get m = NEG_INF: the safe-row guard zeroes their p
        m = jnp.pad(m, ((0, 0), (0, Tqp - Tq), (0, 0)),
                    constant_values=NEG_INF)
        dl = jnp.pad(dl, ((0, 0), (0, Tqp - Tq), (0, 0)))

    qg = _fold_q_like(q, B, Hkv, G, nq, bq, hdk)
    kg = _fold_kv(k, B, Hkv, Sp, hdk)
    vg = _fold_kv(v, B, Hkv, Sp, hdv)
    dog = _fold_q_like(do.astype(jnp.float32), B, Hkv, G, nq, bq, hdv)
    mg = _fold_q_like(m[..., None], B, Hkv, G, nq, bq, 1)
    dlg = _fold_q_like(dl.astype(jnp.float32)[..., None], B, Hkv, G, nq, bq, 1)
    pos = (_fold_rows(q_pos, G, nq, bq), kv_pos.reshape(1, Sp),
           _fold_rows(q_start, G, nq, bq))

    # --- dq: forward's grid, KV innermost, dq accumulates in scratch
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, causal=causal, scale=scale,
                          nk=nk),
        grid=(B * Hkv, nq, nk),
        in_specs=_pos_specs(G, bq, bk, Hkv, kv_inner=True) + [
            pl.BlockSpec((None, None, G * bq, hdk), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, bk, hdk), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, bk, hdv), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, None, G * bq, hdv), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, i, j: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, i, j: (b, i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, G * bq, hdk),
                               lambda b, i, j: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * Hkv, nq, G * bq, hdk),
                                       jnp.float32),
        scratch_shapes=[pltpu.VMEM((G * bq, hdk), jnp.float32)],
        interpret=interpret,
    )(*pos, qg, kg, vg, dog, mg, dlg)

    # --- dk/dv: transposed grid, q innermost, dk/dv accumulate in scratch
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, causal=causal, scale=scale,
                          nq=nq),
        grid=(B * Hkv, nk, nq),
        in_specs=_pos_specs(G, bq, bk, Hkv, kv_inner=False) + [
            pl.BlockSpec((None, None, G * bq, hdk), lambda b, j, i: (b, i, 0, 0)),
            pl.BlockSpec((None, bk, hdk), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, bk, hdv), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((None, None, G * bq, hdv), lambda b, j, i: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, j, i: (b, i, 0, 0)),
            pl.BlockSpec((None, None, G * bq, 1), lambda b, j, i: (b, i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, None, bk, hdk), lambda b, j, i: (b, j, 0, 0)),
            pl.BlockSpec((None, None, bk, hdv), lambda b, j, i: (b, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * Hkv, nk, bk, hdk), jnp.float32),
            jax.ShapeDtypeStruct((B * Hkv, nk, bk, hdv), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, hdk), jnp.float32),
            pltpu.VMEM((bk, hdv), jnp.float32),
        ],
        interpret=interpret,
    )(*pos, qg, kg, vg, dog, mg, dlg)

    dq = _unfold_q_like(dq, B, Hkv, G, nq, bq, hdk, Tq)

    def unfold_kv(x, last):
        return x.reshape(B, Hkv, Sp, last).transpose(0, 2, 1, 3)[:, :S]

    return dq, unfold_kv(dk, hdk), unfold_kv(dv, hdv)


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _flash_partial(q, k, v, q_pos, kv_pos, q_start, causal, scale, block_q,
                   block_k, interpret):
    return _fwd_impl(q, k, v, q_pos, kv_pos, q_start, causal, scale, block_q,
                     block_k, interpret)


def _flash_partial_fwd(q, k, v, q_pos, kv_pos, q_start, causal, scale,
                       block_q, block_k, interpret):
    o, m, l = _fwd_impl(q, k, v, q_pos, kv_pos, q_start, causal, scale,
                        block_q, block_k, interpret)
    # (q, k, v, positions, o, m, l): the Type-1 residual set the offload
    # planner budgets.  The recompute-based kernels consume only m (o and l
    # alias the primal outputs, so saving them costs nothing extra on
    # device); the planner may still row-split any of them to pinned_host.
    return (o, m, l), (q, k, v, q_pos, kv_pos, q_start, o, m, l)


def _flash_partial_bwd(causal, scale, block_q, block_k, interpret, res, cts):
    q, k, v, q_pos, kv_pos, q_start, _o, m, _l = res
    do, _dm, dl = cts   # the max statistic is gradient-frozen (kernels/ref.py)
    dq, dk, dv = _bwd_impl(q, k, v, q_pos, kv_pos, q_start, do, m, dl,
                           causal, scale, block_q, block_k, interpret)

    def zero_pos(p):    # int positions: cotangent space is float0
        return np.zeros(np.shape(p), jax.dtypes.float0)

    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            zero_pos(q_pos), zero_pos(kv_pos), zero_pos(q_start))


_flash_partial.defvjp(_flash_partial_fwd, _flash_partial_bwd)


def flash_attention_partial(q, k, v, q_pos, kv_pos, *, causal=True,
                            scale=None, block_q=None, block_k=None,
                            interpret=False, q_start=None):
    """Pallas partial flash attention (differentiable in q, k, v).

    q: [B, Tq, H, hd_k]; k: [B, S, Hkv, hd_k]; v: [B, S, Hkv, hd_v]
    q_pos: [Tq] or [B, Tq]; kv_pos: [S]  (2**30 == padding)
    q_start: optional [B, Tq] or [Tq] segment window — kv slots below
    q_start are masked (packed-document blocking); None degenerates to the
    plain positional mask (a zero window changes no visibility bit).
    block_q / block_k: the q and kv block lengths; None (the default) takes
    the shape-chosen ``_tiles``.
    Returns (o [B,Tq,H,hd_v] f32 un-normalized, m [B,Tq,H] f32, l [B,Tq,H] f32).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    B, Tq = q.shape[0], q.shape[1]
    if q_pos.ndim == 1:
        q_pos = jnp.broadcast_to(q_pos[None, :], (B, Tq))
    if q_start is None:
        q_start = jnp.zeros((B, Tq), jnp.int32)
    elif q_start.ndim == 1:
        q_start = jnp.broadcast_to(q_start[None, :], (B, Tq))
    return _flash_partial(q, k, v, q_pos, kv_pos, q_start, bool(causal),
                          float(scale), block_q, block_k, bool(interpret))

"""Mixture-of-Experts with expert parallelism over the `model` axis.

Token-choice top-k routing with capacity-bounded, all_to_all dispatch:

  1. route local tokens (router weight is gathered — it's tiny);
  2. scatter token copies into per-destination-rank send buffers
     [sp, C, d] (C = capacity per src->dst pair, static);
  3. all_to_all over the model axis (the EP dispatch collective);
  4. second-level scatter into per-local-expert capacity buffers and one
     batched matmul per expert stack [E_loc, C_e, *];
  5. inverse all_to_all, weighted combine of the top-k returns.

Over-capacity token copies are dropped (standard capacity-factor semantics);
tests pin cf high enough to verify exact equivalence with the dense oracle.
DeepSeek's shared expert runs densely on the local shard.  The auxiliary
load-balancing loss (Switch-style f·P) is returned for the trainer.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.parallel.ctx import Ctx
from repro.runtime import scopes


def moe_dims(cfg, sp: int):
    E = cfg.moe.num_experts
    assert E % sp == 0, f"experts {E} must divide model axis {sp}"
    return E, E // sp


@scopes.scoped(scopes.MLP)
def moe_block(x_loc, p, cfg, ctx: Ctx, *, name_tag=None) -> Tuple[jax.Array, jax.Array]:
    """x_loc: [B, T_loc, d] sequence shard. Returns (y [B,T_loc,d], aux)."""
    moe = cfg.moe
    B, Tl, d = x_loc.shape
    sp = ctx.sp
    E, E_loc = moe_dims(cfg, sp)
    K = moe.top_k
    n_tok = B * Tl
    xt = x_loc.reshape(n_tok, d)

    # ---- routing (fp32) ----------------------------------------------------
    logits = (xt @ p["router"]).astype(jnp.float32)          # [n, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                   # [n, K]
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize
    # Switch aux loss: E * mean(f_e * P_e)
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(top_e, E), axis=1), axis=0)
    P_e = jnp.mean(probs, axis=0)
    aux = E * jnp.sum(f_e * P_e)

    # ---- level-1 dispatch: per-destination-rank send buffers ---------------
    flat_e = top_e.reshape(-1)                               # [n*K]
    flat_w = top_p.reshape(-1)
    flat_tok = jnp.repeat(jnp.arange(n_tok), K)
    dst = flat_e // E_loc                                    # [n*K] in [0,sp)
    C = max(1, math.ceil(n_tok * K / sp * moe.capacity_factor))
    one = jax.nn.one_hot(dst, sp, dtype=jnp.int32)           # [n*K, sp]
    pos = jnp.sum(jnp.cumsum(one, axis=0) * one, axis=-1) - 1  # pos in dst buf
    keep = pos < C
    dst_c = jnp.where(keep, dst, sp - 1)
    pos_c = jnp.where(keep, pos, C)                          # C = trash slot
    send = jnp.zeros((sp, C + 1, d), x_loc.dtype)
    send = send.at[dst_c, pos_c].set(xt[flat_tok], mode="drop")
    send_eid = jnp.full((sp, C + 1), -1, jnp.int32)
    send_eid = send_eid.at[dst_c, pos_c].set(
        jnp.where(keep, flat_e % E_loc, -1), mode="drop")
    send, send_eid = send[:, :C], send_eid[:, :C]

    # ---- all_to_all over the model axis ------------------------------------
    recv = ctx.all_to_all_model(send, split_axis=0, concat_axis=0)
    recv_eid = ctx.all_to_all_model(send_eid[..., None], 0, 0)[..., 0]
    rt = recv.reshape(sp * C, d)
    re = recv_eid.reshape(sp * C)

    # ---- level-2 dispatch into per-expert capacity buffers -----------------
    Ce = max(1, math.ceil(sp * C / E_loc * moe.capacity_factor))
    valid = re >= 0
    eid = jnp.where(valid, re, 0)
    one2 = jax.nn.one_hot(eid, E_loc, dtype=jnp.int32) * valid[:, None]
    pos2 = jnp.sum(jnp.cumsum(one2, axis=0) * one2, axis=-1) - 1
    pos2 = jnp.where(valid, pos2, Ce)
    keep2 = (pos2 < Ce) & valid
    eid_c = jnp.where(keep2, eid, 0)
    pos2_c = jnp.where(keep2, pos2, Ce)
    buf = jnp.zeros((E_loc, Ce + 1, d), x_loc.dtype)
    buf = buf.at[eid_c, pos2_c].set(jnp.where(keep2[:, None], rt, 0),
                                    mode="drop")
    buf = buf[:, :Ce]

    # ---- expert FFNs (batched over the local expert stack) -----------------
    h_g = jnp.einsum("ecd,edf->ecf", buf, p["w1"])
    h_u = jnp.einsum("ecd,edf->ecf", buf, p["w3"])
    h = jax.nn.silu(h_g) * h_u
    if name_tag is not None:
        h = name_tag(h)
    out = jnp.einsum("ecf,efd->ecd", h, p["w2"])             # [E_loc, Ce, d]

    # ---- undispatch + return + combine --------------------------------------
    back = out[eid_c, pos2_c] * keep2[:, None].astype(out.dtype)
    back = back.reshape(sp, C, d)
    ret = ctx.all_to_all_model(back, split_axis=0, concat_axis=0)
    got = ret[dst_c, pos_c] * keep[:, None].astype(ret.dtype)  # [n*K, d]
    y = jnp.zeros((n_tok, d), jnp.float32)
    y = y.at[flat_tok].add(got.astype(jnp.float32)
                           * flat_w[:, None].astype(jnp.float32))
    y = y.astype(x_loc.dtype)

    # ---- shared experts (dense, deepseek) -----------------------------------
    if moe.n_shared_experts:
        g = xt @ p["ws1"]
        u = xt @ p["ws3"]
        hs = jax.nn.silu(g) * u
        if name_tag is not None:
            hs = name_tag(hs)
        y = y + hs @ p["ws2"]

    return y.reshape(B, Tl, d), aux

"""Shared model layers: norms, RoPE, MLPs, embeddings, vocab-parallel loss.

Conventions (see DESIGN.md §4):
  * Activations are sequence-sharded over the `model` axis: x is
    [B, T_local, d_model] with full d_model per rank.
  * Weights arrive here already *gathered* (full) — storage sharding and the
    per-layer all-gather happen in the runner.  Exceptions (embedding table,
    LM head, MoE experts) stay sharded and use the collective helpers below.
  * Norm/softmax math in fp32; matmul I/O in the model dtype (bf16 target).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.parallel.ctx import Ctx
from repro.runtime import scopes

# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps: float = 1e-6):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


@scopes.scoped(scopes.NORM)
def apply_norm(x, p, kind: str):
    if kind == "rmsnorm":
        return rms_norm(x, p["scale"])
    return layer_norm(x, p["scale"], p["bias"])


# ---------------------------------------------------------------------------
# RoPE (positions given explicitly — chunked execution needs global offsets)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, fraction: float = 1.0):
    rot = int(head_dim * fraction) // 2 * 2
    inv = 1.0 / (theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot))
    return inv, rot


def apply_rope(x, positions, theta: float, fraction: float = 1.0):
    """x: [B, T, H, hd]; positions: [B, T] or [T] int32 global positions."""
    hd = x.shape[-1]
    inv, rot = rope_freqs(hd, theta, fraction)
    if rot == 0:
        return x
    pos = positions.astype(jnp.float32)
    if pos.ndim == 1:
        pos = pos[None, :]
    ang = pos[..., None] * inv[None, None, :]          # [B, T, rot/2]
    cos = jnp.cos(ang)[:, :, None, :]                  # [B, T, 1, rot/2]
    sin = jnp.sin(ang)[:, :, None, :]
    xr = x[..., :rot].astype(jnp.float32)
    x1, x2 = xr[..., 0::2], xr[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape[:-1] + (rot,))
    if rot < hd:
        out = jnp.concatenate([out, xr_pass := x[..., rot:].astype(jnp.float32)], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Activations / MLP
# ---------------------------------------------------------------------------


def _act(h, kind: str):
    if kind == "gelu":
        return jax.nn.gelu(h)
    if kind == "relu2":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(kind)


@scopes.scoped(scopes.MLP)
def mlp(x, p, act: str, *, name_tag=None):
    """Standard transformer MLP. Gated (swiglu/geglu) uses w1 (gate) + w3 (up).

    name_tag: optional fn applied to the big [.., d_ff] intermediate so the
    SPPO offload policy can route it (two-level activation management).
    """
    if act in ("swiglu", "geglu"):
        g = x @ p["w1"]
        u = x @ p["w3"]
        h = (jax.nn.silu(g) if act == "swiglu" else jax.nn.gelu(g)) * u
    else:
        h = x @ p["w1"]
        if "b1" in p:
            h = h + p["b1"]
        h = _act(h, act)
    if name_tag is not None:
        h = name_tag(h)
    y = h @ p["w2"]
    if "b2" in p:
        y = y + p["b2"]
    return y


# ---------------------------------------------------------------------------
# Vocab-parallel embedding (table sharded on vocab over `model` axis)
# ---------------------------------------------------------------------------


def pad_vocab(v: int, multiple: int = 128) -> int:
    return (v + multiple - 1) // multiple * multiple


def embed_tokens(ids, table_local, ctx: Ctx, *, out_dtype=jnp.bfloat16):
    """ids: [B, T] global token ids; table_local: [Vp/sp, d] this rank's rows.

    Returns the *sequence shard* [B, T/sp, d]: masked local gather followed by
    a reduce-scatter over the sequence dim (one collective, half the bytes of
    a psum).  Single-device: plain gather, full sequence.
    """
    vloc = table_local.shape[0]
    lo = ctx.model_index() * vloc
    idx = jnp.clip(ids - lo, 0, vloc - 1)
    hit = ((ids >= lo) & (ids < lo + vloc))[..., None]
    out = jnp.where(hit, jnp.take(table_local, idx, axis=0), 0).astype(out_dtype)
    return ctx.reduce_scatter_model(out, axis=1)


# ---------------------------------------------------------------------------
# Vocab-parallel LM head + cross entropy
# ---------------------------------------------------------------------------


def vocab_parallel_xent(x_local, head_local, labels, mask, ctx: Ctx,
                        *, real_vocab: int):
    """x_local: [B, T/sp, d] sequence shard (full d); head_local: [d, Vp/sp];
    labels/mask: [B, T] for the full (chunk) sequence.

    All-gathers x over the sequence (cheap: d-sized), computes the local
    vocab-shard logits, and reduces scalar statistics — the full-vocab logits
    tensor never materializes on any device (Megatron vocab-parallel CE).
    Returns (sum_loss, sum_correct_logits_grad_path) summed over tokens.
    """
    x = ctx.all_gather_model(x_local, axis=1)            # [B, T, d]
    logits = (x @ head_local).astype(jnp.float32)        # [B, T, Vp/sp]
    vloc = logits.shape[-1]
    lo = ctx.model_index() * vloc
    # mask out padded vocab columns
    col = lo + jnp.arange(vloc)
    logits = jnp.where(col[None, None, :] < real_vocab, logits, -1e30)

    # max statistic is gradient-frozen (cancels in the softmax ratio; pmax
    # has no VJP)
    m = jax.lax.stop_gradient(
        ctx.pmax_model(jax.lax.stop_gradient(jnp.max(logits, axis=-1))))
    z = jnp.exp(logits - m[..., None])
    l = ctx.psum_model(jnp.sum(z, axis=-1))              # [B, T]
    idx = jnp.clip(labels - lo, 0, vloc - 1)
    hit_mask = (labels >= lo) & (labels < lo + vloc)
    picked = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
    hit = ctx.psum_model(jnp.where(hit_mask, picked, 0.0))  # [B, T]

    tok_loss = (jnp.log(l) + m - hit) * mask
    return jnp.sum(tok_loss), jnp.sum(mask)


# ---------------------------------------------------------------------------
# Initialization helpers
# ---------------------------------------------------------------------------


def trunc_normal(key, shape, std, dtype):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def dense_init(key, d_in, d_out, dtype, *, std: Optional[float] = None):
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    return trunc_normal(key, (d_in, d_out), std, dtype)

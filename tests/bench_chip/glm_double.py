"""A stand-in reference of a GLM-4-shaped decoder, for the harness's tests
on the CPU.  It is registered in place of ``references.<name>`` by the
tests alone: it is not GLM-4-9B's reference, which is written from GLM-4's
published description with its own cell.

Per layer (pre-norm):  x += Wo·attn(rope(h·Wq + bq), rope(h·Wk + bk),
h·Wv + bv) with h = RMSNorm1(x);  x += W_down·(silu(h2·W_gate) ⊙ h2·W_up)
with h2 = RMSNorm2(x).  Then the final RMSNorm, the untied head and the
mean cross entropy over every token whose label is >= 0.  Grouped-query
attention: query head i reads KV head i // (heads / kv_heads).  Rotary
turns the first ``partial_rotary_factor`` of each head, in interleaved
pairs, at position * theta**(-2i / rotated width); the rest passes, and
the key bias's passed half, which shifts all of a query's scores alike, is
read apart (``Leaf.parts``).  An RMSNorm gain w is held as g = w − 1,
filled at 0 and applied as (1 + g), as the program holds it: in bfloat16
a gain held at 1.0 would drop every update under half an ulp of 1 (2^-8)
that one held at 0 keeps, and the parameters' change would then compare
storage, not the model.  Float32 at the highest matmul precision, in one
piece: no blocking, for sizes a test run holds.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.chip.references import Leaf

HIGHEST = lax.Precision.HIGHEST


def leaves(m) -> dict:
    L, d, ff = m.layers, m.d, m.ff
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    out_scale = 1.0 / math.sqrt(2 * m.published_layers)
    turned = np.arange(kvd) % m.head_dim < _rotated(m)

    def layer(shape, path, **kw):
        return Leaf((L,) + shape, path, per_layer=True, **kw)

    def matrix(shape, path, std):
        return layer(shape, path, std=std, decay=True, matmul=1.0)

    return {
        "ln1": layer((d,), ("ln1", "scale"), fill=0.0),
        "wq": matrix((d, qd), ("attn", "wq"), 1 / math.sqrt(d)),
        "bq": layer((qd,), ("attn", "bq")),
        "wk": matrix((d, kvd), ("attn", "wk"), 1 / math.sqrt(d)),
        "bk": layer((kvd,), ("attn", "bk"),
                    parts={"rot": turned, "pass": ~turned}),
        "wv": matrix((d, kvd), ("attn", "wv"), 1 / math.sqrt(d)),
        "bv": layer((kvd,), ("attn", "bv")),
        "wo": matrix((qd, d), ("attn", "wo"), out_scale / math.sqrt(qd)),
        "ln2": layer((d,), ("ln2", "scale"), fill=0.0),
        "w_gate": matrix((d, ff), ("mlp", "w1"), 1 / math.sqrt(d)),
        "w_up": matrix((d, ff), ("mlp", "w3"), 1 / math.sqrt(d)),
        "w_down": matrix((ff, d), ("mlp", "w2"), out_scale / math.sqrt(ff)),
        "embed": Leaf((m.vocab, d), ("embed", "table"), per_layer=False,
                      std=0.02, decay=True),
        "head": Leaf((d, m.vocab), ("head", "w"), per_layer=False,
                     std=1 / math.sqrt(d), decay=True, matmul=1.0),
        "lnf": Leaf((d,), ("final_norm", "scale"), per_layer=False, fill=0.0),
    }


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def rms_norm(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * (1.0 + g)


def _rotated(m) -> int:
    """Leading dims of each head that rotary turns."""
    return int(m.head_dim * m.partial_rotary_factor) // 2 * 2


def rope(x, theta, rot):
    """x: [B, T, H, hd]; the first ``rot`` of hd turn."""
    T = x.shape[1]
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0:rot:2], x[..., 1:rot:2]
    turned = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return jnp.concatenate([turned.reshape(x.shape[:-1] + (rot,)),
                            x[..., rot:]], axis=-1)


class Reference:
    def __init__(self, model, *, precision: str = "float32"):
        if precision != "float32":
            raise ValueError(f"precision {precision!r}")
        self.m = model
        self._vg = jax.jit(jax.value_and_grad(self.loss))

    def layer(self, p, x):
        m = self.m
        B, T, _ = x.shape
        h = rms_norm(x, p["ln1"], m.norm_eps)
        q = (_mm(h, p["wq"]) + p["bq"]).reshape(B, T, m.heads, m.head_dim)
        k = (_mm(h, p["wk"]) + p["bk"]).reshape(B, T, m.kv_heads,
                                                m.head_dim)
        v = (_mm(h, p["wv"]) + p["bv"]).reshape(B, T, m.kv_heads,
                                                m.head_dim)
        rot = _rotated(m)
        q, k = rope(q, m.rope_theta, rot), rope(k, m.rope_theta, rot)
        rep = m.heads // m.kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST) \
            / math.sqrt(m.head_dim)
        causal = jnp.tril(jnp.ones((T, T), bool))
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", a, v, precision=HIGHEST)
        x = x + _mm(o.reshape(B, T, m.heads * m.head_dim), p["wo"])
        h2 = rms_norm(x, p["ln2"], m.norm_eps)
        u = jax.nn.silu(_mm(h2, p["w_gate"])) * _mm(h2, p["w_up"])
        return x + _mm(u, p["w_down"])

    def loss(self, params, tokens, labels):
        """Mean cross entropy; ``params`` in float32."""
        x = params["embed"][tokens]
        for p in params["layers"]:
            x = self.layer(p, x)
        logits = _mm(rms_norm(x, params["lnf"], self.m.norm_eps),
                     params["head"])
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(logits, jnp.maximum(labels, 0)[..., None],
                                   axis=-1)[..., 0]
        w = (labels >= 0).astype(jnp.float32)
        return jnp.sum((lse - pick) * w) / jnp.maximum(jnp.sum(w), 1.0)

    def loss_and_grads(self, params, tokens, labels):
        """The loss and float32 gradients, computed on the float32 upcast
        of ``params``."""
        p32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        loss, grads = self._vg(p32, jnp.asarray(tokens), jnp.asarray(labels))
        return float(loss), grads

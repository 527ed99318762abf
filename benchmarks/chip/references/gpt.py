"""Plain reference of the GPT decoder the ``sppo-gpt-*`` configurations
state: float32 arithmetic at the highest matmul precision, no kernels, no
pipeline, no offload.  It imports nothing of the program.

Per layer (pre-norm):  x += Wo·attn(rope(LN1(x)·Wq), rope(LN1(x)·Wk),
LN1(x)·Wv);  x += W2·gelu_tanh(LN2(x)·W1).  Then the final LayerNorm, the
untied head, and the mean cross entropy over every token whose label is
>= 0.  Attention is causal with scale 1/sqrt(head_dim); rotary embedding
rotates interleaved pairs (x[2i], x[2i+1]) by position * theta**(-2i/hd).

To fit one chip at 16K tokens and 4096 wide, the gradient is taken one
layer at a time (each layer's forward is recomputed inside its backward),
attention runs in query spans over only the keys they can see, in blocks
of queries and groups of heads, and the MLP and the head in blocks of
tokens.  Blocking
changes the order of float32 sums, nothing else.

``precision="float8"`` is the control: the same computation with every
matmul of the layers and the head on float8 operands (``fp8_matmul``), the
step below the bfloat16 the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from ..weights import LAYER_LEAVES

HIGHEST = lax.Precision.HIGHEST


def _quantize(x, dtype):
    """x as float8 of ``dtype`` with one float32 scale for the tensor."""
    top = float(jnp.finfo(dtype).max)
    s = lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top)
    return (x / s).astype(dtype), s


def _dot8(a8, sa, b8, sb):
    return jnp.matmul(a8, b8, preferred_element_type=jnp.float32) * (sa * sb)


@jax.custom_vjp
def fp8_matmul(a, b):
    """a @ b on float8 operands, as float8 training computes it: e4m3 for
    activations and weights in the forward, e5m2 for the incoming gradient
    in the backward, each tensor scaled to its format's range."""
    return _dot8(*_quantize(a, jnp.float8_e4m3fn), *_quantize(b, jnp.float8_e4m3fn))


def _fp8_fwd(a, b):
    qa, qb = _quantize(a, jnp.float8_e4m3fn), _quantize(b, jnp.float8_e4m3fn)
    return _dot8(*qa, *qb), (qa, qb)


def _fp8_bwd(res, g):
    (a8, sa), (b8, sb) = res
    g8, sg = _quantize(g, jnp.float8_e5m2)
    da = _dot8(g8, sg, b8.T, sb)
    a2 = a8.reshape(-1, a8.shape[-1])
    db = _dot8(a2.T, sa, g8.reshape(-1, g8.shape[-1]), sg)
    return da, db


fp8_matmul.defvjp(_fp8_fwd, _fp8_bwd)


def matmul(a, b, precision: str):
    """a @ b over the last axis of a and the first of b, float32 out."""
    if precision == "float8":
        return fp8_matmul(a, b)
    return jnp.matmul(a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * lax.rsqrt(var + eps) * scale + bias


def gelu_tanh(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def rope(x, pos, theta):
    """x: [B, T, H, hd]; pos: [T]."""
    hd = x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]      # [T, hd/2]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.reshape(x.shape)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


class GPT:
    def __init__(self, model, *, precision: str = "float32",
                 q_span: int = 4096, q_block: int = 1024,
                 head_group: int = 8, token_block: int = 2048):
        if precision not in ("float32", "float8"):
            raise ValueError(f"precision {precision!r}")
        self.m = model
        self.precision = precision
        self.q_span = q_span
        self.q_block = q_block
        self.head_group = min(head_group, model.heads)
        self.token_block = token_block
        self._layer_fwd = jax.jit(lambda p, x: self.layer(_f32(p), x))
        self._layer_bwd = jax.jit(self._layer_vjp)
        self._head = jax.jit(self._head_vg)
        self._embed = jax.jit(lambda table, tok: _f32(table)[tok])
        self._embed_bwd = jax.jit(
            lambda tok, dx, n: jnp.zeros((n, dx.shape[-1]), jnp.float32)
            .at[tok.reshape(-1)].add(dx.reshape(-1, dx.shape[-1])),
            static_argnums=2)

    # ---- pieces ------------------------------------------------------------
    def _mm(self, a, b):
        return matmul(a, b, self.precision)

    def _attention(self, q, k, v):
        """Causal attention, q/k/v: [B, T, H, hd] -> [B, T, H, hd].

        Queries go in spans of ``q_span`` rows, each against the keys up to
        its end (the keys past it would all be masked), and within a span in
        blocks of ``q_block`` rows and groups of ``head_group`` heads, each
        block recomputed in the backward rather than stored."""
        B, T, H, hd = q.shape
        G, span = self.head_group, min(self.q_span, T)
        bq = min(self.q_block, span)
        ng = H // G
        scale = 1.0 / math.sqrt(hd)

        def split(x):   # [B, S, H, hd] -> [H/G, B, S, G, hd]
            return x.reshape(B, x.shape[1], ng, G, hd).transpose(2, 0, 1, 3, 4)

        qs, ks, vs = split(q), split(k), split(v)
        outs = []
        for s0 in range(0, T, span):
            s1 = min(T, s0 + span)
            kb, vb = ks[:, :, :s1], vs[:, :, :s1]
            nb = (s1 - s0) // bq
            # [H/G, B, nb, bq, G, hd] -> [nb * H/G, B, bq, G, hd]
            qb = qs[:, :, s0:s1].reshape(ng, B, nb, bq, G, hd)
            qb = qb.transpose(2, 0, 1, 3, 4, 5).reshape(nb * ng, B, bq, G, hd)
            q0 = s0 + bq * (jnp.arange(nb * ng) // ng)
            gi = jnp.arange(nb * ng) % ng

            def block(args, kb=kb, vb=vb):
                qg, q0_, g_ = args
                kg, vg = kb[g_], vb[g_]                      # [B, S, G, hd]
                sc = jnp.einsum("bqgd,bkgd->bgqk", qg, kg, precision=HIGHEST,
                                preferred_element_type=jnp.float32) * scale
                qpos = q0_ + jnp.arange(bq)
                kpos = jnp.arange(kg.shape[1])
                sc = jnp.where(kpos[None, None, None, :]
                               <= qpos[None, None, :, None], sc, -jnp.inf)
                p = jax.nn.softmax(sc, axis=-1)
                return jnp.einsum("bgqk,bkgd->bqgd", p, vg, precision=HIGHEST,
                                  preferred_element_type=jnp.float32)

            o = lax.map(jax.checkpoint(block), (qb, q0, gi))
            # [nb * H/G, B, bq, G, hd] -> [B, nb * bq, H, hd]
            o = o.reshape(nb, ng, B, bq, G, hd).transpose(2, 0, 3, 1, 4, 5)
            outs.append(o.reshape(B, s1 - s0, H, hd))
        return jnp.concatenate(outs, axis=1)

    def layer(self, p, x):
        """One decoder layer; p: that layer's leaves, x: [B, T, d]."""
        m = self.m
        B, T, d = x.shape
        pos = jnp.arange(T)
        h = layer_norm(x, p["ln1_scale"], p["ln1_bias"], m.norm_eps)
        q = self._mm(h, p["wq"]).reshape(B, T, m.heads, m.head_dim)
        k = self._mm(h, p["wk"]).reshape(B, T, m.kv_heads, m.head_dim)
        v = self._mm(h, p["wv"]).reshape(B, T, m.kv_heads, m.head_dim)
        q, k = rope(q, pos, m.rope_theta), rope(k, pos, m.rope_theta)
        rep = m.heads // m.kv_heads
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        a = self._attention(q, k, v).reshape(B, T, m.heads * m.head_dim)
        x = x + self._mm(a, p["wo"])

        def mlp(xb):
            hb = layer_norm(xb, p["ln2_scale"], p["ln2_bias"], m.norm_eps)
            return xb + self._mm(gelu_tanh(self._mm(hb, p["w1"])), p["w2"])

        tb = min(self.token_block, T)
        xs = x.reshape(B, T // tb, tb, d).transpose(1, 0, 2, 3)
        ys = lax.map(jax.checkpoint(mlp), xs)
        return ys.transpose(1, 0, 2, 3).reshape(B, T, d)

    def _layer_vjp(self, p, x, dy):
        _, pull = jax.vjp(self.layer, _f32(p), x)
        return pull(dy)

    def _head_vg(self, scale, bias, head, x, labels):
        return jax.value_and_grad(self.head_loss, argnums=(0, 1, 2, 3))(
            _f32(scale), _f32(bias), _f32(head), x, labels)

    def head_loss(self, scale, bias, head, x, labels):
        """Mean cross entropy of the tokens with labels >= 0."""
        m = self.m
        B, T, d = x.shape
        tb = min(self.token_block, T)

        def block(args):
            xb, lb = args
            h = layer_norm(xb, scale, bias, m.norm_eps)
            logits = self._mm(h, head)                     # [B, tb, V]
            lse = jax.nn.logsumexp(logits, axis=-1)
            pick = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None],
                                       axis=-1)[..., 0]
            w = (lb >= 0).astype(jnp.float32)
            return jnp.sum((lse - pick) * w), jnp.sum(w)

        xs = x.reshape(B, T // tb, tb, d).transpose(1, 0, 2, 3)
        ls = labels.reshape(B, T // tb, tb).transpose(1, 0, 2)
        tot, cnt = lax.map(jax.checkpoint(block), (xs, ls))
        return jnp.sum(tot) / jnp.maximum(jnp.sum(cnt), 1.0)

    # ---- one step's loss and gradient ---------------------------------------
    def loss_and_grads(self, params, tokens, labels):
        """params: {"layers": [per-layer dict], "embed", "head", "lnf_scale",
        "lnf_bias"}, in any float type: each piece computes on its float32
        upcast.  Returns the loss
        (float) and the gradients in the same layout, float32."""
        tok = jnp.asarray(tokens)
        xs = [self._embed(params["embed"], tok)]
        for p in params["layers"]:
            xs.append(self._layer_fwd(p, xs[-1]))
        loss, (g_s, g_b, g_h, dx) = self._head(
            params["lnf_scale"], params["lnf_bias"], params["head"], xs.pop(),
            jnp.asarray(labels))
        g_layers = [None] * len(params["layers"])
        for l in reversed(range(len(params["layers"]))):
            g_layers[l], dx = self._layer_bwd(params["layers"][l], xs.pop(),
                                              dx)
        g_emb = self._embed_bwd(tok, dx, params["embed"].shape[0])
        return float(loss), {"layers": g_layers, "embed": g_emb,
                             "head": g_h, "lnf_scale": g_s, "lnf_bias": g_b}


def from_canonical(canon: dict, layers: int) -> dict:
    """The layout ``GPT`` takes, from the benchmark's canonical leaves
    (per-layer leaves stacked on a leading axis)."""
    out = {k: v for k, v in canon.items() if k not in LAYER_LEAVES}
    out["layers"] = [{k: canon[k][l] for k in LAYER_LEAVES}
                     for l in range(layers)]
    return out

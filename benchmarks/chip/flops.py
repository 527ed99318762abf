"""Operations and bytes a step requires, from shapes alone.

Nothing here depends on how the program computes: block sizes, chunking,
recomputation, padding and pipeline drain ticks are not work the step
requires, so they never enter a count.  A kernel's roofline share and the
step's MFU are therefore read against the same work whatever the
implementation does.
"""
from __future__ import annotations

import math

from . import references


def attention_pairs(B: int, T: int, S: int, causal: bool) -> int:
    """(query, key) pairs one head scores.  Causal training attention puts
    the T queries at the last T of S positions; query i then sees the
    S - T + i + 1 keys up to itself."""
    if not causal:
        return B * T * S
    first = S - T + 1
    return B * (T * first + T * (T - 1) // 2)


def attention_fwd(*, B, H, Hkv, T, S, head_dim, causal=True,
                  itemsize=2) -> tuple:
    """(FLOPs, bytes) of one attention forward.  FLOPs: 2·hd for q·k and
    2·hd for p·v per visible pair and query head; grouped KV heads change
    no FLOP.  Bytes: Q, K, V and O once in the model type and the
    log-sum-exp in float32."""
    flops = 4 * head_dim * H * attention_pairs(B, T, S, causal)
    q_o = 2 * B * T * H * head_dim * itemsize
    kv = 2 * B * S * Hkv * head_dim * itemsize
    return flops, q_o + kv + 4 * B * T * H


def attention_bwd(*, B, H, Hkv, T, S, head_dim, causal=True,
                  itemsize=2) -> tuple:
    """(FLOPs, bytes) of the attention backward (dq and dkv together):
    twice the forward's FLOPs (dS·K and dS^T·Q, P^T·dO and dO·V^T); the
    recomputation of P is not required work.  Bytes: Q, K, V, O, dO, dQ,
    dK, dV once and the log-sum-exp."""
    f, _ = attention_fwd(B=B, H=H, Hkv=Hkv, T=T, S=S, head_dim=head_dim,
                         causal=causal, itemsize=itemsize)
    q_like = 4 * B * T * H * head_dim * itemsize      # Q, O, dO, dQ
    kv_like = 4 * B * S * Hkv * head_dim * itemsize   # K, V, dK, dV
    return 2 * f, q_like + kv_like + 4 * B * T * H


def matmul_params(model, pick=None) -> int:
    """Weights that multiply each token, as the reference's leaf table
    states their share (``Leaf.matmul``): the layers' projections and the
    head whole, routed experts by the share of them a token visits.  The
    embedding is a lookup, and norms and biases are elementwise.  With
    ``pick``, only the leaves it accepts."""
    table = references.of(model).leaves(model)
    return round(sum(math.prod(lf.shape) * lf.matmul
                     for lf in table.values() if pick is None or pick(lf)))


def weights_matmul(model, pick, *, tokens: int) -> tuple:
    """(FLOPs, bytes) one training step requires of the matmuls of the
    leaves ``pick`` accepts: 6·N·T (forward 2, backward 4), and their
    weights read once in bfloat16.  The activations they multiply
    are not counted: the bytes are a floor, and at thousands of tokens the
    FLOPs bound the time."""
    n = matmul_params(model, pick)
    return 6 * n * tokens, 2 * n


def train_step_flops(model, *, batch: int, seq: int) -> int:
    """Model FLOPs of one training step: 6·N·T for the weights (forward 2,
    backward 4) plus causal attention, forward and backward, in every
    layer."""
    tokens = batch * seq
    fwd, _ = attention_fwd(B=batch, H=model.heads, Hkv=model.kv_heads,
                           T=seq, S=seq, head_dim=model.head_dim)
    return 6 * matmul_params(model) * tokens + 3 * fwd * model.layers


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at best: the larger of compute at its bf16
    peak and traffic at its HBM bandwidth."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

"""Shares of a roofline over a traced window: a Pallas kernel's, and a
layer's matmuls' against its scope's device time."""
from __future__ import annotations

from . import flops, scopes, tracefile


def share(r, names, *, backward: bool):
    """100 x least time / kernel time per step, or None when the trace
    holds no event of the kernels ``names``."""
    ops = {i for i, k in r.hlo["kernels"].items() if k in names}
    tr = r.trace
    if not ops or r.steps == 0 or not tr["window"] or r.peak is None:
        return None
    t0, t1 = tr["window"]
    per_dev = [tracefile.op_ns(ev, ops, t0, t1)
               for ev in tr["devices"].values()]
    kernel_s = sum(per_dev) / len(per_dev) / 1e9 / r.steps
    if kernel_s <= 0:
        return None
    m, tf = r.cell.model, r.cell.traffic
    fn = flops.attention_bwd if backward else flops.attention_fwd
    f, b = fn(B=tf["batch"], H=m.heads, Hkv=m.kv_heads, T=tf["seq_len"],
              S=tf["seq_len"], head_dim=m.head_dim)
    least = flops.least_time(f * m.layers / r.chips, b * m.layers / r.chips,
                             r.peak)
    return 100.0 * least / kernel_s


def layer_share(r, layer: str, pick):
    """100 x least time of the matmuls of the leaves ``pick`` accepts
    (``flops.weights_matmul`` over the step's tokens, split evenly over
    the chips) / device time of the ops in layer scope ``layer``
    (``scopes.ms_per_step``), per step: recomputed ops count as time, not
    as work.  None where the scope has no time or the leaves no FLOPs."""
    ms = scopes.ms_per_step(r, scopes.in_layer(r, layer))
    if ms is None or r.peak is None:
        return None
    tf = r.cell.traffic
    f, b = flops.weights_matmul(r.cell.model, pick,
                                tokens=tf["batch"] * tf["seq_len"])
    if f == 0:
        return None
    least = flops.least_time(f / r.chips, b / r.chips, r.peak)
    return 100.0 * least / (ms / 1e3)

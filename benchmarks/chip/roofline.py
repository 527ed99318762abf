"""A Pallas kernel's share of its roofline over a traced window."""
from __future__ import annotations

from . import flops, tracefile


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

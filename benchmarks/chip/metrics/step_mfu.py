"""The whole step's share of the chips' bf16 peak, in percent: the model
FLOPs one step requires (flops.train_step_flops: 6·N·T for the weights plus
causal attention forward and backward; recomputation not counted) over the
traced steps' time on the host clock, the chips and the peak."""
from .. import flops


def read(r):
    if r.steps == 0 or r.peak is None:
        return None
    tr, m = r.cell.traffic, r.cell.model
    work = flops.train_step_flops(m, batch=tr["batch"], seq=tr["seq_len"])
    step_s = r.window_s / r.steps
    return 100.0 * work / (step_s * r.chips * r.peak["bf16_flops_per_s"])

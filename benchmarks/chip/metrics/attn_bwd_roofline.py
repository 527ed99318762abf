"""The attention backward kernels' share of their roofline, in percent: the
least time of the required backward (flops.attention_bwd: twice the
forward's FLOPs, the recomputed scores not counted) over the device time of
the dq and dkv kernels' events, per traced step.  Kernel names below were
checked by hand on a sppo-gpt-7b trace."""
from .. import roofline

NAMES = frozenset({"_flash_bwd_dq_kernel", "_flash_bwd_dkv_kernel"})


def read(r):
    return roofline.share(r, NAMES, backward=True)

"""The attention forward kernel's share of its roofline, in percent: the
least time the step's required forward attention takes on the chip
(flops.attention_fwd over every layer, split evenly over the chips) over
the device time of the kernel's events, both per traced step.  Events are
matched by HLO instruction to the Pallas kernels named below (checked by
hand on a sppo-gpt-7b trace); recomputed forwards count as kernel time,
not as work."""
from .. import roofline

NAMES = frozenset({"_flash_partial_kernel"})


def read(r):
    return roofline.share(r, NAMES, backward=False)

"""The head and loss's share of its roofline, in percent: the least time
of the head's matmul (6·T·d·V: the reference table's global leaf under the
program's ``head`` path, or the embedding where it is tied and its
``matmul`` share says so) at the chip's bf16 peak, over the device time per
step of the ops whose innermost scope is ``sppo.head_loss``: the head and
the softmax cross-entropy, forward and backward (the final norm is
``sppo.norm``)."""
from .. import roofline, scopes


def takes(lf) -> bool:
    return not lf.per_layer and lf.path[0] in ("head", "embed")


def read(r):
    return roofline.layer_share(r, scopes.HEAD_LOSS, takes)

"""The MLP's share of its roofline, in percent: the least time of the
step's MLP matmuls (6·T·N over the reference table's per-layer leaves under
the program's ``mlp`` or ``moe`` path, each by its ``matmul`` share: a
gated MLP's three matrices, routed experts by the share a token visits) at
the chip's bf16 peak, over the device time per step of the ops whose
innermost scope is ``sppo.mlp`` (forward, recomputed and backward)."""
from .. import roofline, scopes


def takes(lf) -> bool:
    return lf.per_layer and lf.path[0] in ("mlp", "moe")


def read(r):
    return roofline.layer_share(r, scopes.MLP, takes)

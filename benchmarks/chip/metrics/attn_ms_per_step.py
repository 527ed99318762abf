"""Device time per step, in milliseconds, of the operations whose innermost
scope is ``sppo.attn``: the QKV and output projections, RoPE and the
attention kernels, forward, recomputed and backward."""
from .. import scopes


def read(r):
    return scopes.ms_per_step(r, scopes.in_layer(r, scopes.ATTN))

"""Device time per step, in milliseconds, of the operations whose innermost
scope is ``sppo.head_loss``: the LM head matmul and the softmax
cross-entropy, forward and backward (the final norm is ``sppo.norm``)."""
from .. import scopes


def read(r):
    return scopes.ms_per_step(r, scopes.in_layer(r, scopes.HEAD_LOSS))

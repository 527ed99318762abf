"""Device time per step, in milliseconds, of recomputed operations: those
with a ``rematted_computation`` component in their ``op_name`` (the forward
that ``jax.checkpoint`` replays in the backward), in any layer."""
from .. import scopes


def read(r):
    names = r.scopes
    return scopes.ms_per_step(r, lambda op: scopes.is_recompute(names.get(op)))

"""Device time per step, in milliseconds, of the operations whose innermost
scope is ``sppo.adamw``: the clipping norm and the AdamW update, with the
core's waits on the host-resident moments' copies (to the device before the
update, back to the host after it)."""
from .. import scopes


def read(r):
    return scopes.ms_per_step(r, scopes.in_layer(r, scopes.ADAMW))

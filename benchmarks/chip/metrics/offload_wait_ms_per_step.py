"""Core time per step, in milliseconds, spent on the ``XLA Ops`` line in the
synchronous ends of the activations' copies to and from host memory (S(5)):
each ``copy-done`` and each synchronous ``copy`` of layer ``sppo.offload``,
the exposed part of the offload and reload (the ``Async XLA Ops`` line,
read by ``host_copy_ms_per_step``, holds the part that runs under other
work).  The moments' copies are ``sppo.adamw`` and count in
``optimizer_ms_per_step``; how host copies get their layer is in
``scopes.py``."""
from .. import scopes

WAITS = ("copy-done", "copy")


def read(r):
    return scopes.ms_per_step(r, lambda op: (
        op.partition(".")[0] in WAITS and scopes.is_host_copy(r, op)
        and scopes.layer(r, op) == scopes.OFFLOAD))

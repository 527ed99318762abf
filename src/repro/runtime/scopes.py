"""The train step's named layer scopes.

Each scope is a ``jax.named_scope``: it changes nothing in the compiled
program but the ``op_name`` metadata of the operations traced inside it, so
a profiler trace of the step can be split by layer
(``benchmarks/chip/scopes.py`` reads them).  Scopes nest; the innermost one
names an operation's layer, so an offload copy issued inside an attention
block belongs to ``sppo.offload``.  JAX keeps the names through ``jvp``,
``transpose``, ``jax.checkpoint`` (recomputed operations carry a
``rematted_computation`` component), ``lax.scan`` bodies and ``custom_vjp``
backwards.  No scope is opened inside a Pallas kernel body.

  sppo.embed      token and position embedding (``ModelDef.embed``)
  sppo.norm       every LayerNorm/RMSNorm (``layers.apply_norm``)
  sppo.attn       attention blocks: projections, RoPE, the kernels
  sppo.mlp        dense and expert MLPs
  sppo.head_loss  the LM head and softmax cross-entropy
  sppo.adamw      gradient clipping norm, the AdamW update and the
                  host-resident moments' copies
  sppo.offload    activation offload and reload (tags, prefetch seam)
"""
from __future__ import annotations

import functools

import jax

EMBED = "sppo.embed"
NORM = "sppo.norm"
ATTN = "sppo.attn"
MLP = "sppo.mlp"
HEAD_LOSS = "sppo.head_loss"
ADAMW = "sppo.adamw"
OFFLOAD = "sppo.offload"

LAYERS = (EMBED, NORM, ATTN, MLP, HEAD_LOSS, ADAMW, OFFLOAD)


def scoped(name: str):
    """Decorator: run the function inside ``jax.named_scope(name)``, with a
    fresh context manager per call (one shared instance keeps the name
    stack it restores on itself, and would not survive re-entry)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap

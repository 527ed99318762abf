"""Attention dispatch: the Pallas kernels or the blockwise-jnp reference.

Three backends, one per process unless switched in code:

  * ``pallas``    — the fused flash kernels compiled for the TPU; the
    default when JAX's default backend is a TPU;
  * ``jnp``       — the blockwise-jnp reference (identical math, autodiff
    backward); the default everywhere else;
  * ``interpret`` — the Pallas kernels in Pallas interpret mode, the CPU
    test path.  Never chosen by default: tests and the CI leg ask for it.

``REPRO_ATTENTION=<name>`` picks the backend for a process;
``set_backend`` / ``backend`` switch it in code.  ``pallas`` off a TPU is
an error from the compiler, not a silent fallback.
"""
from __future__ import annotations

import contextlib
import os

import jax

from repro.kernels import ref as _ref
from repro.kernels import flash_attention as _fa

BACKENDS = ("pallas", "jnp", "interpret")

_BACKEND = None   # resolved on first use: the default needs the JAX backend


def set_backend(name: str) -> None:
    global _BACKEND
    if name not in BACKENDS:
        raise ValueError(f"unknown attention backend {name!r}; "
                         f"known: {BACKENDS}")
    _BACKEND = name


def get_backend() -> str:
    if _BACKEND is None:
        set_backend(os.environ.get("REPRO_ATTENTION") or
                    ("pallas" if jax.default_backend() == "tpu" else "jnp"))
    return _BACKEND


@contextlib.contextmanager
def backend(name: str):
    """Scoped backend switch: ``with kops.backend("interpret"): ...``.

    Restores the previous global on exit (exception-safe), so tests can flip
    backends without leaking state across modules.  The flag is read at
    trace time — re-trace (fresh ``jax.jit``) inside the block to take
    effect on jitted callables.
    """
    prev = get_backend()
    set_backend(name)
    try:
        yield name
    finally:
        set_backend(prev)


def attention_partial(q, k, v, q_pos, kv_pos, *, causal=True, scale=None,
                      block_k=512, q_start=None):
    """Partial flash attention against a local KV shard (see kernels/ref.py).

    Dispatches on the backend flag.  Every backend returns identical
    (o, m, l) and differentiates in (q, k, v) — the Pallas path via the
    fused backward kernels' custom_vjp, the jnp path via autodiff of the
    blockwise scan — with the max statistic m gradient-frozen on both.
    ``q_start`` is the optional per-query segment window ([B,Tq] or [Tq]
    int32): only kv slots with kv_pos >= q_start are visible
    (packed-document blocking).
    """
    name = get_backend()
    if name == "jnp":
        return _ref.attention_partial_ref(
            q, k, v, q_pos, kv_pos, causal=causal, scale=scale,
            block_k=block_k, q_start=q_start)
    return _fa.flash_attention_partial(
        q, k, v, q_pos, kv_pos, causal=causal, scale=scale, q_start=q_start,
        interpret=name == "interpret")

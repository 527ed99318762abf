"""Shared host-memory helpers (DESIGN.md §10/§11).

Both executed-offload paths — activations (core/offload.py) and optimizer
moments (optim/adamw.py) — place tensors into the device's ``pinned_host``
memory and move them back with explicit ``device_put`` dataflow.  Inside
``jit`` the copies are ``jax.memory.Space.Host`` / ``Space.Device`` puts
(the jaxpr shows ``f32<host>`` avals); concrete arrays are committed
through their own sharding re-kinded with ``with_memory_kind``.  A device
without ``pinned_host`` memory is an error, never a silent no-op.

This module is the single home for the memory-kind probe and the D2H/H2D
primitives; it imports nothing from ``repro`` so every layer (core, optim,
runtime, parallel) can use it without cycles.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

DEVICE_KIND = "device"
HOST_KIND = "pinned_host"


def host_memory_kind(device=None) -> str:
    """The host memory kind offloads go to: ``pinned_host``, which TPU, GPU
    and CPU devices all expose.  Raises when `device` (default: the first
    device) has no such memory."""
    dev = device if device is not None else jax.devices()[0]
    kinds = sorted(m.kind for m in dev.addressable_memories())
    if HOST_KIND not in kinds:
        raise RuntimeError(f"{dev.device_kind} exposes no {HOST_KIND!r} "
                           f"memory (has {kinds}); host offload needs it")
    return HOST_KIND


def _is_traced(t) -> bool:
    return isinstance(t, jax.core.Tracer)


def to_host(t):
    """One D2H: place `t` in host memory.  Inside jit this is the
    ``Space.Host`` device_put the ledger's copy accounting counts; eagerly
    it commits the concrete array's own sharding into pinned_host."""
    if _is_traced(t):
        return jax.device_put(t, jax.memory.Space.Host)
    return jax.device_put(t, t.sharding.with_memory_kind(host_memory_kind()))


def to_device(t):
    """One H2D: bring a host-resident `t` back to device memory."""
    if _is_traced(t):
        return jax.device_put(t, jax.memory.Space.Device)
    return jax.device_put(t, t.sharding.with_memory_kind(DEVICE_KIND))


def row_scale_sharding(p, kind: str):
    """Host sharding for a per-row scale buffer shaped ``p.shape[:-1] + (1,)``:
    `p`'s own sharding with the trailing axis unpartitioned — the scale's
    trailing dim is 1 and cannot carry the payload's last-axis shards (a
    model-sharded (rows, d) param would ask the (rows, 1) scale to split
    its singleton axis)."""
    sh = p.sharding
    if (isinstance(sh, NamedSharding) and p.ndim >= 1
            and len(sh.spec) == p.ndim and sh.spec[-1] is not None):
        sh = NamedSharding(sh.mesh, PartitionSpec(*sh.spec[:-1], None))
    return sh.with_memory_kind(kind)


def host_zeros(shape, dtype, like=None, sharding=None):
    """Zeros born in host memory: the buffer is built host-side (numpy) and
    placed directly into pinned_host, so *no device allocation ever
    happens* — the init_state fix for the step-0 peak spike (DESIGN.md
    §11).  Under abstract tracing (eval_shape / jit of init — the dry-run's
    shape-only path) a concrete host buffer must not materialize, so this
    emits traced zeros immediately put to host; the real init paths
    (launch/train.py, memledger) are eager."""
    import numpy as np

    import jax.numpy as jnp

    if _is_traced(like):
        # the jaxpr keeps the host-residency fact
        # (memledger.init_moment_device_bytes nets host-placed creations
        # out of the device-space count)
        return to_host(jnp.zeros(shape, dtype))
    host = np.zeros(shape, np.dtype(dtype))
    if sharding is None:
        sharding = like.sharding.with_memory_kind(host_memory_kind())
    return jax.device_put(host, sharding)


def memory_kind_of(arr) -> Optional[str]:
    """The committed memory kind of a concrete array (None if unknown)."""
    sh = getattr(arr, "sharding", None)
    return getattr(sh, "memory_kind", None)


# ---------------------------------------------------------------------------
# Compressed host residency: the shared quantize/dequantize primitives
# ---------------------------------------------------------------------------
#
# Both executed offload channels (act_off rows, core/offload.py, and the
# AdamW moments, optim/adamw.py) can cross the host link compressed:
# bf16/fp32 rows quantize to an 8-bit wire dtype with one fp32 scale per
# row of the trailing axis (symmetric absmax scaling), and the backward /
# update H2D dequantizes.  The payload is what lives in host memory and
# crosses PCIe; the scales are tiny (4 bytes per trailing-axis row) and the
# activation channel keeps them device-resident with the keep set
# (DESIGN.md §14).  Zero/constant rows are safe by construction: a row with
# absmax 0 gets scale 1.0, quantizes to exact zeros, and dequantizes to
# exact zeros — no division by zero, no NaN (the offload analogue of the
# PR 2 dead-row m=-inf sanitization).

OFFLOAD_CODECS = ("none", "fp8", "int8")

# symmetric quantization range per codec: fp8_e4m3fn saturates at 448,
# int8 at 127 (the sign-symmetric range, -127..127)
_CODEC_QMAX = {"fp8": 448.0, "int8": 127.0}


def codec_wire_dtype(codec: str):
    """The 1-byte wire dtype of a codec (None for the uncompressed channel)."""
    import jax.numpy as jnp

    if codec in (None, "none"):
        return None
    if codec == "fp8":
        return jnp.float8_e4m3fn
    if codec == "int8":
        return jnp.int8
    raise ValueError(f"unknown offload codec {codec!r}; "
                     f"known: {OFFLOAD_CODECS}")


def codec_itemsize(codec: str, *, default: int = 2) -> int:
    """Wire bytes per element of the compressed payload (`default` — the
    bf16 activation itemsize — for the uncompressed channel)."""
    import numpy as np

    wire = codec_wire_dtype(codec)
    return default if wire is None else np.dtype(wire).itemsize


def quantize(t, codec: str):
    """Per-row symmetric quantization: (payload, scale).

    Rows are the trailing axis (one fp32 scale per [..., 1] slice — per
    head for [B, T, H, hd] attention tensors, per token for [B, T, d_ff]
    MLP hiddens, per matrix row for 2-D moment leaves).  payload is the
    codec's wire dtype; ``dequantize(payload, scale, codec, t.dtype)``
    reconstructs within the codec's resolution.  All-zero rows map to
    (zeros, 1.0) exactly."""
    import jax.numpy as jnp

    wire = codec_wire_dtype(codec)
    assert wire is not None, f"quantize called with codec={codec!r}"
    qmax = _CODEC_QMAX[codec]
    t32 = t.astype(jnp.float32)
    if t.ndim >= 1:
        amax = jnp.max(jnp.abs(t32), axis=-1, keepdims=True)
    else:
        amax = jnp.abs(t32)
    # floored at the smallest normal float32: a subnormal scale is flushed
    # to zero on the device, and the row would come back as zeros
    scale = jnp.where(amax > 0.0,
                      jnp.maximum(amax / qmax, jnp.finfo(jnp.float32).tiny),
                      1.0)
    # saturate BEFORE the wire cast for both codecs: t32/scale can land an
    # ulp above qmax depending on how XLA fuses the division (the AD-traced
    # program rearranges it differently than the plain forward), and
    # float8_e4m3fn has no inf — an overflowing convert produces NaN
    q = jnp.clip(t32 / scale, -qmax, qmax)
    if codec == "int8":
        payload = jnp.round(q).astype(wire)
    else:
        payload = q.astype(wire)
    return payload, scale


def dequantize(payload, scale, codec: str, dtype):
    """Inverse of ``quantize``: payload * scale, cast back to `dtype`."""
    import jax.numpy as jnp

    return (payload.astype(jnp.float32) * scale).astype(dtype)


def to_transport(payload, codec: str):
    """View an int8 payload as an fp8 byte container for channels that must
    carry an inexact dtype (the prefetch seam's link cotangent — JAX gives
    integer outputs a float0 tangent, which cannot transport the reloaded
    bytes).  bitcast is bit-exact both ways; fp8 payloads pass through."""
    import jax
    import jax.numpy as jnp

    if codec == "int8":
        return jax.lax.bitcast_convert_type(payload, jnp.float8_e4m3fn)
    return payload


def from_transport(payload, codec: str):
    """Inverse of ``to_transport``: recover the int8 payload bytes."""
    import jax
    import jax.numpy as jnp

    if codec == "int8":
        return jax.lax.bitcast_convert_type(payload, jnp.int8)
    return payload

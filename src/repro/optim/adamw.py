"""AdamW with global-norm clipping, schedules, and *executed* memory knobs.

Runs *outside* shard_map on global (sharded) arrays — XLA/GSPMD inserts the
(elementwise-free) collectives for the norm reductions.  Memory knobs used by
the big-model plans (DESIGN.md §4, §11):
  * ``opt_dtype``: moment dtype (deepseek-v3 uses bf16, as in its report);
  * ``offload_moments``: keep ``AdamWState.m/v`` resident in host memory
    (ZeRO-Offload analogue — the same host memory kinds and D2H/H2D
    primitives the activation offload path uses, runtime/hostmem.py).
    This is *executed dataflow*, not a sharding hint: ``init_state``
    births the moments in host space (no device allocation), and
    ``apply_update`` stages exactly one H2D per moment leaf, computes the
    fp32 update on device, and writes the new moments back with one D2H
    per leaf — one leaf pair on device at a time.
  * ZeRO-1 across the `pod` axis is expressed through the moment shardings
    built in parallel/specs.py.

Every host-resident moment leaf is tagged with a ``checkpoint_name``
(``opt_m@<i>`` / ``opt_v@<i>``) so the memory ledger
(runtime/memledger.moment_bytes_from_jaxpr) can account the exact bytes kept
off-device from the traced update — the optimizer-state analogue of the
``act_off@<tick>`` activation names.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.runtime import hostmem

# checkpoint-name bases for the host-resident moments; leaf-qualified as
# opt_m@<leaf-index> so the ledger attributes bytes per leaf exactly
OPT_M_NAME = "opt_m"
OPT_V_NAME = "opt_v"


def moment_names(i: int):
    return f"{OPT_M_NAME}@{i}", f"{OPT_V_NAME}@{i}"


def moment_scale_names(i: int):
    """Names of a compressed moment leaf's per-row scales.  Deliberately
    *not* under the ``opt_m@``/``opt_v@`` prefixes — the ledger's moment
    channel counts payload bytes and scale bytes separately (the scales are
    host-resident here, unlike the activation channel's device-resident
    ``act_scale``; see DESIGN.md §14)."""
    return f"{OPT_M_NAME}_scale@{i}", f"{OPT_V_NAME}_scale@{i}"


class AdamWState(NamedTuple):
    step: jax.Array   # int32 []
    m: object         # pytree like params
    v: object         # pytree like params


def init_state(params, opt_dtype=jnp.float32, *, offload_moments: bool = False,
               moments_dtype: str = "none") -> AdamWState:
    """Zero moments, placed where they will live.

    With ``offload_moments`` the zeros are *born in host memory*
    (hostmem.host_zeros: numpy buffer -> device_put into the host space), so
    initialization never materializes an opt_dtype copy of the parameters in
    device memory — the step-0 peak equals the steady-state peak
    (regression-tested in tests/test_opt_offload.py).

    With ``moments_dtype`` ("fp8" | "int8", DESIGN.md §14) each moment leaf
    is the compressed host residency pair ``(payload, scale)`` — the 1-byte
    wire payload plus its per-row fp32 scales, both host-resident.  Zero
    payload dequantizes to zero under any scale, so all-zero init is exact."""
    if moments_dtype not in (None, "none"):
        assert offload_moments, (
            "moments_dtype compression requires offload_moments (there is "
            "no host channel to compress otherwise)")
        wire = hostmem.codec_wire_dtype(moments_dtype)

        def zeros(p):
            sshape = p.shape[:-1] + (1,) if p.ndim >= 1 else ()
            # the scale can't inherit p's sharding verbatim: its trailing
            # dim is 1, so a last-axis-sharded param needs the partition
            # dropped there (row_scale_sharding)
            ssh = (None if isinstance(p, jax.core.Tracer) else
                   hostmem.row_scale_sharding(p, hostmem.host_memory_kind()))
            return (hostmem.host_zeros(p.shape, wire, like=p),
                    hostmem.host_zeros(sshape, jnp.float32, like=p,
                                       sharding=ssh))
    elif offload_moments:
        zeros = lambda p: hostmem.host_zeros(p.shape, opt_dtype, like=p)
    else:
        zeros = lambda p: jnp.zeros(p.shape, opt_dtype)
    return AdamWState(step=jnp.zeros((), jnp.int32),
                      m=jax.tree_util.tree_map(zeros, params),
                      v=jax.tree_util.tree_map(zeros, params))


def cosine_lr(step, *, peak=3e-4, warmup=100, total=10000, floor=0.1):
    warm = peak * (step + 1) / warmup
    prog = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
    cos = peak * (floor + (1 - floor) * 0.5 * (1 + jnp.cos(jnp.pi * prog)))
    return jnp.where(step < warmup, warm, cos).astype(jnp.float32)


def global_norm(tree) -> jax.Array:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def apply_update(params, grads, state: AdamWState, *, lr, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.1, clip_norm=1.0,
                 offload_moments: bool = False,
                 moments_dtype: str = "none",
                 probe: Optional[callable] = None,
                 grad_norm: Optional[jax.Array] = None):
    """One AdamW step. Returns (new_params, new_state, metrics).

    grad_norm: the global norm that clipping uses, for a gradient layout
    that stores some leaves more than once (the dp replicas of a pipeline
    stage); default ``global_norm(grads)``.

    offload_moments: per moment leaf, exactly one H2D device_put brings
    the host-resident moment on device, the fp32 update runs there, and one
    D2H writes the new moment back to host — the round trip is value-level
    identity, so offload on/off updates are equal
    (tests/test_opt_offload.py).

    moments_dtype ("fp8" | "int8", DESIGN.md §14): the host residency is
    the compressed ``(payload, scale)`` pair — the H2D brings both on
    device and dequantizes to fp32 for the update; the D2H writes back the
    re-quantized pair.  Compression cuts the *host* bytes and the transfer
    volume (payload + scales vs the full opt_dtype leaf); the device-side
    update still runs in fp32 either way.  Lossy by design — drift bounds
    are pinned in tests/test_offload_quant.py.

    probe: optional identity hook (runtime/memledger.update_probe) threaded
    onto the step counter — runtime evidence that the update phase executed.
    """
    compressed = moments_dtype not in (None, "none")
    assert not compressed or offload_moments, (
        "moments_dtype compression requires offload_moments")
    gnorm = global_norm(grads) if grad_norm is None else grad_norm
    scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-12))
    step = state.step + 1
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m32, v32 = m.astype(jnp.float32), v.astype(jnp.float32)
        m_new = b1 * m32 + (1 - b1) * g
        v_new = b2 * v32 + (1 - b2) * g * g
        u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
        # decoupled weight decay on matrices only (ndim >= 2)
        if p.ndim >= 2:
            u = u + weight_decay * p.astype(jnp.float32)
        p_new = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        return p_new, m_new.astype(m.dtype), v_new.astype(v.dtype)

    flat_p, treedef = jax.tree_util.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state.m)
    flat_v = treedef.flatten_up_to(state.v)
    def fetch(leaf, name, scale_name):
        """Host residency -> device fp32 moment (compressed: H2D the
        (payload, scale) pair and dequantize; raw: H2D the named leaf)."""
        if compressed:
            payload, sc = leaf
            payload = hostmem.to_device(checkpoint_name(payload, name))
            sc = hostmem.to_device(checkpoint_name(sc, scale_name))
            return hostmem.dequantize(payload, sc, moments_dtype, jnp.float32)
        # the *host-resident* buffer carries the name, mirroring the
        # act_off contract: what the ledger counts is what lives off
        # device between steps
        return hostmem.to_device(checkpoint_name(leaf, name))  # one H2D

    def store(leaf_new):
        """Device moment -> host residency (compressed: quantize and D2H
        the pair; raw: D2H the leaf)."""
        if compressed:
            payload, sc = hostmem.quantize(leaf_new, moments_dtype)
            return hostmem.to_host(payload), hostmem.to_host(sc)
        if offload_moments:
            return hostmem.to_host(leaf_new)        # one D2H writes back
        return leaf_new

    out = []
    for i, (p, g, m, v) in enumerate(zip(flat_p, flat_g, flat_m, flat_v)):
        if offload_moments and out and isinstance(p, jax.core.Tracer):
            # leaf i's H2D waits for leaf i-1's D2H: one moment pair on
            # device at a time — without the fence XLA's scheduler starts
            # every leaf's H2D up front and holds the new moments until
            # late D2Hs, so the whole moment set lands on device at once
            # (eager calls run leaf by leaf anyway)
            m, v, _ = jax.lax.optimization_barrier((m, v, out[-1]))
        if offload_moments:
            nm, nv = moment_names(i)
            nms, nvs = moment_scale_names(i)
            m = fetch(m, nm, nms)
            v = fetch(v, nv, nvs)
        p_new, m_new, v_new = upd(p, g, m, v)
        out.append((p_new, store(m_new), store(v_new)))
    if probe is not None:
        step = probe(step)
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    metrics = {"grad_norm": gnorm, "lr": lr}
    return new_p, AdamWState(step=step, m=new_m, v=new_v), metrics

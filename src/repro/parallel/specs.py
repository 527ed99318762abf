"""Global shapes + NamedShardings for params/optimizer/batch, per cell.

Shapes come from ``jax.eval_shape`` over the init functions — no allocation,
so this works for deepseek-v3-671b as well as the reduced smoke configs.

Layouts (DESIGN.md §4):
  stage params   [data_size, slots_per_stage, ...]   P('data', None, ...)
                 entry i holds stage (i % pp)'s slots (dp-replicated).
  globals        [...]                               replicated over data.
  tokens/labels  [pods, data_size, B_loc, S]         P('pod','data',...)
                 row (p, i) is the batch shard of dp group (p, i // pp).
  moments        like params; optional ZeRO-1 over the pod axis and/or
                 pinned_host memory kind (big-model plans).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.model_zoo import ModelDef


def _marker_spec(marker, lead: Tuple[Optional[str], ...]):
    """PartitionSpec for one leaf: lead axes + 'model' at the marker dim."""
    if isinstance(marker, int):
        dim = marker
    elif isinstance(marker, str) and marker.startswith("keep"):
        dim = int(marker[4:])
    else:
        return P(*lead) if lead else P()
    parts = list(lead) + [None] * (dim + 1)
    parts[len(lead) + dim] = "model"
    return P(*parts)


def stage_specs(mdef: ModelDef, pp: int):
    """Pytree of PartitionSpecs for stage params [data, spp, ...]."""
    spec_tree = mdef.stage_spec()
    return jax.tree_util.tree_map(
        lambda m: _marker_spec(m, ("data", None)), spec_tree)


def globals_specs(mdef: ModelDef):
    return jax.tree_util.tree_map(
        lambda m: _marker_spec(m, ()), mdef.globals_spec())


def stage_struct(mdef: ModelDef, pp: int, data_size: int,
                 dtype=jnp.bfloat16):
    """Global ShapeDtypeStructs for the stacked stage params."""
    per_stage = jax.eval_shape(
        lambda k: mdef.init_stage_params(k, 0, pp, dtype),
        jax.ShapeDtypeStruct((2,), jnp.uint32))
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct((data_size,) + s.shape, s.dtype),
        per_stage)


def globals_struct(mdef: ModelDef, dtype=jnp.bfloat16):
    return jax.eval_shape(lambda k: mdef.init_globals(k, dtype),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def param_struct_and_specs(mdef: ModelDef, pp: int, data_size: int,
                           dtype=jnp.bfloat16):
    struct = {"stages": stage_struct(mdef, pp, data_size, dtype),
              "globals": globals_struct(mdef, dtype)}
    specs = {"stages": stage_specs(mdef, pp),
             "globals": globals_specs(mdef)}
    return struct, specs


def opt_specs(param_specs, *, zero1_pod: bool = False, param_struct=None,
              model_size: int = 16, pods: int = 2):
    """Moment shardings mirror the params; ZeRO-1 over the pod axis shards
    the 'model' dim jointly over ('model','pod') when requested — only for
    leaves whose dim remains divisible (small per-head vectors stay
    model-sharded)."""
    if not zero1_pod:
        return jax.tree_util.tree_map(lambda s: s, param_specs)

    def widen(spec: P, leaf=None):
        parts = list(spec)
        for i, ax in enumerate(parts):
            if ax == "model":
                if leaf is not None and leaf.shape[i] % (model_size * pods):
                    return spec
                parts[i] = ("model", "pod")
                return P(*parts)
        return spec

    if param_struct is not None:
        return jax.tree_util.tree_map(widen, param_specs, param_struct)
    return jax.tree_util.tree_map(widen, param_specs)


def shardings(mesh, specs, memory_kind: Optional[str] = None):
    def mk(spec):
        if memory_kind is not None:
            return NamedSharding(mesh, spec, memory_kind=memory_kind)
        return NamedSharding(mesh, spec)
    return jax.tree_util.tree_map(mk, specs)


def moment_shardings(mesh, opt_param_specs, *, offload_moments: bool = False):
    """NamedShardings for the AdamW moment trees (DESIGN.md §11): the
    param-mirroring specs from ``opt_specs``, committed to the backend's
    host memory kind when the plan offloads moments.  This is the sharding
    side of the executed path — apply_update's explicit H2D/D2H copies are
    what move the bytes."""
    kind = None
    if offload_moments:
        from repro.runtime import hostmem
        kind = hostmem.host_memory_kind(mesh.devices.flat[0])
    return shardings(mesh, opt_param_specs, memory_kind=kind)


def count_params(mdef: ModelDef, pp: int, data_size: int) -> int:
    """Deduped parameter count (stage stack divided by dp replication)."""
    st = stage_struct(mdef, pp, data_size)
    gl = globals_struct(mdef)
    n_stage = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(st))
    n_stage = n_stage * pp // data_size
    n_glob = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(gl))
    return n_stage + n_glob


def count_active_params(mdef: ModelDef, pp: int, data_size: int) -> int:
    """MoE-aware active parameter count for MODEL_FLOPS = 6·N_active·D."""
    cfg = mdef.cfg
    total = count_params(mdef, pp, data_size)
    emb = L_embed_params(mdef)
    total -= emb
    if cfg.moe is None:
        return total
    st = stage_struct(mdef, pp, data_size)
    expert_leaves = ("w1", "w2", "w3")
    dense_of_experts = 0
    for name in expert_leaves:
        leaf = st["moe"][name] if "moe" in st else None
        if leaf is not None:
            dense_of_experts += int(np.prod(leaf.shape)) * pp // data_size
    active_frac = cfg.moe.top_k / cfg.moe.num_experts
    return total - dense_of_experts + int(dense_of_experts * active_frac)


def L_embed_params(mdef: ModelDef) -> int:
    gl = globals_struct(mdef)
    n = int(np.prod(gl["embed"]["table"].shape))
    if "pos" in gl:
        n += int(np.prod(gl["pos"]["table"].shape))
    return n

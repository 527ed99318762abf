"""Weights from `--seed`, made by the benchmark and handed to both sides.

``canonical(model, key)`` draws every weight of the model in one traced
function: per-layer leaves stacked on a leading layer axis, plus the
embedding, the final norm and the head.  The scheme is the program's own
(truncated normals at 1/sqrt(fan-in); output projections scaled by
1/sqrt(2 * published depth); norms at gain 1, bias 0), so the model behaves
as the program's trainer would start it.  The reference computes on these
leaves as they are; ``to_program`` lays them out in the program's parameter
tree (stage-major stacks, padded vocabulary), and ``program_rows`` reads
one leaf of that layout back.  Nothing here imports the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LAYER_LEAVES = ("ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo",
                "ln2_scale", "ln2_bias", "w1", "w2")
GLOBAL_LEAVES = ("embed", "head", "lnf_scale", "lnf_bias")

# where each leaf lives in the program's parameter tree
PROGRAM_PATHS = {
    "ln1_scale": ("ln1", "scale"), "ln1_bias": ("ln1", "bias"),
    "wq": ("attn", "wq"), "wk": ("attn", "wk"), "wv": ("attn", "wv"),
    "wo": ("attn", "wo"),
    "ln2_scale": ("ln2", "scale"), "ln2_bias": ("ln2", "bias"),
    "w1": ("mlp", "w1"), "w2": ("mlp", "w2"),
    "embed": ("embed", "table"), "head": ("head", "w"),
    "lnf_scale": ("final_norm", "scale"), "lnf_bias": ("final_norm", "bias"),
}
# program leaves that are structure, not weights: the per-slot residual gate
# (1 on a layer, 0 on pipeline padding)
STRUCTURAL = ("gate",)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def shapes(model) -> dict:
    L, d, ff = model.layers, model.d, model.ff
    qd, kvd = model.heads * model.head_dim, model.kv_heads * model.head_dim
    return {
        "ln1_scale": (L, d), "ln1_bias": (L, d),
        "wq": (L, d, qd), "wk": (L, d, kvd), "wv": (L, d, kvd),
        "wo": (L, qd, d),
        "ln2_scale": (L, d), "ln2_bias": (L, d),
        "w1": (L, d, ff), "w2": (L, ff, d),
        "embed": (model.vocab, d), "head": (d, model.vocab),
        "lnf_scale": (d,), "lnf_bias": (d,),
    }


def leaf(model, key, name: str, dtype=jnp.bfloat16):
    """One canonical leaf, drawn from ``key`` alone."""
    shape = shapes(model)[name]
    if name.endswith("_scale"):
        return jnp.ones(shape, dtype)
    if name.endswith("_bias"):
        return jnp.zeros(shape, dtype)
    out_scale = 1.0 / math.sqrt(2 * model.published_layers)
    std = {
        "wq": 1 / math.sqrt(model.d), "wk": 1 / math.sqrt(model.d),
        "wv": 1 / math.sqrt(model.d),
        "wo": out_scale / math.sqrt(model.heads * model.head_dim),
        "w1": 1 / math.sqrt(model.d), "w2": out_scale / math.sqrt(model.ff),
        "embed": 0.02, "head": 1 / math.sqrt(model.d),
    }[name]
    k = jax.random.fold_in(key, (LAYER_LEAVES + GLOBAL_LEAVES).index(name))
    return (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32)
            * std).astype(dtype)


def canonical(model, key, dtype=jnp.bfloat16) -> dict:
    return {name: leaf(model, key, name, dtype)
            for name in LAYER_LEAVES + GLOBAL_LEAVES}


def program_rows(x, name: str, model, slots: int):
    """A program leaf in canonical layout: [layers, ...] from a stage leaf
    [data, slots, ...] (first replica of each stage), real vocabulary only
    for the embedding and the head."""
    if name in LAYER_LEAVES:
        return jnp.stack([x[l // slots, l % slots]
                          for l in range(model.layers)])
    return x[tuple(slice(0, n) for n in shapes(model)[name])]


def norm(x, name: str):
    """Float32 norm: per layer ([layers]) for a stacked leaf, else whole."""
    sq = jnp.square(x.astype(jnp.float32))
    if name in LAYER_LEAVES:
        return jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(sq))


def named(name: str, value) -> dict:
    """{"L0.wq": float, ...} or {"embed": float} from one ``norm``."""
    if name in LAYER_LEAVES:
        return {f"L{l}.{name}": float(v) for l, v in enumerate(value)}
    return {name: float(value)}


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def to_program(canon: dict, struct: dict, *, pp: int, data: int) -> dict:
    """The program's tree {"stages": ..., "globals": ...} from canonical
    leaves.  A stage leaf is [data, slots, ...]; data row i holds stage
    i % pp, whose slot j is layer (i % pp) * slots + j (ghost slots past the
    last layer are zero with gate 0)."""
    out = {}
    first = _get(struct["stages"], PROGRAM_PATHS["wq"])
    slots = first.shape[1]
    n_layers = canon["wq"].shape[0]

    def stack(per_layer, fill):
        rows = []
        for i in range(data):
            s = i % pp
            rows.append(jnp.stack([
                per_layer(s * slots + j) if s * slots + j < n_layers
                else fill for j in range(slots)]))
        return jnp.stack(rows)

    for name in LAYER_LEAVES:
        path = PROGRAM_PATHS[name]
        want = _get(struct["stages"], path)
        leaf = stack(lambda l, n=name: canon[n][l],
                     jnp.zeros(canon[name].shape[1:], canon[name].dtype))
        _set(out, ("stages",) + path, leaf.astype(want.dtype))
    gate = _get(struct["stages"], STRUCTURAL)
    _set(out, ("stages",) + STRUCTURAL, stack(
        lambda l: jnp.ones((), gate.dtype), jnp.zeros((), gate.dtype)))
    for name in GLOBAL_LEAVES:
        path = PROGRAM_PATHS[name]
        want = _get(struct["globals"], path)
        leaf = canon[name]
        pad = [(0, w - h) for w, h in zip(want.shape, leaf.shape)]
        _set(out, ("globals",) + path, jnp.pad(leaf, pad).astype(want.dtype))
    return out

"""Weights from `--seed`, made by the benchmark and handed to both sides.

Every leaf is named in the leaf table of the configuration's reference
(``references.Leaf``).  ``canonical(table, key)`` draws every weight of the
model in one traced function: per-layer leaves stacked on a leading layer
axis, the others whole, each as the table states it, so the model behaves
as the program's trainer would start it.  The reference computes on these
leaves as they are (``by_layer``); ``to_program`` lays them out in the
program's parameter tree (stage-major stacks, padded vocabulary), and
``program_rows`` reads one leaf of that layout back.  Nothing here imports
the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

# program leaves that are structure, not weights: the per-slot residual gate
# (1 on a layer, 0 on pipeline padding)
STRUCTURAL = ("gate",)


def seed_key(seed: int):
    """A PRNG key from any whole number up to 2**64."""
    return jax.random.fold_in(jax.random.key(seed % 2**32), seed // 2**32)


def leaf(table: dict, key, name: str, dtype=jnp.bfloat16):
    """One leaf as both sides hold it, drawn from ``key`` alone: the fold-in
    index is its place in the table."""
    lf = table[name]
    if lf.std is None:
        return jnp.full(lf.shape, lf.fill, dtype)
    k = jax.random.fold_in(key, list(table).index(name))
    x = jax.random.truncated_normal(k, -2.0, 2.0, lf.shape, jnp.float32)
    return (x * lf.std).astype(dtype)


def canonical(table: dict, key, dtype=jnp.bfloat16) -> dict:
    return {name: leaf(table, key, name, dtype) for name in table}


def by_layer(held: dict, table: dict) -> dict:
    """The layout a reference computes on: {"layers": [{leaf: one layer's
    slice}, ...], global leaf: array, ...}."""
    per_layer = [n for n, lf in table.items() if lf.per_layer]
    out = {n: held[n] for n, lf in table.items() if not lf.per_layer}
    layers = table[per_layer[0]].shape[0]
    out["layers"] = [{n: held[n][l] for n in per_layer}
                     for l in range(layers)]
    return out


def program_rows(x, lf, slots: int):
    """A program leaf in canonical layout: [layers, ...] from a stage leaf
    [data, slots, ...] (first replica of each stage), real vocabulary only
    for the embedding and the head."""
    if lf.per_layer:
        return jnp.stack([x[l // slots, l % slots]
                          for l in range(lf.shape[0])])
    return x[tuple(slice(0, n) for n in lf.shape)]


def norm(x, per_layer: bool):
    """Float32 norm: per layer ([layers]) for a stacked leaf, else whole."""
    sq = jnp.square(x.astype(jnp.float32))
    if per_layer:
        return jnp.sqrt(jnp.sum(sq, axis=tuple(range(1, x.ndim))))
    return jnp.sqrt(jnp.sum(sq))


def norms(x, lf) -> dict:
    """{"": ``norm``} of one leaf, or {"[part]": ``norm`` of the part}, one
    per part where the table splits the leaf."""
    if not lf.parts:
        return {"": norm(x, lf.per_layer)}
    return {f"[{p}]": norm(jnp.where(mask, x, 0), lf.per_layer)
            for p, mask in lf.parts.items()}


def named(name: str, values: dict, per_layer: bool) -> dict:
    """{"L0.wq": float, ...} or {"embed": float} from one ``norms``."""
    out = {}
    for part, value in values.items():
        if per_layer:
            out.update({f"L{l}.{name}{part}": float(v)
                        for l, v in enumerate(value)})
        else:
            out[name + part] = float(value)
    return out


def _get(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def _set(tree, path, value):
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = value


def _paths(tree, prefix=()):
    if not isinstance(tree, dict):
        return {prefix}
    return set().union(*(_paths(v, prefix + (k,)) for k, v in tree.items()))


def part(lf) -> str:
    return "stages" if lf.per_layer else "globals"


def slots(struct: dict) -> int:
    """Layer slots per stage of the program's tree."""
    return _get(struct["stages"], STRUCTURAL).shape[1]


def to_program(held: dict, table: dict, struct: dict, *, pp: int,
               data: int) -> dict:
    """The program's tree {"stages": ..., "globals": ...} from canonical
    leaves.  A stage leaf is [data, slots, ...]; data row i holds stage
    i % pp, whose slot j is layer (i % pp) * slots + j (ghost slots past the
    last layer are zero with gate 0).  The table has to name every weight
    of the program's tree, and only those."""
    want = {(part(lf),) + lf.path for lf in table.values()}
    want.add(("stages",) + STRUCTURAL)
    have = _paths(struct)
    if want != have:
        raise ValueError(f"the reference's leaf table and the program's "
                         f"tree differ: only in the table "
                         f"{sorted(want - have)}, only in the program "
                         f"{sorted(have - want)}")
    out = {}
    n_slots = slots(struct)
    n_layers = next(lf.shape[0] for lf in table.values() if lf.per_layer)

    def stack(per_layer, fill):
        rows = []
        for i in range(data):
            s = i % pp
            rows.append(jnp.stack([
                per_layer(s * n_slots + j) if s * n_slots + j < n_layers
                else fill for j in range(n_slots)]))
        return jnp.stack(rows)

    for name, lf in table.items():
        path = (part(lf),) + lf.path
        target = _get(struct, path)
        x = held[name]
        if lf.per_layer:
            x = stack(lambda l, x=x: x[l], jnp.zeros(x.shape[1:], x.dtype))
        else:
            x = jnp.pad(x, [(0, w - h)
                            for w, h in zip(target.shape, x.shape)])
        _set(out, path, x.astype(target.dtype))
    gate = _get(struct["stages"], STRUCTURAL)
    _set(out, ("stages",) + STRUCTURAL, stack(
        lambda l: jnp.ones((), gate.dtype), jnp.zeros((), gate.dtype)))
    return out

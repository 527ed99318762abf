"""The reference's AdamW updates each leaf in blocks of rows (``blocks``),
so that a 151552-row embedding's float32 moments never sit on the device
whole.  The update is elementwise: blocks have to give the bits of the
whole-leaf update, on the first step and on later ones (moments reloaded
from host memory), with and without decay, for 2-D, stacked and 1-D
leaves, and where the rows do not divide into blocks."""
import math
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir))

from benchmarks.chip import adamw  # noqa: E402

# a block budget no leaf reaches: each leaf updated whole
WHOLE = 2**62
OPT = {"lr_peak": 3e-2, "warmup_steps": 2, "total_steps": 10, "b1": 0.9,
       "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


@pytest.mark.parametrize("shape,most", [
    ((12, 5), 20),          # rows of a 2-D leaf, 4 to a block
    ((13, 5), 20),          # the last block shorter
    ((3, 4, 5), 40),        # a stacked leaf: two layers to a block
    ((3, 4, 5), 12),        # a layer larger than a block: its rows
    ((7,), 3),              # a vector
    ((6, 4), 100),          # a leaf that fits is one block
    ((2, 9), 4),            # a row larger than a block: its elements
])
def test_blocks_cover_each_element_once(shape, most):
    seen = np.zeros(shape, int)
    bs = adamw.blocks(shape, most)
    for starts, sizes in bs:
        seen[tuple(slice(a, a + n) for a, n in zip(starts, sizes))] += 1
        assert math.prod(sizes) <= most
    assert (seen == 1).all()
    assert adamw.blocks(shape, WHOLE) == [((0,) * len(shape), shape)]
    if math.prod(shape) > most:
        assert len(bs) > 1


def _tree(shapes, seed, scale, dtype):
    import jax
    import jax.numpy as jnp

    key = jax.random.key(seed)
    return {name: (scale * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)).astype(dtype)
            for i, (name, shape) in enumerate(sorted(shapes.items()))}


def _moment(blocks_of_leaf, shape, most):
    out = np.zeros(shape, np.float32)
    for (starts, sizes), b in zip(adamw.blocks(shape, most), blocks_of_leaf):
        out[tuple(slice(a, a + n) for a, n in zip(starts, sizes))] = b
    return out


def sliced_and_whole(shapes, slice_bytes, monkeypatch, *, steps=3):
    """Both updates from the same weights and gradients: per step, the
    params (and while kept, the moments) of the update in blocks of at
    most ``slice_bytes`` and of the whole-leaf update."""
    import jax.numpy as jnp

    out = []
    for size in (slice_bytes, WHOLE):
        monkeypatch.setattr(adamw, "SLICE_BYTES", size)
        opt = adamw.AdamW(OPT, jnp.bfloat16,
                          lambda path: path[-1].key.startswith("w"))
        params = _tree(shapes, 0, 1.0, jnp.bfloat16)
        got = []
        for step in range(steps):
            grads = _tree(shapes, 100 + step, 0.3, jnp.float32)
            params = opt.update(params, grads, step, 2.5,
                                keep=step < steps - 1)
            read = {"p": {k: np.asarray(v.astype(jnp.float32))
                          for k, v in params.items()}}
            if opt.m is not None:
                read.update({
                    part: {k: _moment(blks, shapes[k], opt.most)
                           for k, blks in tree.items()}
                    for part, tree in (("m", opt.m), ("v", opt.v))})
            got.append(read)
        out.append(got)
    return out


SHAPES = {
    "2-D": {"w": (37, 24)},                  # 37 rows: blocks of 8, then 5
    "stacked": {"w": (5, 4, 24)},            # 2 layers a block, then 1
    "undecayed": {"b": (37, 24)},
    "layer-rows": {"w": (3, 20, 24)},        # a layer cut into its rows
    "vector": {"b": (500,)},                 # runs of 192 elements
    "whole": {"w": (4, 24)},                 # a leaf that fits: one block
    "row-elements": {"w": (3, 400)},         # a row cut into its elements
    "tree": {"w1": (5, 4, 24), "w2": (37, 24), "b": (5, 24)},
}


@pytest.mark.parametrize("leaf", list(SHAPES))
def test_the_update_in_blocks_is_the_whole_leaf_update(leaf, monkeypatch):
    # 8 rows of 24 float32 elements: 2304 bytes of g, m and v
    sliced, whole = sliced_and_whole(SHAPES[leaf], 8 * 24 * 12 + 11,
                                     monkeypatch)
    assert len(sliced) == 3 and "m" not in sliced[-1]
    for a, b in zip(sliced, whole):
        assert a.keys() == b.keys()
        for part in a:
            for k in a[part]:
                np.testing.assert_array_equal(a[part][k], b[part][k])
    moved = whole[0]["p"]
    first = _tree(SHAPES[leaf], 0, 1.0, np.float32)
    assert all((moved[k] != np.asarray(first[k].astype("bfloat16")
                                       .astype("float32"))).any()
               for k in moved)

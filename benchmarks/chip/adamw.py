"""Plain AdamW as a configuration states it, for the reference's steps.

Global-norm clipping, bias-corrected moments in float32, decoupled weight
decay on the weight matrices only, and the learning rate of a linear warm-up
into a cosine decay to a tenth of the peak.  Parameters are kept in the type
the configuration states (bfloat16): each update is computed in float32 and
rounded to it.  The moments live in host memory between steps.  Each leaf
is updated in blocks of rows (``blocks``), each block's moments moved to
the device for its update alone, so that the reference fits beside a 16 GiB
chip's copy of the weights and gradients also where one leaf is large (a
151552-row embedding: 2.48 GB of float32 gradient).  The update is
elementwise: blocks give the same bits as the whole leaf.  Imports nothing
of the program.
"""
from __future__ import annotations

import itertools
import math

import jax
import jax.numpy as jnp
from jax import lax

# float32 gradient, first and second moment of one block, at most
SLICE_BYTES = 256 * 2**20
# bytes an element of a block takes in them
BLOCK_ELEMENT_BYTES = 3 * 4


def learning_rate(opt: dict, step: int) -> float:
    """The rate of update number ``step`` (0 for the first)."""
    peak, warm, total = opt["lr_peak"], opt["warmup_steps"], opt["total_steps"]
    if step < warm:
        return peak * (step + 1) / warm
    prog = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * prog)))


def _host(x):
    return jax.device_put(x, jax.memory.Space.Host)


def _device(x):
    return jax.device_put(x, jax.memory.Space.Device)


def blocks(shape, most: int) -> list:
    """[(starts, sizes)]: a leaf of ``shape`` cut along its leading axes into
    blocks of at most ``most`` elements, in order.  The cut is at the first
    axis one of whose indices holds at most ``most``: into runs of rows of
    a 2-D leaf, of layers of a stacked one, and, where one layer is larger
    than that, of its rows, one layer at a time.  A leaf that fits is one
    block.  The runs of an axis are of one length but the last, which is
    shorter where they do not divide it."""
    shape = tuple(shape)
    if math.prod(shape) <= most:
        return [((0,) * len(shape), shape)]
    a = next(i for i in range(len(shape))
             if math.prod(shape[i + 1:]) <= most)
    rows = max(1, most // math.prod(shape[a + 1:]))
    rows = -(-shape[a] // -(-shape[a] // rows))     # as even as they go
    rest = len(shape) - a - 1
    out = []
    for outer in itertools.product(*map(range, shape[:a])):
        for s in range(0, shape[a], rows):
            out.append((outer + (s,) + (0,) * rest,
                        (1,) * a + (min(rows, shape[a] - s),)
                        + shape[a + 1:]))
    return out


class AdamW:
    """``update(params, grads, step)``: one step on a tree of leaves.
    ``decayed(path)`` says which leaves take weight decay.  Each leaf is
    updated in ``blocks`` whose float32 gradient and moments take at most
    ``SLICE_BYTES``, as it is when the optimizer is built.  The moments are
    kept per block, in host memory."""

    def __init__(self, opt: dict, param_dtype, decayed):
        self.opt = opt
        self.dtype = param_dtype
        self.decayed = decayed
        self.most = max(1, SLICE_BYTES // BLOCK_ELEMENT_BYTES)
        self.m = self.v = None
        self._upd = jax.jit(self._update_block,
                            static_argnames=("sizes", "first", "decay",
                                             "keep"),
                            donate_argnums=0)

    def _update_block(self, p, g, m, v, starts, lr, clip, bc1, bc2, *,
                      sizes, first, decay, keep):
        """``p`` with the block at ``starts`` updated (in place: ``p`` is
        donated); ``g`` is the whole gradient leaf, ``m`` and ``v`` the
        block's moments."""
        o = self.opt
        g = lax.dynamic_slice(g, starts, sizes) * clip
        if first:
            m_new = (1 - o["b1"]) * g
            v_new = (1 - o["b2"]) * g * g
        else:
            m_new = o["b1"] * _device(m) + (1 - o["b1"]) * g
            v_new = o["b2"] * _device(v) + (1 - o["b2"]) * g * g
        u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + o["eps"])
        p32 = lax.dynamic_slice(p, starts, sizes).astype(jnp.float32)
        if decay:
            u = u + o["weight_decay"] * p32
        p_new = (p32 - lr * u).astype(self.dtype)
        p = lax.dynamic_update_slice(p, p_new.astype(p.dtype), starts)
        if not keep:
            return p, None, None
        return p, _host(m_new), _host(v_new)

    def update(self, params, grads, step: int, gnorm: float, *,
               keep: bool = True):
        """New params after update number ``step``, given the gradient's
        global norm.  ``keep=False`` drops the moments (after the last step
        the reference takes)."""
        o = self.opt
        clip = min(1.0, o["clip_norm"] / max(gnorm, 1e-12))
        t = step + 1
        bc1, bc2 = 1 - o["b1"] ** t, 1 - o["b2"] ** t
        lr = learning_rate(o, step)
        flat_p, tdef = jax.tree_util.tree_flatten_with_path(params)
        flat_g = tdef.flatten_up_to(grads)
        first = self.m is None
        flat_m = [None] * len(flat_p) if first else tdef.flatten_up_to(self.m)
        flat_v = [None] * len(flat_p) if first else tdef.flatten_up_to(self.v)
        new_p, new_m, new_v = [], [], []
        for i, ((path, p), g) in enumerate(zip(flat_p, flat_g)):
            decay = bool(self.decayed(path))
            ms, vs = [], []
            for j, (starts, sizes) in enumerate(blocks(p.shape, self.most)):
                p, m, v = self._upd(
                    p, g, None if first else flat_m[i][j],
                    None if first else flat_v[i][j], starts, lr, clip, bc1,
                    bc2, sizes=sizes, first=first, decay=decay, keep=keep)
                ms.append(m)
                vs.append(v)
            flat_g[i] = flat_m[i] = flat_v[i] = g = None
            new_p.append(p)
            new_m.append(ms)
            new_v.append(vs)
        self.m = tdef.unflatten(new_m) if keep else None
        self.v = tdef.unflatten(new_v) if keep else None
        return tdef.unflatten(new_p)

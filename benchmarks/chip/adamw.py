"""Plain AdamW as a configuration states it, for the reference's steps.

Global-norm clipping, bias-corrected moments in float32, decoupled weight
decay on the weight matrices only, and the learning rate of a linear warm-up
into a cosine decay to a tenth of the peak.  Parameters are kept in the type
the configuration states (bfloat16): each update is computed in float32 and
rounded to it.  The moments live in host memory between steps, one leaf
pair on the device at a time, so the reference fits beside a 16 GiB chip's
copy of the weights and gradients.  Imports nothing of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


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


class AdamW:
    """``update(params, grads, step)``: one step on a tree of leaves.
    ``decayed(path)`` says which leaves take weight decay."""

    def __init__(self, opt: dict, param_dtype, decayed):
        self.opt = opt
        self.dtype = param_dtype
        self.decayed = decayed
        self.m = self.v = None
        self._upd = jax.jit(self._update_leaf,
                            static_argnames=("first", "decay", "keep"),
                            donate_argnums=0)

    def _update_leaf(self, p, g, m, v, lr, clip, bc1, bc2, *, first, decay,
                     keep):
        o = self.opt
        g = g * clip
        if first:
            m_new = (1 - o["b1"]) * g
            v_new = (1 - o["b2"]) * g * g
        else:
            m_new = o["b1"] * _device(m) + (1 - o["b1"]) * g
            v_new = o["b2"] * _device(v) + (1 - o["b2"]) * g * g
        u = (m_new / bc1) / (jnp.sqrt(v_new / bc2) + o["eps"])
        p32 = p.astype(jnp.float32)
        if decay:
            u = u + o["weight_decay"] * p32
        p_new = (p32 - lr * u).astype(self.dtype)
        if not keep:
            return p_new, None, None
        return p_new, _host(m_new), _host(v_new)

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
            q, m, v = self._upd(p, g, flat_m[i], flat_v[i], lr, clip, bc1,
                                bc2, first=first,
                                decay=bool(self.decayed(path)), keep=keep)
            flat_g[i] = flat_m[i] = flat_v[i] = None
            new_p.append(q)
            new_m.append(m)
            new_v.append(v)
        self.m = tdef.unflatten(new_m) if keep else None
        self.v = tdef.unflatten(new_v) if keep else None
        return tdef.unflatten(new_p)

"""Plain references, one module per architecture, named by a configuration
file's ``reference`` key.

A reference module exports:

- ``leaves(model)``: its leaf table, ``{name: Leaf}``, the per-layer leaves
  first.  The order fixes the index each leaf's draw folds into the seed,
  so a module keeps it once a cell uses it.
- ``Reference(model, precision=...)``: the model's one-step
  ``loss_and_grads(params, tokens, labels) -> (loss, grads)``, with
  ``params`` and ``grads`` laid out as ``weights.by_layer`` lays them.

Nothing here or in a reference imports the program.
"""
from __future__ import annotations

import importlib
from dataclasses import dataclass


@dataclass(frozen=True)
class Leaf:
    """One weight of the model as its reference states it.

    ``shape`` is the leaf's own; a per-layer leaf is stacked on a leading
    layer axis.  It is drawn as a truncated normal (±2 std) at ``std``, or,
    where ``std`` is None, filled with ``fill``.  Both sides hold the leaf
    in the form the program stores it: an RMSNorm gain w that the program
    holds as w − 1 and applies as (1 + g) is filled at 0, and the reference
    applies it so too.  ``path`` is where the leaf lives in the program's
    parameter tree, under ``stages`` for a per-layer leaf, else
    ``globals``.  ``decay``: AdamW decays it.  ``matmul``: the share of
    the leaf that multiplies each token (``flops.matmul_params``): 1 for a
    projection, top_k / n_experts for a stack of routed experts, 0 for a
    lookup, a norm or a bias.

    ``parts``: {part: boolean mask over the leaf's shape, without the layer
    axis}, where the leaf's elements play different roles.  Its norms are
    then read per part (``weights.norms``), so that the check's rule on the
    reference's gradient (``check.moved``) can leave out a part whose
    gradient is nought to rounding: the passed half of a key bias under
    partial rotary, which shifts every score of a query alike.
    """

    shape: tuple
    path: tuple
    per_layer: bool
    std: float | None = None
    fill: float = 0.0
    decay: bool = False
    matmul: float = 0.0
    parts: dict | None = None


def of(model):
    """The reference module the configuration names."""
    return importlib.import_module(f"{__name__}.{model.reference}")

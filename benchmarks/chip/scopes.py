"""Device time by the program's layer scopes.

The program opens one ``jax.named_scope`` per layer of the train step
(``src/repro/runtime/scopes.py``); the names reach the compiled HLO as each
instruction's ``op_name`` (``hlo_scopes``), e.g.
``jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/
rematted_computation/sppo.mlp/dot_general``.  Scopes nest, and the innermost
one names the layer.  JAX's own ``rematted_computation`` component marks an
operation that ``jax.checkpoint`` recomputes in the backward.  The names
are kept here, not imported, so the benchmark reads a program that lacks
them (an older one) as having nothing to read.

The harness hands a reader the step's op_names (``Readings.scopes``, from
``hlo_scopes`` of the compiled step's HLO text) beside its kernels, host
copies and collectives (``tracefile.hlo_ops``).

Host copies: XLA leaves most of the copies it makes without an ``op_name``
and ``hlo_scopes`` gives them that of the value they move or the operation a
reload feeds.  The AdamW moments' copies so come out under ``sppo.adamw``;
the activations' are moved out of and back into the layer scan, a loop,
which has no layer.  A host copy with no layer is therefore counted as
``sppo.offload``, the activation transfer it is.
"""
from __future__ import annotations

import re

from . import tracefile

EMBED = "sppo.embed"
NORM = "sppo.norm"
ATTN = "sppo.attn"
MLP = "sppo.mlp"
HEAD_LOSS = "sppo.head_loss"
ADAMW = "sppo.adamw"
OFFLOAD = "sppo.offload"
LAYERS = (EMBED, NORM, ATTN, MLP, HEAD_LOSS, ADAMW, OFFLOAD)

_SCOPE = re.compile(r"sppo\.[a-z_]+")
_RECOMPUTE = re.compile(r"(?:^|[/(])rematted_computation(?:[/)]|$)")
_COPIES = ("copy", "copy-start", "copy-done")


def hlo_scopes(hlo_text: str) -> dict:
    """{instruction: op_name} for one compiled program's HLO text: every
    instruction whose line has an ``op_name`` in its metadata, except
    ``tracefile.CONTAINERS``, whose events span the operations of their
    bodies.  XLA drops the ``op_name`` of most host copies it makes (the v5e
    compiler keeps it where a copy-done merges into a named instruction).
    A host copy with none -- its start, its done (a reload's done included)
    or a synchronous copy -- takes that of its done, else of the done's
    first named user (the operation a reload feeds), else of the value it
    moves, found through the copies before it."""
    copies = tracefile.hlo_ops(hlo_text)["host_copies"]
    found = {}
    # every op_name; each copy's opcode and operand; each value's users
    named, kinds, moved, users = {}, {}, {}, {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT\s+)?%(\S+)\s*=\s*(.*)$", line)
        if not m:
            continue
        name, rest = m.groups()
        op = re.search(r"\)?\s*([a-z][a-z0-9-]*)\(", rest)
        opcode = op.group(1) if op else ""
        op_name = re.search(r'op_name="([^"]*)"', rest)
        if op_name:
            named[name] = op_name.group(1)
            if opcode not in tracefile.CONTAINERS:
                found[name] = op_name.group(1)
        operands = rest[op.end():] if op else ""
        for ref in re.findall(r"%([^\s,)]+)", operands):
            users.setdefault(ref, []).append(name)
        if opcode in _COPIES:
            kinds[name] = opcode
            src = re.match(r"[^%)]*%([^\s,)]+)", operands)
            if src:
                moved[name] = src.group(1)

    def source(name):
        for _ in range(8):
            if name in named or name not in moved:
                break
            name = moved[name]
        return named.get(name)

    for done, kind in kinds.items():
        start = moved.get(done) if kind == "copy-done" else done
        if kind == "copy-start" or not {done, start} & copies:
            continue
        op_name = (named.get(done)
                   or next((named[u] for u in users.get(done, ())
                            if u in named), None)
                   or source(start))
        for name in (done, start):
            if name not in named and op_name:
                found[name] = op_name
    return found


def layer_of(op_name: str | None) -> str | None:
    """The innermost of LAYERS in an ``op_name`` path, also inside
    transformation wrappers (``transpose(jvp(sppo.attn))``); None if none."""
    hits = [s for s in _SCOPE.findall(op_name or "") if s in LAYERS]
    return hits[-1] if hits else None


def is_recompute(op_name: str | None) -> bool:
    """Whether the operation is recomputed: a ``rematted_computation``
    component anywhere in its ``op_name``."""
    return bool(_RECOMPUTE.search(op_name or ""))


def is_host_copy(r, op: str) -> bool:
    """A copy to or from host memory: in ``host_copies``, or the copy-done
    of one there (``copy-done.N`` of ``copy-start.N``, as XLA names them: a
    reload's done has no S(5) shape of its own)."""
    copies = r.hlo["host_copies"]
    kind, _, n = op.partition(".")
    return op in copies or (kind == "copy-done" and (
        "copy-start." + n if n else "copy-start") in copies)


def layer(r, op: str) -> str | None:
    """The layer of an instruction: its innermost scope; a host copy with
    none is an activation transfer, OFFLOAD (see the module's note)."""
    found = layer_of(r.scopes.get(op))
    if found is None and is_host_copy(r, op):
        return OFFLOAD
    return found


def _is_container(op: str) -> bool:
    return op.rsplit(".", 1)[0] in tracefile.CONTAINERS


def ms_per_step(r, pred) -> float | None:
    """Summed time, in milliseconds per step, of the ``XLA Ops`` events whose
    instruction ``pred`` accepts, clipped to the traced window; the mean
    over the cell's devices.  Loops and calls, whose events span the
    operations they run, are never taken.  None when no event is taken, or
    when the step's HLO carries no scope at all (a program without them)."""
    tr = r.trace
    if (r.steps == 0 or not tr["window"] or not tr["devices"]
            or not r.scopes):
        return None
    t0, t1 = tr["window"]
    per_dev = []
    for events in tr["devices"].values():
        ops = {e[0] for e in events if not _is_container(e[0])}
        per_dev.append(tracefile.op_ns(events, {op for op in ops if pred(op)},
                                       t0, t1))
    if not any(per_dev):
        return None
    return sum(per_dev) / len(per_dev) / r.steps / 1e6


def in_layer(r, name: str):
    """A ``pred`` for ``ms_per_step``: the instructions of layer ``name``."""
    return lambda op: layer(r, op) == name


def by_layer(r) -> dict:
    """{layer: ms per step} over LAYERS, plus "unscoped" for the operations
    in no layer; layers with nothing to read are left out."""
    out = {name: ms_per_step(r, in_layer(r, name)) for name in LAYERS}
    out["unscoped"] = ms_per_step(r, lambda op: layer(r, op) is None)
    return {k: v for k, v in out.items() if v is not None}


def unscoped_share(r) -> float | None:
    """Share of the device's busy time, in percent, spent in operations in
    no layer; the mean over the cell's devices.  None where nothing carries
    a layer."""
    layers = by_layer(r)
    if not set(layers) - {"unscoped"}:
        return None
    t0, t1 = r.trace["window"]
    busy = [tracefile.busy_ns(ev, t0, t1) for ev in r.trace["devices"].values()]
    busy_ms = sum(busy) / len(busy) / r.steps / 1e6
    return 100.0 * layers.get("unscoped", 0.0) / busy_ms


def top_unscoped(r, top: int = 10) -> list:
    """[[instruction, ms per step], ...]: the operations in no layer with
    the most device time, the mean over the devices."""
    tr = r.trace
    if r.steps == 0 or not tr["window"]:
        return []
    t0, t1 = tr["window"]
    tot = {}
    for events in tr["devices"].values():
        for op, a, b in tracefile._clip(events, t0, t1):
            if not _is_container(op) and layer(r, op) is None:
                tot[op] = tot.get(op, 0) + (b - a)
    nd = max(1, len(tr["devices"]))
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:top]
    return [[op, v / nd / r.steps / 1e6] for op, v in best]

"""Shared jaxpr def-use walker (DESIGN.md §17).

One traversal serves every jaxpr consumer in the repo: the memory ledger's
tagged-byte / device_put accounting (runtime/memledger.py) and the static
contract auditor (analysis/audit.py).  The walker is deliberately dumb and
total — it visits every equation of every sub-jaxpr (jit / shard_map /
scan / remat / custom_vjp bodies, wherever a ``Jaxpr`` or ``ClosedJaxpr``
hides in an equation's params) exactly once, carrying:

  * ``path``  — the primitive names of the enclosing higher-order equations
    (e.g. ``("shard_map", "scan", "remat2")``), the scope evidence the
    overlap-hazard rule R3 keys on;
  * ``mult``  — the product of enclosing ``scan`` trip counts, so byte
    accounting over a scanned body charges every iteration.

Shapes and dtypes are static facts of the traced program, so everything
computed here is exact accounting, not an estimate.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Tuple

import jax
from jax.extend import core as jex_core

# Bit widths of the sub-byte ml_dtypes: numpy's ``dtype.itemsize`` reports a
# full byte for them (packed XLA buffers hold 2 int4s per byte), so
# itemsize*8 would double-count every int4/fp4 tensor.  Anything not listed
# really is itemsize*8 bits.
DTYPE_BITS = {
    "int2": 2, "uint2": 2,
    "int4": 4, "uint4": 4,
    "float4_e2m1fn": 4,
}

# Primitives that only relabel / relay data — the backward producer walk
# (``first_real_producer``) looks straight through them.
LAYOUT_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "squeeze", "expand_dims", "transpose",
    "convert_element_type", "copy", "stop_gradient", "name",
    "optimization_barrier",
})

# Higher-order primitives whose body executes *sequentially* with respect to
# the surrounding program: an explicit copy nested inside one of these scopes
# cannot be hoisted ahead by the scheduler — it serializes into the scope's
# own execution (the R3 overlap-hazard evidence).
SEQUENTIAL_SCOPES = frozenset({"scan", "while", "remat2", "remat",
                               "checkpoint"})


def aval_elems(aval) -> int:
    try:
        size = 1
        for s in aval.shape:
            size *= int(s)
        return size
    except Exception:  # pragma: no cover - abstract tokens etc.
        return 0


def aval_bytes(aval) -> int:
    try:
        bits = DTYPE_BITS.get(aval.dtype.name, aval.dtype.itemsize * 8)
        return (aval_elems(aval) * bits + 7) // 8
    except Exception:  # pragma: no cover - abstract tokens etc.
        return 0


def sub_jaxprs(v) -> Iterator[object]:
    """Yield every (open) Jaxpr reachable from one equation-param value."""
    if isinstance(v, jex_core.ClosedJaxpr):
        yield v.jaxpr
    elif isinstance(v, jex_core.Jaxpr):
        yield v
    elif isinstance(v, (list, tuple)):
        for item in v:
            yield from sub_jaxprs(item)


def eqn_sub_jaxprs(eqn) -> Iterator[object]:
    for v in eqn.params.values():
        yield from sub_jaxprs(v)


@dataclass(frozen=True)
class Site:
    """One equation, located: the scope jaxpr it lives in, its index there,
    the enclosing higher-order primitive names, and the scan multiplier."""

    path: Tuple[str, ...]
    jaxpr: object
    index: int
    eqn: object
    mult: int

    @property
    def scope(self) -> str:
        return "/".join(self.path) or "top"

    @property
    def in_sequential_scope(self) -> bool:
        return any(p in SEQUENTIAL_SCOPES for p in self.path)


def _as_jaxpr(closed_or_jaxpr):
    return getattr(closed_or_jaxpr, "jaxpr", closed_or_jaxpr)


def iter_sites(closed_or_jaxpr, *, path: Tuple[str, ...] = (),
               mult: int = 1) -> Iterator[Site]:
    """DFS over every equation of every nested sub-jaxpr, exactly once."""
    jaxpr = _as_jaxpr(closed_or_jaxpr)
    for i, eqn in enumerate(jaxpr.eqns):
        yield Site(path=path, jaxpr=jaxpr, index=i, eqn=eqn, mult=mult)
        m = mult
        if eqn.primitive.name == "scan":
            m = mult * int(eqn.params.get("length", 1))
        sub_path = path + (eqn.primitive.name,)
        for sub in eqn_sub_jaxprs(eqn):
            yield from iter_sites(sub, path=sub_path, mult=m)


# A traced put names its target as a ``jax.memory.Space`` (the jaxpr prints
# ``devices=(MemorySpace.Host,)``); Space.Host is the pinned_host kind.
_SPACE_KINDS = {jax.memory.Space.Host: "pinned_host",
                jax.memory.Space.Device: "device"}


def device_put_kinds_of(eqn):
    """Memory-kind list of one ``device_put`` equation (may be empty when
    the put carries no explicit placement): a ``jax.memory.Space`` target
    maps to its kind, a sharding target gives its ``memory_kind``."""
    kinds = []
    for d in eqn.params.get("devices", ()):
        k = (_SPACE_KINDS.get(d) if isinstance(d, jax.memory.Space)
             else getattr(d, "memory_kind", None))
        if k is not None:
            kinds.append(k)
    return kinds


def walk_named(closed_or_jaxpr) -> Tuple[Dict[str, int], Dict[str, int]]:
    """{checkpoint name: bytes}, {checkpoint name: elems} over the whole
    trace, with enclosing scan trip counts multiplied in — the byte channel
    behind ``memledger.tagged_bytes_from_jaxpr`` and the moments walk."""
    out: Dict[str, int] = {}
    elems: Dict[str, int] = {}
    for site in iter_sites(closed_or_jaxpr):
        eqn = site.eqn
        if eqn.primitive.name != "name":
            continue
        nm = eqn.params.get("name", "")
        out[nm] = out.get(nm, 0) + site.mult * sum(
            aval_bytes(v.aval) for v in eqn.invars)
        elems[nm] = elems.get(nm, 0) + site.mult * sum(
            aval_elems(v.aval) for v in eqn.invars)
    return out, elems


def walk_device_puts(closed_or_jaxpr) -> Dict[str, int]:
    """{memory_kind: equation count} of explicit ``device_put`` equations.

    Counts equations, not executions: a put nested in a scan body counts
    once (parity with the ledger's one-copy contract accounting, which
    compares against per-step equation counts)."""
    out: Dict[str, int] = {}
    for site in iter_sites(closed_or_jaxpr):
        if site.eqn.primitive.name != "device_put":
            continue
        for kind in device_put_kinds_of(site.eqn):
            out[kind] = out.get(kind, 0) + 1
    return out


# ---------------------------------------------------------------------------
# Scope-local def-use lookups (the audit rules' walking primitives)
# ---------------------------------------------------------------------------


def producers(jaxpr) -> Dict[object, object]:
    """{var: producing eqn} within one scope (invars/constvars absent)."""
    jaxpr = _as_jaxpr(jaxpr)
    out: Dict[object, object] = {}
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            out[v] = eqn
    return out


def first_real_producer(jaxpr, var, prods: Optional[Dict] = None,
                        *, through=LAYOUT_PRIMS):
    """Walk backward from ``var`` through pure layout/relabel equations and
    return the first producing eqn that actually computes something — or
    None when the chain bottoms out at a scope input/constant (a value that
    was never written in this scope)."""
    if prods is None:
        prods = producers(jaxpr)
    seen = 0
    while True:
        if isinstance(var, jex_core.Literal):
            return None
        eqn = prods.get(var)
        if eqn is None:
            return None
        if eqn.primitive.name not in through:
            return eqn
        var = eqn.invars[0]
        seen += 1
        if seen > 10000:  # pragma: no cover - malformed graph guard
            return eqn


def ancestor_prims(jaxpr, var, prods: Optional[Dict] = None,
                   *, limit: int = 2000) -> set:
    """Primitive names of every equation reachable backward from ``var``
    within this scope (bounded) — provenance evidence, e.g. "does this
    select predicate derive from ``axis_index``?"."""
    if prods is None:
        prods = producers(jaxpr)
    prims: set = set()
    frontier = [var]
    visited = set()
    while frontier and len(visited) < limit:
        v = frontier.pop()
        if isinstance(v, jex_core.Literal) or id(v) in visited:
            continue
        visited.add(id(v))
        eqn = prods.get(v)
        if eqn is None:
            continue
        prims.add(eqn.primitive.name)
        frontier.extend(eqn.invars)
    return prims


_WRAPPER_PRIMS = ("jit", "shard_map", "remat2", "custom_vjp_call",
                  "custom_jvp_call", "closed_call")


def _wrapper_body(eqn):
    """The single body jaxpr of a wrapper equation, or None."""
    for v in eqn.params.values():
        subs = list(sub_jaxprs(v))
        if len(subs) == 1:
            return subs[0]
    return None


def outvar_frames(closed_or_jaxpr, index: int):
    """Resolve output ``index`` of a traced program through wrapper
    equations (jit / shard_map / remat) and pure layout equations to the
    scope that actually computes it.

    Returns ``(frames, scope_jaxpr, var)`` where ``frames`` is the wrapper
    chain walked through, outermost first, as ``(parent_jaxpr, wrapper_eqn)``
    pairs — the evidence needed to chase provenance of a value back OUT of
    the final scope (see ``cross_scope_ancestor_prims``)."""
    jaxpr = _as_jaxpr(closed_or_jaxpr)
    var = jaxpr.outvars[index]
    frames = []
    steps = 0
    while steps < 10000:
        steps += 1
        if isinstance(var, jex_core.Literal):
            return frames, jaxpr, var
        prods = producers(jaxpr)
        eqn = prods.get(var)
        if eqn is None:
            return frames, jaxpr, var
        if eqn.primitive.name in LAYOUT_PRIMS:
            var = eqn.invars[0]
            continue
        if eqn.primitive.name not in _WRAPPER_PRIMS:
            return frames, jaxpr, var
        inner = _wrapper_body(eqn)
        if inner is None or len(inner.outvars) != len(eqn.outvars):
            return frames, jaxpr, var
        pos = list(eqn.outvars).index(var)
        frames.append((jaxpr, eqn))
        jaxpr, var = inner, inner.outvars[pos]
    return frames, jaxpr, var  # pragma: no cover - malformed graph guard


def descend_outvar(closed_or_jaxpr, index: int):
    """``outvar_frames`` without the frame evidence — ``(scope_jaxpr, var)``."""
    _, jaxpr, var = outvar_frames(closed_or_jaxpr, index)
    return jaxpr, var


def cross_scope_ancestor_prims(frames, jaxpr, var, *, limit: int = 2000):
    """Primitive names reachable backward from ``var``, hopping OUT of the
    current scope through the wrapper ``frames`` when the chain bottoms out
    at a scope input (a value computed by the caller and passed in).

    Position mapping assumes the wrapper's operands align 1:1 with the body
    jaxpr's invars (true for jit / shard_map / remat2); when they don't,
    the hop is skipped and provenance is simply truncated there."""
    prims: set = set()
    stack = list(frames)
    vars_here = [var]
    budget = limit
    while vars_here and budget > 0:
        jx = _as_jaxpr(jaxpr)
        prods = producers(jx)
        frontier = list(vars_here)
        visited = set()
        hit_invars = []
        while frontier and budget > 0:
            v = frontier.pop()
            if isinstance(v, jex_core.Literal) or id(v) in visited:
                continue
            visited.add(id(v))
            budget -= 1
            eqn = prods.get(v)
            if eqn is None:
                if v in jx.invars:
                    hit_invars.append(jx.invars.index(v))
                continue
            prims.add(eqn.primitive.name)
            frontier.extend(eqn.invars)
        if not hit_invars or not stack:
            break
        parent, weqn = stack.pop()
        offset = len(weqn.invars) - len(jx.invars)
        if offset < 0:
            break
        jaxpr = parent
        vars_here = [weqn.invars[p + offset] for p in hit_invars
                     if p + offset < len(weqn.invars)]
    return prims

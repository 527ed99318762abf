"""Trace-time contract auditor (DESIGN.md §17).

Traces a plan cell's real step functions over ``ShapeDtypeStruct`` inputs —
``jax.make_jaxpr`` / ``jax.eval_shape`` only, so nothing is allocated,
compiled, or executed — and proves the offload/pipeline dataflow contracts
on the jaxpr itself:

  R1  transfer counts — exactly one D2H per tagged ``act_off`` capture and
      one H2D per backward replay (the counts the runtime ledger's
      ``device_put_kinds`` later measures); one H2D + one D2H per moment
      leaf on the explicit opt-state path.
  R2  placement — ``act_scale@`` stays device-side; moment zeros never
      materialize in device memory at init.
  R3  overlap hazard — an H2D nested inside a sequential scope (scan /
      while / remat) serializes into that scope's own backward instead of
      overlapping it (the PR 5 "sync" exposure, now a named finding).
  R4  masked state — every pipeline-state output of the pp>1 prefill must
      pass through a tick-validity ``select`` keyed on the stage index
      (the PR 9 drain-tick KV clobber class).
  R5  codec pairing — every captured quantized payload has a reachable
      ``act_scale@`` name, and no inexact (sub-fp32 float) payload is ever
      named inside a remat/scan scope (the PR 7 NaN trap).

Each rule's evidence is recorded in ``AuditReport.counters`` even when it
passes, so a clean report documents what was proven.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Set, Tuple

import jax
import jax.numpy as jnp

from repro.analysis import dataflow as df
from repro.analysis.report import AuditReport, Finding
from repro.core import offload as ofl
from repro.runtime import hostmem

# dtypes that cannot ride a differentiated residual in the open (PR 7):
# quantized payloads must cross remat boundaries bitcast to an exact
# integer container, else the remat replay re-derives cotangents for an
# inexact value and NaN-poisons the backward
_INEXACT_WIRE_PREFIXES = ("float8", "float4")


# ---------------------------------------------------------------------------
# Trace facts: one walk, every rule's raw evidence
# ---------------------------------------------------------------------------


@dataclass
class TraceFacts:
    d2h: int = 0                    # device_put eqns into host kinds
    h2d: int = 0                    # device_put eqns into device kind
    capture_pairs: int = 0          # host-put → act_off name, same scope
    paired_off_names: Set[str] = field(default_factory=set)
    names: Set[str] = field(default_factory=set)
    h2d_hazards: List[df.Site] = field(default_factory=list)   # R3 evidence
    inexact_named: List[Tuple[str, str, str]] = field(
        default_factory=list)       # (name, dtype, scope) inside seq scopes
    scale_host: List[Tuple[str, str]] = field(default_factory=list)  # R2


def scan_trace(closed_jaxpr) -> TraceFacts:
    """Single pass over every equation of a traced program, collecting the
    raw facts the rules judge.  Per-scope producer maps are built lazily —
    only scopes that contain checkpoint names pay for one."""
    facts = TraceFacts()
    prod_cache: Dict[int, Dict] = {}

    def prods_for(jaxpr):
        key = id(jaxpr)
        if key not in prod_cache:
            prod_cache[key] = df.producers(jaxpr)
        return prod_cache[key]

    for site in df.iter_sites(closed_jaxpr):
        eqn = site.eqn
        prim = eqn.primitive.name
        if prim == "device_put":
            kinds = df.device_put_kinds_of(eqn)
            for kind in kinds:
                if kind == hostmem.DEVICE_KIND:
                    facts.h2d += 1
                    if site.in_sequential_scope:
                        facts.h2d_hazards.append(site)
                else:
                    facts.d2h += 1
        elif prim == "name":
            nm = eqn.params.get("name", "")
            facts.names.add(nm)
            if nm.startswith(ofl.SCALE_NAME):
                pe = df.first_real_producer(site.jaxpr, eqn.invars[0],
                                            prods_for(site.jaxpr))
                if pe is not None and pe.primitive.name == "device_put":
                    kinds = df.device_put_kinds_of(pe)
                    if kinds and all(k != hostmem.DEVICE_KIND
                                     for k in kinds):
                        facts.scale_host.append((nm, site.scope))
            elif nm.startswith(ofl.OFF_NAME):
                dt = eqn.invars[0].aval.dtype.name
                if (site.in_sequential_scope
                        and dt.startswith(_INEXACT_WIRE_PREFIXES)):
                    facts.inexact_named.append((nm, dt, site.scope))
                # a capture pair: the name's input was produced, in this
                # same scope, by an explicit host-kind device_put — the
                # D2H half of one offload site
                pe = prods_for(site.jaxpr).get(eqn.invars[0])
                if pe is not None and pe.primitive.name == "device_put":
                    kinds = df.device_put_kinds_of(pe)
                    if kinds and all(k != hostmem.DEVICE_KIND
                                     for k in kinds):
                        facts.capture_pairs += 1
                        facts.paired_off_names.add(nm)
    return facts


# ---------------------------------------------------------------------------
# Rules over one activation trace (train-grad / prefill)
# ---------------------------------------------------------------------------


def _audit_act_trace(rep: AuditReport, closed_jaxpr, trace: str,
                     *, codec: str) -> TraceFacts:
    facts = scan_trace(closed_jaxpr)
    rep.counters[f"{trace}.d2h"] = facts.d2h
    rep.counters[f"{trace}.h2d"] = facts.h2d
    rep.counters[f"{trace}.offload_sites"] = facts.capture_pairs

    # R1: the trace's own capture pairs fix the expected transfer budget —
    # one D2H per tagged site, one H2D per replay.  Deriving the expectation
    # from the trace (not from plan math) keeps the rule exact under
    # alpha-quantization and reserve-last zeroing.
    if facts.d2h != facts.capture_pairs:
        rep.add(Finding(
            id="R1-d2h-count", rule="R1", trace=trace,
            message=(f"{facts.d2h} host-kind device_puts for "
                     f"{facts.capture_pairs} tagged offload sites "
                     "(expected exactly one D2H per site)")))
    if facts.h2d != facts.capture_pairs:
        rep.add(Finding(
            id="R1-h2d-count", rule="R1", trace=trace,
            message=(f"{facts.h2d} device-kind device_puts for "
                     f"{facts.capture_pairs} tagged offload sites "
                     "(expected exactly one H2D per replay)")))

    # R3: an H2D inside a scan/while/remat scope is consumed by that
    # scope's own execution — the reload cannot be hoisted ahead of the
    # backward that needs it, so the transfer time is fully exposed.
    for site in facts.h2d_hazards:
        rep.add(Finding(
            id="R3-overlap-hazard", rule="R3", trace=trace,
            scope=site.scope,
            message=("H2D reload issued inside a sequential scope — the "
                     "copy serializes into the issuing chunk's own "
                     "backward instead of overlapping it")))

    # R2: codec scales must stay device-side (the backward dequantizes
    # with them immediately; a host-resident scale adds a blocking reload
    # on the critical path and un-pairs the payload).
    for nm, scope in facts.scale_host:
        rep.add(Finding(
            id="R2-scale-placement", rule="R2", trace=trace, subject=nm,
            scope=scope,
            message=f"codec scale {nm} was placed in host memory "
                    "(scales must stay device-resident)"))

    # R5a: quantized payload ↔ scale pairing.
    if codec not in (None, "none"):
        for nm in sorted(facts.paired_off_names):
            if ofl.scale_name_for(nm) not in facts.names:
                rep.add(Finding(
                    id="R5-codec-pairing", rule="R5", trace=trace,
                    subject=nm,
                    message=(f"quantized payload {nm} has no reachable "
                             f"{ofl.scale_name_for(nm)} — the backward "
                             "cannot dequantize it")))

    # R5b: inexact payloads named inside remat/scan scopes (the PR 7 trap).
    for nm, dt, scope in facts.inexact_named:
        rep.add(Finding(
            id="R5-inexact-residual", rule="R5", trace=trace, subject=nm,
            scope=scope,
            message=(f"residual {nm} is named as {dt} inside a remat/scan "
                     "scope — quantized payloads must cross remat "
                     "boundaries in an exact integer container")))
    return facts


# ---------------------------------------------------------------------------
# R4: masked pipeline state on the pp>1 prefill
# ---------------------------------------------------------------------------


def _audit_state_mask(rep: AuditReport, closed_jaxpr, n_state: int) -> None:
    rep.counters["prefill.state_leaves"] = n_state
    for i in range(n_state):
        frames, scope, var = df.outvar_frames(closed_jaxpr, i)
        prods = df.producers(scope)
        pe = df.first_real_producer(scope, var, prods)
        if pe is None:
            # never written in the traced step — nothing to clobber
            continue
        if pe.primitive.name != "select_n":
            rep.add(Finding(
                id="R4-unmasked-state", rule="R4", trace="prefill",
                subject=f"state[{i}]",
                message=(f"pipeline-state output {i} is written by "
                         f"`{pe.primitive.name}` with no tick-validity "
                         "select — warmup/drain ticks clobber it "
                         "(the pp>1 KV-cache corruption class)")))
            continue
        pred_prims = df.cross_scope_ancestor_prims(
            frames, scope, pe.invars[0])
        if "axis_index" not in pred_prims:
            rep.add(Finding(
                id="R4-mask-predicate", rule="R4", trace="prefill",
                subject=f"state[{i}]",
                message=(f"pipeline-state output {i} is select-guarded, "
                         "but the predicate does not derive from the "
                         "stage index (axis_index) — it cannot encode "
                         "tick validity")))


# ---------------------------------------------------------------------------
# Moments channel (R1/R2 on the optimizer update + init)
# ---------------------------------------------------------------------------


def _audit_moments(rep: AuditReport, cell, pstruct) -> None:
    from repro.optim import adamw
    from repro.runtime import memledger as ml

    plan = cell.plan
    opt_dtype = (jnp.bfloat16 if plan.opt_dtype == "bfloat16"
                 else jnp.float32)
    moments_dtype = getattr(plan, "moments_dtype", "none")
    state = jax.eval_shape(
        lambda p: adamw.init_state(p, opt_dtype, offload_moments=True,
                                   moments_dtype=moments_dtype), pstruct)

    def opt_fn(p, g, s):
        return adamw.apply_update(p, g, s, lr=1e-3, offload_moments=True,
                                  moments_dtype=moments_dtype)

    cjx = jax.make_jaxpr(opt_fn)(pstruct, pstruct, state)
    rep.traces.append("opt-update")
    facts = scan_trace(cjx)
    n_leaves = (len(jax.tree_util.tree_leaves(state.m))
                + len(jax.tree_util.tree_leaves(state.v)))
    rep.counters["opt-update.d2h"] = facts.d2h
    rep.counters["opt-update.h2d"] = facts.h2d
    rep.counters["opt-update.moment_leaves"] = n_leaves

    # one H2D into the staged update and one D2H back per host leaf — the
    # one-copy contract (DESIGN.md §11)
    if facts.h2d != n_leaves or facts.d2h != n_leaves:
        rep.add(Finding(
            id="R1-moment-copy-count", rule="R1", trace="opt-update",
            message=(f"moments update shows {facts.h2d} H2D / {facts.d2h} "
                     f"D2H for {n_leaves} host moment leaves (expected "
                     "exactly one each per leaf)")))
    for site in facts.h2d_hazards:
        rep.add(Finding(
            id="R3-overlap-hazard", rule="R3", trace="opt-update",
            scope=site.scope,
            message="moment H2D issued inside a sequential scope"))

    init_dev = ml.init_moment_device_bytes(
        pstruct, opt_dtype, offload_moments=True,
        moments_dtype=moments_dtype)
    rep.counters["opt-init.device_bytes"] = init_dev
    if init_dev:
        rep.add(Finding(
            id="R2-moment-init-device", rule="R2", trace="opt-init",
            message=(f"{init_dev} bytes of moment zeros materialize in "
                     "device memory at init (offloaded moments must be "
                     "born host-resident)")))


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def audit_cell(cell, *, data_size: int, model_size: int,
               name: str = "") -> AuditReport:
    """Audit one resolved plan cell.  Traces the cell's real step functions
    (the same builders CI measures and serves with) over struct inputs and
    applies every applicable rule.  Returns the report; never raises on a
    finding — tracing errors are captured in ``report.error``."""
    from repro.launch.mesh import make_mesh
    from repro.parallel import runner
    from repro.parallel import specs as SP
    from repro.runtime import memledger as ml

    plan = cell.plan
    rep = AuditReport(cell=name or cell.shape.name, pp=plan.pp,
                      prefetch=plan.prefetch)
    train = cell.shape.kind == "train"
    assert plan.grad_accum == 1, "audit_cell needs grad_accum == 1 (the " \
        "scan walk would fold the accumulation factor into the counts)"

    g_stage = SP.stage_struct(cell.mdef, plan.pp, cell.data_size, cell.dtype)
    gl = SP.globals_struct(cell.mdef, cell.dtype)
    bstruct, _ = runner.batch_struct(cell)

    if train:
        fn = ml.step_fn(cell, data_size=data_size, model_size=model_size,
                        with_grad=True)
        cjx = jax.make_jaxpr(fn)(g_stage, gl, bstruct)
        rep.traces.append("train-grad")
        _audit_act_trace(rep, cjx, "train-grad", codec=plan.offload_dtype)

    if (not train) or plan.pp > 1:
        mesh = make_mesh((data_size, model_size), ("data", "model"))
        pre_fn, sstruct, _ = runner.make_prefill_step(cell, mesh)
        pstruct = {"stages": g_stage, "globals": gl}
        cjx_pre = jax.make_jaxpr(pre_fn)(pstruct, bstruct)
        rep.traces.append("prefill")
        if not train:
            # serve cells must show a transfer-free prefill (offload is
            # rejected for them at resolve time; this proves it held)
            _audit_act_trace(rep, cjx_pre, "prefill", codec="none")
        if plan.pp > 1:
            _audit_state_mask(rep, cjx_pre,
                              len(jax.tree_util.tree_leaves(sstruct)))

    if train and plan.offload_moments:
        _audit_moments(rep, cell, g_stage)
    return rep


DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def resolve_gate_cell(gate: dict, *, pp: int = None, prefetch: str = None):
    """Resolve one budgets.json *train* gate to the cell the memory-gate
    measures (mirrors benchmarks/memgate.run_gate), with optional pp /
    prefetch overrides for the audit sweep.  Returns (cell, data_size,
    model_size)."""
    from repro.configs.base import ShapeConfig, get_config
    from repro.models.model_zoo import build_model
    from repro.parallel import runner

    cfg = get_config(gate["arch"])
    if gate.get("reduced", True):
        cfg = cfg.reduced()
    mdef = build_model(cfg)
    shape = ShapeConfig(gate["name"], gate["seq"], gate["batch"], "train")
    doc_lens = None
    if gate.get("doc_lens"):
        from repro.data import pipeline as dpipe

        doc_lens = [int(x) for x in
                    dpipe.sample_doc_lengths(**gate["doc_lens"])]
    use_pp = gate["pp"] if pp is None else pp
    overrides = dict(pp=use_pp, dp=gate["data_size"] // use_pp,
                     n_chunks=gate["n_chunks"], grad_accum=1,
                     partition="length", offload=True,
                     msp=gate.get("msp", False),
                     offload_moments=bool(gate.get("offload_moments",
                                                   False)),
                     opt_dtype=gate.get("opt_dtype", "float32"),
                     offload_dtype=gate.get("offload_dtype", "none"),
                     moments_dtype=gate.get("moments_dtype", "none"))
    if prefetch is not None:
        overrides["prefetch"] = prefetch
    cell = runner.resolve_cell(
        mdef, shape, data_size=gate["data_size"],
        model_size=gate["model_size"], overrides=overrides,
        doc_lens=doc_lens)
    cell = dataclasses.replace(
        cell, dtype=DTYPES[gate.get("dtype", "bfloat16")])
    return cell, gate["data_size"], gate["model_size"]


def resolve_serve_gate_cell(gate: dict):
    """Resolve a budgets.json serve gate to the engine's prefill cell
    (mirrors launch/serve.ServeEngine's resolution — the decode cell has
    its own offload-rejection asserts at resolve time)."""
    from repro.configs.base import ShapeConfig, get_config
    from repro.models.model_zoo import build_model
    from repro.parallel import runner

    cfg = get_config(gate["arch"])
    if gate.get("reduced", True):
        cfg = cfg.reduced()
    mdef = build_model(cfg)
    data_size, model_size = gate["data_size"], gate["model_size"]
    kg = gate["slots"] * data_size
    pre_shape = ShapeConfig("engine_prefill", gate["s_bucket"], kg,
                            "prefill")
    cell = runner.resolve_cell(
        mdef, pre_shape, data_size=data_size, model_size=model_size,
        overrides=dict(n_chunks=max(1, gate["s_bucket"] // 64),
                       offload=False, remat="none", pp=1, dp=data_size))
    return cell, data_size, model_size


def audit_gate(gate: dict, *, pp: int = None,
               prefetch: str = None) -> AuditReport:
    """Audit one budgets.json gate (train or serve)."""
    label = gate["name"] + (f"@pp{pp}" if pp is not None else "")
    try:
        if gate.get("kind") == "serve":
            cell, ds, ms = resolve_serve_gate_cell(gate)
        else:
            cell, ds, ms = resolve_gate_cell(gate, pp=pp, prefetch=prefetch)
        return audit_cell(cell, data_size=ds, model_size=ms, name=label)
    except Exception as e:  # noqa: BLE001 - a broken trace IS a finding
        rep = AuditReport(cell=label, pp=pp or gate.get("pp", 1))
        rep.error = f"{type(e).__name__}: {e}"
        return rep

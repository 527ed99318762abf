"""Memory ledger: measured per-tick activation + optimizer-state accounting
for the executed offload paths (DESIGN.md §10/§11).

Measurement channels, all taken from the *real* program:

1. **Tagged-byte accounting** — every pipeline tick tags its Type-1
   activations with tick-qualified checkpoint names (``act_off@t3`` /
   ``act_keep@t3``, runner.chunk_tag).  ``tagged_bytes_from_jaxpr`` walks
   the traced jaxpr of the loss (through jit / shard_map / remat / scan,
   multiplying by scan trip counts) and sums the exact aval bytes behind
   each name.  Shapes are static facts of the executed program, so this is
   exact per-device accounting — not an estimate.

2. **Runtime tick probes** — ``tick_probe`` is a custom_vjp identity the
   runner threads onto the compute path; its fwd/bwd rules fire host
   callbacks recording wall-clock per tick, so the ledger can verify that
   every tick's forward AND backward actually executed, plus coarse
   per-phase wall time.  The callbacks are unordered (ordered effects are
   not supported under shard_map), so cross-tick ordering is telemetry,
   not a contract.  On CPU the host copies are folded into device memory
   by XLA, so *exposed transfer time* is reported as the step-time delta
   against an offload-off run (see ``measure``) — on a TPU backend the
   same probes bracket the real async copies.

3. **Moments channel** (PR 4) — when the plan offloads optimizer state,
   ``apply_update`` names every host-resident AdamW moment leaf
   (``opt_m@<i>`` / ``opt_v@<i>``, optim/adamw.py) and stages exactly one
   H2D per leaf into the device update.  ``moment_bytes_from_jaxpr`` walks
   the traced update for those names, ``device_put_kinds`` counts the
   explicit H2D/D2H copies per memory kind, and ``update_probe`` is the
   update-phase runtime-evidence hook.  The measured numbers must match
   the cost model's closed form (``costmodel.moment_bytes_per_param``) and
   the one-H2D-per-leaf contract (tests/test_opt_offload.py).

4. **H2D channel** (PR 5, DESIGN.md §12) — ``price_h2d`` replays the
   backward reload lane over the measured per-tick off-bytes and the
   measured backward windows (bwd probe wall clocks), under the plan's
   ``prefetch`` placement: "ahead" exposes only the reload time that
   overflows the next tick's backward window, "sync" (autodiff placement)
   exposes every reload in full.  Per-tick ``h2d_stall_s`` CSV column plus
   ``h2d_exposed_s``/``prefetch_ahead`` summary rows; the memgate's
   prefetch ablation gates the strict ahead-vs-sync reduction.

0. **Pool channel** (Type-0, DESIGN.md §16) — serving has no activation
   recurrence; its device-memory story is the paged KV pool
   (``runtime/kvpool.py``).  ``PoolChannel`` records the measured per-rank
   bytes of the real pool arrays against the cost model's closed form
   (``costmodel.kv_pool_bytes``), plus the host allocator's peak / lifetime
   block counts as the recycling evidence.  CI's serve half of the
   memory-gate holds the measured/predicted ratio to the same 1.1x honesty
   band the train channels get.

5. **Compressed channel** (DESIGN.md §14) — when the plan sets
   ``offload_dtype``, the traced ``act_off@…`` names carry the 1-byte
   codec payload and ``act_scale@…`` names the device-resident per-row
   fp32 scales.  The ledger keeps ``off_bytes`` in *raw* device units
   (what the §5.2 recurrence drains — elems × the activation itemsize)
   and reports the honest host/wire side separately as
   ``off_wire_bytes`` plus ``scale_bytes``; ``price_h2d`` prices the
   reload lane over the wire form.

The ledger then replays the §5.2 recurrence M_t = M_{t-1} + A_t −
α_{t-1}A_{t-1} over the measured per-tick bytes; CI's memory-gate compares
that measured peak — plus the device-resident moments term — against the
simulator's prediction from the analytic cost model
(core/simulate.spmd_tick_peak over costmodel.chunk_act_bytes with
row-quantized alphas, plus costmodel.moment_bytes_per_param for the
opt-state gates).
"""
from __future__ import annotations

import csv
import functools
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import offload as ofl

from jax.experimental import io_callback


# ---------------------------------------------------------------------------
# Runtime tick probes
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def tick_probe(x, ledger, tick):
    """Identity on the compute path; records (phase, tick, wall) per device
    into `ledger` when the program actually executes the tick."""
    return x


def _probe_fwd(x, ledger, tick):
    if io_callback is not None:
        io_callback(lambda: ledger.record_runtime("fwd", tick), None,
                    ordered=False)
    return x, None


def _probe_bwd(ledger, tick, res, g):
    if io_callback is not None:
        io_callback(lambda: ledger.record_runtime("bwd", tick), None,
                    ordered=False)
    return (g,)


tick_probe.defvjp(_probe_fwd, _probe_bwd)


# ---------------------------------------------------------------------------
# Jaxpr walk: exact tagged bytes per tick
# ---------------------------------------------------------------------------


# The traversal itself lives in analysis/dataflow.py (DESIGN.md §17) — one
# shared walker serves the ledger's byte/copy accounting and the static
# contract auditor.  The underscore aliases are kept because the honesty
# tests reach for them when sizing expected buffers.
from repro.analysis import dataflow as _df  # noqa: E402

_DTYPE_BITS = _df.DTYPE_BITS
_aval_elems = _df.aval_elems
_aval_bytes = _df.aval_bytes
_sub_jaxprs = _df.sub_jaxprs


def tagged_bytes_from_jaxpr(closed_jaxpr) -> Dict[str, Dict[str, int]]:
    """{suffix: {"off": bytes, "off_elems": n, "keep": bytes,
    "scale": bytes}} from a traced (forward) jaxpr.  Walk the
    *forward-only* trace — under grad the remat'd backward repeats the
    name equations and would double-count.

    "off" is the bytes of the named host rows *as traced* — under a
    compressed plan (DESIGN.md §14) that is the wire/host payload;
    "off_elems" is the element count behind the same names, so callers can
    reconstruct the raw device bytes the §5.2 recurrence drains (elems ×
    the activation itemsize) independent of the transport dtype.  "scale"
    is the device-resident per-row codec scales (``act_scale@…``), zero on
    uncompressed plans."""
    raw, elems = _df.walk_named(closed_jaxpr)
    per: Dict[str, Dict[str, int]] = {}
    bases = ((ofl.OFF_NAME, "off"), (ofl.KEEP_NAME, "keep"),
             (ofl.SCALE_NAME, "scale"))
    for nm, nbytes in raw.items():
        for base, kind in bases:
            if nm.startswith(base):
                suffix = nm[len(base):]
                per.setdefault(suffix, {"off": 0, "off_elems": 0,
                                        "keep": 0, "scale": 0})
                per[suffix][kind] += nbytes
                if kind == "off":
                    per[suffix]["off_elems"] += elems.get(nm, 0)
                break
    return per


# ---------------------------------------------------------------------------
# Moments channel: optimizer-state bytes + explicit-copy accounting
# ---------------------------------------------------------------------------


def moment_bytes_from_jaxpr(closed_jaxpr) -> Dict[str, object]:
    """{"m": bytes, "v": bytes, "leaves": {name: bytes}} from the traced
    optimizer update: the aval bytes behind every leaf-qualified
    ``opt_m@<i>`` / ``opt_v@<i>`` checkpoint name (optim/adamw.py).  Like
    the activation walk, shapes are static facts of the executed program —
    exact accounting, not an estimate."""
    from repro.optim.adamw import OPT_M_NAME, OPT_V_NAME

    raw, _ = _df.walk_named(closed_jaxpr)
    leaves = {nm: b for nm, b in raw.items()
              if nm.startswith(OPT_M_NAME + "@")
              or nm.startswith(OPT_V_NAME + "@")}
    m_b = sum(b for nm, b in leaves.items() if nm.startswith(OPT_M_NAME))
    v_b = sum(b for nm, b in leaves.items() if nm.startswith(OPT_V_NAME))
    # compressed residency (§14): the per-row fp32 scales are host leaves
    # of their own, named opt_{m,v}_scale@<i> — deliberately NOT under the
    # opt_m@/opt_v@ prefixes, so m/v stay payload-only sums
    scales = {nm: b for nm, b in raw.items()
              if nm.startswith(OPT_M_NAME + "_scale@")
              or nm.startswith(OPT_V_NAME + "_scale@")}
    return {"m": m_b, "v": v_b, "scale": sum(scales.values()),
            "leaves": leaves, "scale_leaves": scales}


def device_put_kinds(closed_jaxpr) -> Dict[str, int]:
    """{memory_kind: count} of explicit ``device_put`` equations in a
    traced program — ``counts["device"]`` is the H2D copies, host kinds
    are the D2H side.  The explicit moments path must show exactly one H2D
    per moment leaf per step (the one-copy contract, DESIGN.md §11).
    Equations are counted once regardless of scan nesting (per-step
    contract accounting, not per-execution)."""
    return _df.walk_device_puts(closed_jaxpr)


def init_moment_device_bytes(params, opt_dtype, *, offload_moments: bool,
                             moments_dtype: str = "none") -> int:
    """Bytes of moment zeros that end up resident in *device* memory space
    after ``adamw.init_state``, from the traced init: creation equations
    (``broadcast_in_dim`` — jnp.zeros) allocate in the default device
    space; creations that are immediately host-placed (hostmem.host_zeros
    emits zeros → host-kind device_put under tracing, and a numpy buffer →
    host placement eagerly) are netted out.  The step-0 peak regression
    (tests/test_opt_offload.py) asserts this is 0 when moments are
    offloaded."""
    from repro.optim import adamw
    from repro.runtime import hostmem

    cjx = jax.make_jaxpr(lambda ps: adamw.init_state(
        ps, opt_dtype, offload_moments=offload_moments,
        moments_dtype=moments_dtype))(params)
    created: Dict[object, int] = {}
    dev = 0
    for eqn in cjx.jaxpr.eqns:
        if eqn.primitive.name == "broadcast_in_dim":
            nbytes = sum(_aval_bytes(v.aval) for v in eqn.outvars)
            dev += nbytes
            for v in eqn.outvars:
                created[v] = _aval_bytes(v.aval)
        elif eqn.primitive.name == "device_put":
            kinds = _df.device_put_kinds_of(eqn)
            if kinds and all(k != hostmem.DEVICE_KIND for k in kinds):
                for v in eqn.invars:
                    dev -= created.pop(v, 0)
    return dev


@dataclass
class MomentChannel:
    """Measured optimizer-state residency for one cell's update step."""

    offloaded: bool
    opt_dtype: str
    host_kind: Optional[str]
    m_bytes: int                   # real state buffers (Σ leaf nbytes)
    v_bytes: int
    n_leaves: int                  # leaves per moment tree
    max_pair_bytes: int            # largest single-leaf m+v pair
    named_bytes: int               # jaxpr walk over opt_m@/opt_v@ names
    h2d_count: int                 # explicit copies into device space
    d2h_count: int                 # explicit copies into host kinds
    init_dev_bytes: int            # device-materialized zeros at init

    @property
    def total_bytes(self) -> int:
        return self.m_bytes + self.v_bytes

    @property
    def host_bytes(self) -> int:
        """Bytes resident in host memory between steps."""
        return self.total_bytes if self.offloaded else 0

    @property
    def dev_resident_bytes(self) -> int:
        """Bytes resident in device memory through the whole step."""
        return 0 if self.offloaded else self.total_bytes

    @property
    def dev_peak_bytes(self) -> int:
        """Device-memory contribution at the step peak: the full set when
        moments live on device; the per-leaf staging pair when offloaded
        (the one-H2D-per-leaf contract bounds what the update stages —
        actual concurrency is the hardware scheduler's, DESIGN.md §11)."""
        return self.max_pair_bytes if self.offloaded else self.total_bytes


@dataclass
class PoolChannel:
    """Measured paged-KV pool residency for one serve engine (Type-0).

    ``measured_bytes`` is the per-rank device footprint of the real pool
    arrays; ``predicted_bytes`` the cost model's closed form
    (``costmodel.kv_pool_bytes``).  ``peak_blocks``/``total_blocks`` come
    from the host allocator over a served trace: lifetime allocations
    exceeding the physical block count while the peak stays within it is
    the evidence that freed blocks are actually recycled."""

    n_blocks: int
    block_tokens: int
    n_layers: int
    measured_bytes: int
    predicted_bytes: int
    peak_blocks: int = 0
    total_blocks: int = 0

    @property
    def ratio(self) -> float:
        return self.measured_bytes / max(self.predicted_bytes, 1)


# ---------------------------------------------------------------------------
# The ledger
# ---------------------------------------------------------------------------


@dataclass
class TickRow:
    tick: int
    chunk: int            # chunk fed at this tick (last chunk on drain ticks)
    valid: bool           # False for the SPMD drain ticks (masked compute)
    alpha: float
    mat_bytes: int        # tagged bytes materialized this tick (off + keep)
    off_bytes: int        # ... of which routed to host, in RAW device bytes
    resident: int = 0     # §5.2 recurrence replay, after materialization
    fwd_t: Optional[float] = None   # runtime probe wall-clock (first sample)
    bwd_t: Optional[float] = None
    h2d_stall_s: Optional[float] = None  # exposed reload time (price_h2d)
    # compressed channel (DESIGN.md §14): the bytes that actually cross the
    # wire / sit in host memory (codec payload; None = raw, == off_bytes)
    # and the device-resident per-row scale bytes that ride the keep set.
    # off_bytes deliberately stays in raw device units — the §5.2 recurrence
    # drains full activation rows from device memory regardless of how few
    # bytes their host copy takes.
    off_wire_bytes: Optional[int] = None
    scale_bytes: int = 0


@dataclass
class MemLedger:
    """Measured per-tick ledger for one (cell, step) execution."""

    alphas: Tuple[float, ...] = ()
    ticks: List[TickRow] = field(default_factory=list)
    runtime_events: List[Tuple[str, int, float]] = field(default_factory=list)
    exposed_transfer_s: Optional[float] = None  # offload-on minus offload-off
    step_time_s: Optional[float] = None
    moments: Optional[MomentChannel] = None     # opt-state channel (§11)
    pool: Optional[PoolChannel] = None          # Type-0 KV pool (§16)
    opt_time_s: Optional[float] = None          # measured update wall time
    prefetch: str = "ahead"                     # plan's reload placement
    h2d_exposed_s: Optional[float] = None       # Σ per-tick h2d_stall_s
    offload_codec: str = "none"                 # act-channel codec (§14)

    # -- runtime channel ----------------------------------------------------
    def record_runtime(self, phase: str, tick: int) -> None:
        self.runtime_events.append((phase, int(tick), time.perf_counter()))

    # -- byte channel -------------------------------------------------------
    def load_tagged(self, per_suffix: Dict[str, Dict[str, int]],
                    events, pp: int, alphas,
                    act_itemsize: Optional[int] = None) -> None:
        """Fold jaxpr-measured per-tick bytes + the feed schedule into tick
        rows and replay the §5.2 recurrence.

        ``act_itemsize`` converts the walked off-channel element counts
        back to raw device bytes; under a compressed plan the traced off
        names carry the 1-byte payload, so ``off_bytes`` (what the device
        recurrence drains) and ``off_wire_bytes`` (what the host/link
        carries) diverge.  Without it (or without element counts in
        ``per_suffix``) the traced bytes are used for both — exact for
        uncompressed plans."""
        self.alphas = tuple(float(a) for a in alphas)
        n_ticks = len(events) + pp - 1
        rows = []
        for t in range(n_ticks):
            e = min(t, len(events) - 1)
            chunk = events[e][0]
            key = f"@t{t}" if pp > 1 else f"@c{chunk}"
            got = per_suffix.get(key, {})
            wire = got.get("off", 0)
            n_el = got.get("off_elems")
            raw_off = (n_el * act_itemsize
                       if act_itemsize is not None and n_el is not None
                       else wire)
            scale = got.get("scale", 0)
            rows.append(TickRow(
                tick=t, chunk=chunk, valid=t < len(events),
                alpha=self.alphas[chunk],
                mat_bytes=raw_off + got.get("keep", 0) + scale,
                off_bytes=raw_off,
                off_wire_bytes=wire,
                scale_bytes=scale))
        # M_t = M_{t-1} + A_t − off_{t-1}: the previous tick's offload
        # drains while tick t computes (§5.2, tick granularity).  Only the
        # raw activation rows drain — the codec scales stay device-resident
        # with the keep set until the backward consumes them.
        m = 0
        prev_off = 0
        for r in rows:
            m += r.mat_bytes
            r.resident = m
            m -= prev_off
            prev_off = r.off_bytes
        self.ticks = rows
        self._fold_runtime()

    def _fold_runtime(self) -> None:
        firsts: Dict[Tuple[str, int], float] = {}
        for phase, tick, t in self.runtime_events:
            key = (phase, tick)
            firsts[key] = min(firsts.get(key, t), t)
        for r in self.ticks:
            r.fwd_t = firsts.get(("fwd", r.tick))
            r.bwd_t = firsts.get(("bwd", r.tick))

    # -- h2d channel --------------------------------------------------------
    def price_h2d(self, *, bw: float, prefetch: Optional[str] = None) -> float:
        """Exposed-H2D replay over the *measured* per-tick bytes and
        backward windows (DESIGN.md §12): the per-tick reload volume is the
        ledger's measured ``off_bytes``, the hiding window is the measured
        backward duration of the next tick (from the bwd probe wall clocks
        — the backward runs ticks in reverse, so tick t's reload can hide
        under tick t+1's backward, whose duration is
        ``bwd_t[t] − bwd_t[t+1]``), and the transfer is priced at `bw`.

        prefetch="ahead" exposes only the part of each reload that does not
        fit its window; "sync" exposes every reload in full (the autodiff
        placement serializes it into its own backward).  Passing an
        explicit `prefetch` prices the counterfactual placement *without*
        touching the ledger's stored per-tick/summary fields — those always
        reflect ``self.prefetch``, the mode the step actually ran.  Like
        the exposed-transfer channel, this is the honest CPU-runnable form
        of the measurement (§9): bytes and windows are measured, the link
        bandwidth is the cost model's — real async-copy overlap is a TPU
        validation item (ROADMAP)."""
        mode = prefetch if prefetch is not None else self.prefetch
        rows = self.ticks
        total = 0.0
        for i, r in enumerate(rows):
            # the reload lane carries the host copy: the codec payload
            # under a compressed plan (off_wire_bytes), raw rows otherwise
            vol = (r.off_wire_bytes if r.off_wire_bytes is not None
                   else r.off_bytes)
            rld = vol / bw if bw else 0.0
            if mode == "sync":
                stall = rld
            else:
                window = 0.0
                if (i + 1 < len(rows) and r.bwd_t is not None
                        and rows[i + 1].bwd_t is not None):
                    window = max(0.0, r.bwd_t - rows[i + 1].bwd_t)
                stall = max(0.0, rld - window)
            if mode == self.prefetch:
                r.h2d_stall_s = stall
            total += stall
        if mode == self.prefetch:
            self.h2d_exposed_s = total
        return total

    # -- derived ------------------------------------------------------------
    @property
    def peak_bytes(self) -> int:
        return max((r.resident for r in self.ticks), default=0)

    @property
    def host_bytes(self) -> int:
        """Total bytes placed in host memory across the forward — the wire
        form when the act channel is compressed (§14)."""
        return sum((r.off_wire_bytes if r.off_wire_bytes is not None
                    else r.off_bytes) for r in self.ticks)

    @property
    def off_bytes_total(self) -> int:
        """Raw device bytes the offload channel drained (codec-independent)."""
        return sum(r.off_bytes for r in self.ticks)

    @property
    def off_wire_bytes_total(self) -> int:
        return sum((r.off_wire_bytes if r.off_wire_bytes is not None
                    else r.off_bytes) for r in self.ticks)

    @property
    def scale_bytes_total(self) -> int:
        """Device-resident codec scale bytes across the forward (§14)."""
        return sum(r.scale_bytes for r in self.ticks)

    @property
    def combined_peak_bytes(self) -> int:
        """Device peak with the optimizer-state term folded in: the §5.2
        activation peak plus the moments' device contribution (full set
        when device-resident; the per-leaf staging pair when offloaded).
        Equals ``peak_bytes`` when no moments channel was measured."""
        mom = self.moments.dev_peak_bytes if self.moments else 0
        return self.peak_bytes + mom

    def runtime_coverage_ok(self, *, require_bwd: bool = True,
                            require_update: Optional[bool] = None) -> bool:
        """Every tick produced forward (and backward) probe samples — the
        evidence that each tick's fwd and bwd actually executed — and,
        when the moments channel is measured (require_update defaults to
        that), at least one update-phase probe fired.  Exact cross-tick
        ordering is deliberately NOT asserted: the probes are unordered
        host callbacks and may drain late relative to the XLA schedule
        (DESIGN.md §10)."""
        if require_update is None:
            require_update = self.moments is not None
        ok = all(r.fwd_t is not None for r in self.ticks) and (
            not require_bwd or all(r.bwd_t is not None for r in self.ticks))
        if require_update:
            ok = ok and any(p == "upd" for p, _, _ in self.runtime_events)
        return ok

    def to_csv(self, path: str) -> None:
        mom = self.moments
        with open(path, "w", newline="") as f:
            w = csv.writer(f)
            w.writerow(["tick", "chunk", "valid", "alpha", "mat_bytes",
                        "off_bytes", "off_wire_bytes", "scale_bytes",
                        "resident_bytes", "moments_dev_bytes",
                        "h2d_stall_s", "fwd_t", "bwd_t"])
            for r in self.ticks:
                w.writerow([r.tick, r.chunk, int(r.valid),
                            f"{r.alpha:.4f}", r.mat_bytes, r.off_bytes,
                            ("" if r.off_wire_bytes is None
                             else r.off_wire_bytes),
                            r.scale_bytes,
                            r.resident,
                            "" if mom is None else mom.dev_resident_bytes,
                            ("" if r.h2d_stall_s is None
                             else f"{r.h2d_stall_s:.9f}"),
                            "" if r.fwd_t is None else f"{r.fwd_t:.6f}",
                            "" if r.bwd_t is None else f"{r.bwd_t:.6f}"])
            w.writerow([])
            w.writerow(["peak_bytes", self.peak_bytes])
            w.writerow(["host_bytes", self.host_bytes])
            w.writerow(["offload_codec", self.offload_codec])
            w.writerow(["off_bytes_total", self.off_bytes_total])
            w.writerow(["off_wire_bytes_total", self.off_wire_bytes_total])
            w.writerow(["scale_bytes_total", self.scale_bytes_total])
            w.writerow(["prefetch_ahead", int(self.prefetch == "ahead")])
            if self.h2d_exposed_s is not None:
                w.writerow(["h2d_exposed_s", f"{self.h2d_exposed_s:.9f}"])
            if self.step_time_s is not None:
                w.writerow(["step_time_s", f"{self.step_time_s:.6f}"])
            if self.exposed_transfer_s is not None:
                w.writerow(["exposed_transfer_s",
                            f"{self.exposed_transfer_s:.6f}"])
            if mom is not None:
                w.writerow(["moments_offloaded", int(mom.offloaded)])
                w.writerow(["moments_total_bytes", mom.total_bytes])
                w.writerow(["moments_host_bytes", mom.host_bytes])
                w.writerow(["moments_dev_peak_bytes", mom.dev_peak_bytes])
                w.writerow(["moments_named_bytes", mom.named_bytes])
                w.writerow(["moments_h2d_per_step", mom.h2d_count])
                w.writerow(["combined_peak_bytes", self.combined_peak_bytes])
                if self.opt_time_s is not None:
                    w.writerow(["opt_time_s", f"{self.opt_time_s:.6f}"])
            if self.pool is not None:
                w.writerow(["kv_pool_bytes", self.pool.measured_bytes])
                w.writerow(["kv_pool_predicted_bytes",
                            self.pool.predicted_bytes])
                w.writerow(["kv_pool_blocks", self.pool.n_blocks])
                w.writerow(["kv_pool_block_tokens", self.pool.block_tokens])
                w.writerow(["kv_pool_layers", self.pool.n_layers])
                w.writerow(["kv_pool_peak_blocks", self.pool.peak_blocks])
                w.writerow(["kv_pool_total_blocks", self.pool.total_blocks])


def read_csv(path: str) -> Dict[str, object]:
    """Round-trip reader for ``MemLedger.to_csv``: returns
    {"rows": [per-tick dicts], "summary": {key: number}}.  The per-tick
    section ends at the blank line; summary lines are key/value pairs.
    Used by the CSV round-trip tests and by offline analysis of the CI
    memledger artifacts."""
    rows: List[Dict[str, object]] = []
    summary: Dict[str, float] = {}
    with open(path, newline="") as f:
        r = csv.reader(f)
        header = next(r)
        in_rows = True
        for line in r:
            if not line:
                in_rows = False
                continue
            if in_rows:
                row: Dict[str, object] = {}
                for k, val in zip(header, line):
                    if val == "":
                        row[k] = None
                    elif k == "alpha" or k.endswith("_t") or k.endswith("_s"):
                        row[k] = float(val)
                    else:
                        row[k] = int(val)
                rows.append(row)
            else:
                key, val = line[0], line[1]
                # try-int / try-float / else-string: summary values are
                # mostly numeric, but e.g. offload_codec is a plain string
                try:
                    summary[key] = int(val)
                except ValueError:
                    try:
                        summary[key] = float(val)
                    except ValueError:
                        summary[key] = val
    return {"rows": rows, "summary": summary}


def update_probe(ledger):
    """Identity hook for ``adamw.apply_update(probe=...)``: fires an
    unordered host callback when the update phase actually executes — the
    moments-channel analogue of ``tick_probe``'s fwd/bwd evidence."""
    def hook(step):
        if io_callback is not None:
            io_callback(lambda: ledger.record_runtime("upd", 0), None,
                        ordered=False)
        return step
    return hook


# ---------------------------------------------------------------------------
# Measured run driver (CPU-runnable; the memory-gate entry point)
# ---------------------------------------------------------------------------


def _drain_callbacks() -> None:
    """Wait for all pending host callbacks (the unordered tick probes) —
    jax.block_until_ready only waits on array outputs."""
    barrier = getattr(jax, "effects_barrier", None)
    if barrier is not None:
        barrier()


def step_fn(cell, *, data_size: int, model_size: int, ledger=None,
            with_grad: bool = True):
    """Just the shard_map'd step function of ``build_step`` — no argument
    arrays are created, so the static auditor (analysis/audit.py) can
    ``jax.make_jaxpr`` it over ShapeDtypeStructs without allocating."""
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.parallel.runner import (_in_specs_for_params, batch_struct,
                                       run_pipeline, shard_map)

    mesh = make_mesh((data_size, model_size), ("data", "model"))
    pspecs = _in_specs_for_params(cell)
    _, bspecs = batch_struct(cell)

    def body(stage_p, g, b):
        ctx = cell.ctx()
        stage_p = jax.tree_util.tree_map(
            lambda a: a.reshape(a.shape[1:]), stage_p)
        tok = b["tokens"].reshape(b["tokens"].shape[2:])
        lab = b["labels"].reshape(b["labels"].shape[2:])
        ds = (b["doc_start"].reshape(b["doc_start"].shape[2:])
              if "doc_start" in b else None)

        def loss(stage_p, g):
            out = run_pipeline(cell, ctx, stage_p, g, tok, lab,
                               None, with_loss=True, ledger=ledger,
                               doc_start=ds)
            num = ctx.psum_loss_all(out["loss"])
            den = ctx.psum_loss_all(out["denom"])
            return num / jnp.maximum(den, 1.0)

        if with_grad:
            l, gr = jax.value_and_grad(loss, argnums=(0, 1))(stage_p, g)
            gs = jax.tree_util.tree_map(lambda a: a[None],
                                        ctx.psum_grads(gr[0]))
            return l, gs
        return (loss(stage_p, g),
                jax.tree_util.tree_map(lambda a: a[None], stage_p))

    return shard_map(body, mesh,
                     in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
                     out_specs=(P(), pspecs["stages"]))


def build_step(cell, *, data_size: int, model_size: int, tokens=None,
               labels=None, doc_start=None, seed: int = 0, ledger=None,
               with_grad: bool = True):
    """The shared shard_map'd step scaffold over `cell`'s mesh layout:
    params stacked stage-major, the dp-major batch layout, and the
    pipeline loss (plus psum'd stage grads when `with_grad`), with
    optional ledger probes on the compute path.

    Returns ``(fn, (g_stage, globals, batch))``.  The measurement harness
    (``measure``), the memory-gate, and the honesty tests all build their
    executable here, so what the gate measures is by construction the same
    program the tests assert on — and ``step_fn`` is the same program the
    static auditor traces."""
    plan = cell.plan
    mdef, cfg = cell.mdef, cell.cfg
    key = jax.random.PRNGKey(seed)
    stages = [mdef.init_stage_params(key, s, plan.pp, cell.dtype)
              for s in range(plan.pp)]
    g_stage = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([ls[i % plan.pp] for i in range(data_size)]),
        *stages)
    gl = mdef.init_globals(key, cell.dtype)
    if cell.varlen and tokens is None:
        # deterministic packed batch from the cell's document histogram:
        # the same corpus the budget-cell / varlen tests run against
        from repro.data import pipeline as dpipe

        pb = dpipe.packed_batch_for(cell.doc_lens, cell.shape.seq_len,
                                    rows=cell.b_loc * plan.dp,
                                    vocab_size=cfg.vocab_size, seed=seed)
        tokens = jnp.asarray(pb.tokens)
        labels = jnp.asarray(pb.labels)
        doc_start = jnp.asarray(pb.doc_start)
    if tokens is None:
        tokens = jax.random.randint(
            key, (cell.b_loc * plan.dp, cell.shape.seq_len), 0,
            cfg.vocab_size)
    if labels is None:
        labels = jnp.roll(tokens, -1, axis=1)
    b_loc = tokens.shape[0] // plan.dp

    def lay(x):
        return jnp.stack([x[(i // plan.pp) * b_loc:
                            (i // plan.pp + 1) * b_loc]
                          for i in range(data_size)])[None]

    batch = {"tokens": lay(tokens), "labels": lay(labels)}
    if cell.varlen:
        assert doc_start is not None, "varlen cell needs a doc_start array"
        batch["doc_start"] = lay(jnp.asarray(doc_start))
    fn = step_fn(cell, data_size=data_size, model_size=model_size,
                 ledger=ledger, with_grad=with_grad)
    return fn, (g_stage, gl, batch)


def predicted_spmd_peak(cell) -> float:
    """The simulator's predicted §5.2 peak for `cell`'s executed form:
    analytic tagged bytes (costmodel.chunk_act_bytes, scaled from the
    bf16 estimate to the cell's activation dtype) played through
    simulate.spmd_tick_peak over the runner's feed events, with each
    chunk's α discretized to the row split the tags actually deploy
    (``offload.quantized_alpha`` over the chunk's local row count) so the
    prediction cannot drift from the executed program at small shapes.
    The single formula behind the CI memory-gate, the honesty tests, and
    the ablation example."""
    from repro.core import costmodel as cm
    from repro.core import simulate as sim
    from repro.parallel import runner

    events = runner.pipeline_feed_events(cell.plan, cell.sched.n)
    acts = cm.chunk_act_bytes(cell.cfg, cell.sched.lengths,
                              batch=cell.b_loc, pp=cell.plan.pp,
                              sp=cell.plan.sp,
                              grad_accum=cell.plan.grad_accum)
    scale = jnp.dtype(cell.dtype).itemsize / cm.ACT_ITEMSIZE
    alphas_q = [ofl.quantized_alpha(ln // cell.plan.sp, a)
                for ln, a in zip(cell.sched.lengths, cell.alphas)]
    chunk_scales = None
    if cell.plan.offload_dtype not in (None, "none"):
        # compressed plans keep the per-row fp32 scales device-resident
        # with the keep set (§14): they enter the peak with the chunk and
        # never drain; only the offloaded row fraction has scales
        sb = cm.chunk_scale_bytes(cell.cfg, cell.sched.lengths,
                                  batch=cell.b_loc, pp=cell.plan.pp,
                                  sp=cell.plan.sp,
                                  grad_accum=cell.plan.grad_accum,
                                  offload_dtype=cell.plan.offload_dtype)
        chunk_scales = [b * a for b, a in zip(sb, alphas_q)]
    peak, _ = sim.spmd_tick_peak(events, pp=cell.plan.pp,
                                 chunk_acts=[a * scale for a in acts],
                                 alphas=alphas_q,
                                 chunk_scales=chunk_scales)
    return peak


def predicted_moment_bytes(cell, *, data_size: int) -> Tuple[float, float]:
    """(total, max_staged_pair) closed-form optimizer-state bytes for the
    measured step's stacked stage-param tree:
    ``costmodel.moment_bytes_per_param(opt_dtype)`` over the eval-shape
    param counts — the analytic side the moments channel is gated
    against.  Scope matches ``measure``'s subject: the stage-parameter
    moments (the depth-scaling term); the dp-replicated globals are
    outside the §5.2 device-budget subject."""
    import numpy as np

    from repro.core import costmodel as cm
    from repro.parallel import specs as SP

    st = SP.stage_struct(cell.mdef, cell.plan.pp, data_size, cell.dtype)
    shapes = [tuple(l.shape) for l in jax.tree_util.tree_leaves(st)]
    dt = cell.plan.opt_dtype
    mdt = getattr(cell.plan, "moments_dtype", "none")
    if mdt not in (None, "none"):
        # compressed residency (§14): per-leaf bytes = payload + per-row
        # scales, for both moments; the staged pair mirrors the measured
        # zip over the flattened (payload, scale) host leaves
        per_leaf = [cm.moment_bytes_from_shapes([s], dt, mdt)
                    for s in shapes]
        pairs = []
        for s in shapes:
            n = int(np.prod(s)) if s else 1
            rows = int(np.prod(s[:-1])) if len(s) >= 1 else 1
            pairs.append(max(2 * n, 2 * rows * cm.SCALE_ITEMSIZE))
        return sum(per_leaf), max(pairs)
    leaves = [int(np.prod(s)) for s in shapes]
    return cm.opt_state_bytes(sum(leaves), dt), cm.opt_state_bytes(
        max(leaves), dt)


def predicted_combined_peak(cell, *, data_size: int) -> float:
    """Predicted activations+moments device peak: the §5.2 tick-loop peak
    plus the moments' device term (full set when device-resident; the
    per-leaf staging pair when the plan offloads them).  The opt-state
    memory-gate's analytic side."""
    total, max_pair = predicted_moment_bytes(cell, data_size=data_size)
    mom = max_pair if cell.plan.offload_moments else total
    return predicted_spmd_peak(cell) + mom


def _measure_opt(cell, ledger: MemLedger, params, grads) -> None:
    """Measure the moments channel: trace + execute one real AdamW update
    over the measured step's stage params/grads with the plan's offload
    knobs, walk the update jaxpr for the opt_m@/opt_v@ names and the
    explicit device_put copies, and record update-phase probe evidence."""
    from repro.optim import adamw
    from repro.runtime import hostmem

    plan = cell.plan
    opt_dtype = (jnp.bfloat16 if plan.opt_dtype == "bfloat16"
                 else jnp.float32)
    kind = hostmem.host_memory_kind() if plan.offload_moments else None
    # the grads land committed to the emulated mesh (shard_map outputs);
    # co-locate the params so the update runs on the same device set, as
    # the real train_step's optimizer does
    params = jax.tree_util.tree_map(
        # transfer-lint: ok (device->device re-shard, no host copy)
        lambda p, g: jax.device_put(p, g.sharding), params, grads)
    moments_dtype = getattr(plan, "moments_dtype", "none")
    state = adamw.init_state(params, opt_dtype,
                             offload_moments=plan.offload_moments,
                             moments_dtype=moments_dtype)
    probe = update_probe(ledger)

    def opt_fn(p, g, s):
        return adamw.apply_update(
            p, g, s, lr=1e-3, offload_moments=plan.offload_moments,
            probe=probe,
            moments_dtype=moments_dtype)

    cjx = jax.make_jaxpr(opt_fn)(params, grads, state)
    named = moment_bytes_from_jaxpr(cjx)
    kinds = device_put_kinds(cjx)
    leaves_m = jax.tree_util.tree_leaves(state.m)
    leaves_v = jax.tree_util.tree_leaves(state.v)
    pairs = [int(m.nbytes) + int(v.nbytes)
             for m, v in zip(leaves_m, leaves_v)]
    init_dev = init_moment_device_bytes(
        params, opt_dtype, offload_moments=plan.offload_moments,
        moments_dtype=moments_dtype)

    exe = jax.jit(opt_fn)
    jax.block_until_ready(exe(params, grads, state))
    _drain_callbacks()
    t0 = time.perf_counter()
    jax.block_until_ready(exe(params, grads, state))
    ledger.opt_time_s = time.perf_counter() - t0
    _drain_callbacks()

    ledger.moments = MomentChannel(
        offloaded=plan.offload_moments,
        opt_dtype=plan.opt_dtype,
        host_kind=kind,
        m_bytes=sum(int(m.nbytes) for m in leaves_m),
        v_bytes=sum(int(v.nbytes) for v in leaves_v),
        n_leaves=len(leaves_m),
        max_pair_bytes=max(pairs) if pairs else 0,
        named_bytes=named["m"] + named["v"] + named.get("scale", 0),
        h2d_count=kinds.get(hostmem.DEVICE_KIND, 0),
        d2h_count=sum(c for k, c in kinds.items()
                      if k != hostmem.DEVICE_KIND),
        init_dev_bytes=init_dev)


def measure(cell, *, data_size: int, model_size: int, seed: int = 0,
            baseline: bool = True, opt: bool = False,
            d2h_bw: Optional[float] = None, tokens=None, labels=None,
            doc_start=None) -> MemLedger:
    """Execute one real train-grad step of `cell` on an emulated mesh with
    the ledger attached, measure the tagged bytes from the traced jaxpr,
    and (optionally) time an offload-off baseline for the exposed-transfer
    estimate.  With ``opt`` the optimizer update is measured too (the
    moments channel, §11): one real AdamW step over the measured grads
    with the plan's ``offload_moments``.  ``d2h_bw``
    prices the exposed-H2D channel (§12); pass the bandwidth of the
    hardware profile the cell was resolved against when it is not the
    default V5E.  Requires grad_accum == 1 (the jaxpr scan walk would
    otherwise multiply the per-microbatch bytes by the accumulation
    factor)."""
    import dataclasses

    from repro.parallel import runner

    plan = cell.plan
    assert plan.grad_accum == 1, "measure() needs grad_accum == 1"
    ledger = MemLedger()
    mk = dict(data_size=data_size, model_size=model_size, seed=seed,
              tokens=tokens, labels=labels, doc_start=doc_start)
    fn_grad, args = build_step(cell, ledger=ledger, with_grad=True, **mk)
    fn_fwd, _ = build_step(cell, ledger=None, with_grad=False, **mk)

    # 1) exact tagged bytes from the forward-only trace (no remat dup)
    per_suffix = tagged_bytes_from_jaxpr(jax.make_jaxpr(fn_fwd)(*args))

    # 2) executed step with runtime probes
    exe = jax.jit(fn_grad)
    jax.block_until_ready(exe(*args))
    _drain_callbacks()
    ledger.runtime_events.clear()      # drop compile-run samples
    t0 = time.perf_counter()
    step_out = exe(*args)
    jax.block_until_ready(step_out)
    ledger.step_time_s = time.perf_counter() - t0
    _drain_callbacks()                 # probes may land after the arrays

    events = runner.pipeline_feed_events(plan, cell.sched.n)
    ledger.offload_codec = plan.offload_dtype
    ledger.load_tagged(per_suffix, events, plan.pp, cell.alphas,
                       act_itemsize=jnp.dtype(cell.dtype).itemsize)

    # 2c) priced exposed-H2D over the measured bytes/windows (§12)
    from repro.core import costmodel as _cm

    ledger.prefetch = plan.prefetch
    ledger.price_h2d(bw=d2h_bw if d2h_bw is not None else _cm.V5E.d2h_bw)

    # 2b) optimizer-state channel over the measured grads
    if opt:
        _measure_opt(cell, ledger, args[0], step_out[1])

    # 3) offload-off baseline: the exposed-transfer estimate
    if baseline and plan.offload:
        cell_off = dataclasses.replace(
            cell, plan=dataclasses.replace(plan, offload=False),
            alphas=tuple(0.0 for _ in cell.alphas))
        fn_off, args_off = build_step(cell_off, ledger=None,
                                      with_grad=True, **mk)
        exe_off = jax.jit(fn_off)
        jax.block_until_ready(exe_off(*args_off))
        t0 = time.perf_counter()
        jax.block_until_ready(exe_off(*args_off))
        ledger.exposed_transfer_s = max(
            0.0, ledger.step_time_s - (time.perf_counter() - t0))
    return ledger

"""SPPO adaptive offloading (§5): sequence-aware ratios + two-level policy.

Two pieces, matching the paper:

1. **Sequence-aware offloading** (§5.2) — per-chunk offload ratio α_i chosen
   so the D2H transfer of chunk i hides under the compute of chunk i+1:
   α_i·A_i = M_threshold = BW_D2H · T_next_comp.  Under a FLOPs-balanced
   partition all T are equal and the paper's invariant
   α_{i-1}A_{i-1} = α_iA_i (monotone α, since A_0 ≥ A_1 ≥ …) emerges as a
   special case; the solver here works for *any* partition (length-based
   chunks have growing T_i, so α_i grows — same mechanism, general form).
   The final chunk never offloads (its backward begins immediately): α_N = 0.

2. **Two-level activation management** (§5.1) — a `jax.checkpoint` policy:
   Type-0 skeletal tensors (KV cache) are *explicit carries*, always on
   device; tagged Type-1 tensors are row-split by α into an offloaded part
   (`act_off` → pinned_host) and a device-resident part (`act_keep`);
   everything untagged (norms, rope, elementwise) is rematerialized.

Memory recurrence (paper eq. §5.2): M_i = M_{i-1} + A_i − α_{i-1}·A_{i-1},
simulated by ``peak_memory`` and asserted in tests.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.core import mutation
from repro.runtime import hostmem, scopes

OFF_NAME = "act_off"
KEEP_NAME = "act_keep"
SCALE_NAME = "act_scale"


def scale_name_for(off_name: str) -> str:
    """The checkpoint name of a codec's per-row scales, carrying the same
    chunk/tick qualifier as the off rows they reconstruct: ``act_off@t3``
    -> ``act_scale@t3``.  Scales stay device-resident (they ride the keep
    set — 4 bytes per row vs the rows themselves; hosting them would add a
    second tiny transfer per site for no memory win) but must be *named*
    and saved: an unnamed scale would be rematerialized by the backward
    replay from the full-precision rows, i.e. the whole act_off tensor
    would come back on device and the offload would be fictitious."""
    return SCALE_NAME + off_name[len(OFF_NAME):]


# ---------------------------------------------------------------------------
# 1. Sequence-aware offload ratio solver
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class OffloadPlan:
    alphas: tuple               # per-chunk offload ratio in [0, 1]
    m_threshold: float          # bytes offloaded per chunk slot (paper's M_thr)
    peak_units: float           # peak device activation memory (chunk-activation units)


def sequence_aware_alphas(act_bytes: Sequence[float],
                          comp_times: Sequence[float],
                          bw_d2h: float,
                          *, reserve_last: bool = True,
                          bwd_over_fwd: float = 2.0) -> OffloadPlan:
    """act_bytes[i]: Type-1 activation volume of chunk i;
    comp_times[i]: *forward* compute time of chunk i; bw_d2h: host-link
    bytes/s.

    α_i = min(1, BW · T_{i+1} / A_i): offload exactly what hides under the
    next chunk's compute.  α of the final chunk is 0 (its backward starts
    immediately — offloading it would only add H2D latency, §5.2).

    With ``reserve_last=False`` the final chunk does offload — a
    memory-constrained override, not a free lunch: its backward is the
    *first backward event* and its replay consumes the reloaded rows, so
    the D2H→H2D round trip serializes onto the critical path (nothing can
    hide it; the simulator charges it in full under either prefetch lane
    mode).  The first backward event's duration —
    ``comp_times[-1] * bwd_over_fwd`` (lumped fwd:bwd split, cf.
    costmodel.BWD_RATIO) — is therefore used as the *sizing budget*: α is
    chosen so each direction of the exposed round trip costs at most about
    one such backward.  The old behavior budgeted by the chunk's own
    *forward* time, which is already spent when the D2H becomes
    schedulable and mis-sizes the bound by the bwd/fwd ratio.
    """
    n = len(act_bytes)
    alphas = []
    for i in range(n):
        if i == n - 1 and reserve_last:
            alphas.append(0.0)
            continue
        window = (comp_times[i + 1] if i + 1 < n
                  else comp_times[i] * bwd_over_fwd)
        alphas.append(max(0.0, min(1.0, bw_d2h * window / max(act_bytes[i], 1e-9))))
    m_thr = max((a * b for a, b in zip(alphas, act_bytes)), default=0.0)
    peak = peak_memory(act_bytes, alphas)
    return OffloadPlan(tuple(alphas), m_thr, peak)


def peak_memory(act_bytes: Sequence[float], alphas: Sequence[float]) -> float:
    """Simulate M_i = M_{i-1} + A_i − α_{i-1}A_{i-1} (offload of chunk i-1
    completes during chunk i's compute); returns the forward-pass peak."""
    m = 0.0
    peak = 0.0
    prev_off = 0.0
    for a, al in zip(act_bytes, alphas):
        m += a              # chunk i activations materialize
        peak = max(peak, m)
        m -= prev_off       # previous chunk's offload drains
        prev_off = al * a
    # last chunk's offload (if any) drains after the loop
    peak = max(peak, m)
    return peak


def fixed_full_alphas(n: int) -> tuple:
    """Baseline: fixed full offloading (α=1 everywhere) — §7.2 'w/ offload'."""
    return tuple(1.0 for _ in range(n))


# ---------------------------------------------------------------------------
# 2. Two-level activation management: checkpoint policy + row-split tagging
# ---------------------------------------------------------------------------


def sppo_policy(offload: bool = True,
                names: tuple = (OFF_NAME, KEEP_NAME)):
    """Checkpoint policy: act_keep saved on device; act_off to pinned_host.

    offload=False degrades to save-only (the 'SPPO w/o offload' ablation)."""
    off_name, keep_name = names
    if offload:
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=[keep_name],
            names_which_can_be_offloaded=[off_name],
            offload_src="device",
            offload_dst="pinned_host",
        )
    return jax.checkpoint_policies.save_only_these_names(off_name, keep_name)


def split_rows(rows: int, alpha: float) -> int:
    """Rows routed off-device for a fractional α (the tags' split point).

    Nearest-row rounding, clipped to [0, rows].  The old ``max(1, ...)``
    floor forced at least one row off-device for *any* α > 0, so on short
    chunks the measured off-bytes exceeded the continuous α·A the ledger
    and simulator predict; predictions now share this discretization via
    ``quantized_alpha`` so the memgate band cannot drift at small shapes."""
    if alpha <= 0.0:
        return 0
    if alpha >= 1.0:
        return rows
    return max(0, min(rows, int(round(rows * alpha))))


def quantized_alpha(rows: int, alpha: float) -> float:
    """The offload ratio the row split actually deploys for a tensor with
    `rows` rows: ``split_rows(rows, α) / rows``.  Ledger/simulator
    predictions use this discretized ratio (runtime/memledger.py) so the
    analytic side matches the executed split exactly."""
    if rows <= 0:
        return 0.0
    return split_rows(rows, float(alpha)) / rows


def chunk_names(suffix: str = "") -> tuple:
    """(off, keep) checkpoint names, optionally qualified per chunk/tick.

    Qualified names (e.g. ``act_off@t3``) let the memledger attribute the
    saved bytes of each pipeline tick exactly from the traced jaxpr
    (runtime/memledger.py); the policies below save any qualified variant."""
    return (OFF_NAME + suffix, KEEP_NAME + suffix)


def make_tag(alpha: float, *, axis: int = 1,
             names: tuple = (OFF_NAME, KEEP_NAME)):
    """Row-split tagger implementing the fractional offload ratio.

    Splits a tagged activation along `axis` (the token/row dim): the first
    ⌈α·rows⌉ rows are routed to pinned_host, the rest stay on device.  α is
    static per chunk (the chunk loop is unrolled), exactly the paper's
    per-subsequence ratio."""
    alpha = float(alpha)
    off_name, keep_name = names

    @scopes.scoped(scopes.OFFLOAD)
    def tag(t):
        if alpha <= 0.0:
            return checkpoint_name(t, keep_name)
        if alpha >= 1.0:
            return checkpoint_name(t, off_name)
        k = split_rows(t.shape[axis], alpha)
        if k <= 0:                       # α quantizes to 0 rows on this shape
            return checkpoint_name(t, keep_name)
        if k >= t.shape[axis]:           # ... or to all rows
            return checkpoint_name(t, off_name)
        lo = jax.lax.slice_in_dim(t, 0, k, axis=axis)
        hi = jax.lax.slice_in_dim(t, k, t.shape[axis], axis=axis)
        lo = checkpoint_name(lo, off_name)
        hi = checkpoint_name(hi, keep_name)
        return jax.lax.concatenate([lo, hi], dimension=axis)

    return tag


def null_tag(t):
    """remat='none' mode: save everything on device."""
    return checkpoint_name(t, KEEP_NAME)


# ---------------------------------------------------------------------------
# 3. Executed offloading: explicit memory-kind placement of act_off rows
# ---------------------------------------------------------------------------
#
# The policy path above delegates placement to XLA's remat offloader.  The
# executed path makes the two-level split explicit dataflow instead: the
# act_off rows are device_put into host memory (D2H) *in the forward*, the
# named residual that jax.checkpoint saves is that host-resident copy, and
# the backward's rematerialization replays only the device_put back to
# device (H2D).  Double-buffering falls out of the dataflow: chunk i's D2H
# depends only on chunk i's forward, so it can overlap chunk i+1's compute,
# and the H2D is issued by the autodiff exactly at chunk i's backward.
# DESIGN.md §10 records the contract.  The memory-kind probe and the
# D2H/H2D primitives are shared with the optimizer-moment offload path
# (optim/adamw.py) via runtime/hostmem.py.

host_memory_kind = hostmem.host_memory_kind


def host_round_trip(t, *, name: str = OFF_NAME, codec: str = "none"):
    """Route `t` through host memory with the saved residual on the host:

      D2H -> checkpoint_name(act_off) -> H2D

    Under ``jax.checkpoint(policy=save_only_these_names(...))`` the named
    host-resident copy is what gets saved; the backward's remat replays only
    the H2D.  The round trip is a value-level identity.

    With a codec the rows cross compressed: quantize before the D2H (the
    host residual is the 1-byte payload), dequantize after the H2D, and the
    per-row fp32 scales stay on device under their own checkpoint name
    (``scale_name_for``).  The round trip is then forward-*lossy* — the
    consumer sees dequant(quant(t)) — so the gradient seam matters: a
    naive round trip would differentiate through quantize (round/convert
    have zero tangents ⇒ dead gradients); ``residual_substitute`` makes it
    a straight-through estimator instead, primal = the reconstruction,
    cotangent routed untouched to `t`'s producers."""
    if codec in (None, "none"):
        th = hostmem.to_host(t)                                   # D2H
        th = checkpoint_name(th, name)                            # host residual
        return hostmem.to_device(th)                              # H2D
    payload, scale = hostmem.quantize(t, codec)
    scale = checkpoint_name(scale, scale_name_for(name))          # device-resident
    # The named host residual crosses as an int8 BYTE CONTAINER: a named
    # fp8 residual under save_only_these_names carries an inexact tangent
    # through the remat partial-eval and poisons the primal with NaNs
    # (jax 0.4.x); integer payloads get float0 tangents and are immune.
    # Bitcast is bit-exact both ways and does not change the byte count —
    # the mirror image of the prefetch seam's to_transport (there the
    # custom_vjp channel needs an INEXACT container for the same payload).
    wire = payload.dtype
    if mutation.active("fp8-named-residual"):
        # seeded PR 7 regression (tests/mutants): skip the byte container,
        # naming the raw inexact payload — the auditor must flag this
        wire = jnp.int8
    pc = (payload if wire == jnp.int8
          else jax.lax.bitcast_convert_type(payload, jnp.int8))
    ph = checkpoint_name(hostmem.to_host(pc), name)
    pc_d = hostmem.to_device(ph)
    payload_d = (pc_d if wire == jnp.int8
                 else jax.lax.bitcast_convert_type(pc_d, wire))
    deq = hostmem.dequantize(payload_d, scale, codec, t.dtype)
    return residual_substitute(t, deq)


def make_exec_tag(alpha: float, *, axis: int = 1,
                  names: tuple = (OFF_NAME, KEEP_NAME), codec: str = "none"):
    """Executed form of ``make_tag``: same row split, but the act_off rows
    round-trip through host memory so the transfers are real program
    dataflow rather than an XLA remat hint.  The tag is a value-level
    identity (slice + concat + copies); it can still shift XLA fusion
    decisions, so offload on/off losses and grads are asserted to match to
    fp32 tolerance (<= 1e-5, tests/test_offload_exec.py), not bitwise.
    With a codec the off rows additionally quantize across the link
    (codec resolution replaces the fp32 tolerance; see
    tests/test_offload_quant.py for the pinned drift bounds)."""
    alpha = float(alpha)
    off_name, keep_name = names

    @scopes.scoped(scopes.OFFLOAD)
    def tag(t):
        if alpha <= 0.0:
            return checkpoint_name(t, keep_name)
        if alpha >= 1.0:
            return host_round_trip(t, name=off_name, codec=codec)
        k = split_rows(t.shape[axis], alpha)
        if k <= 0:
            return checkpoint_name(t, keep_name)
        if k >= t.shape[axis]:
            return host_round_trip(t, name=off_name, codec=codec)
        lo = jax.lax.slice_in_dim(t, 0, k, axis=axis)
        hi = jax.lax.slice_in_dim(t, k, t.shape[axis], axis=axis)
        lo = host_round_trip(lo, name=off_name, codec=codec)
        hi = checkpoint_name(hi, keep_name)
        return jax.lax.concatenate([lo, hi], dimension=axis)

    return tag


# ---------------------------------------------------------------------------
# 4. Prefetch="ahead" tag machinery (DESIGN.md §12)
# ---------------------------------------------------------------------------
#
# The executed path above leaves the backward H2D to autodiff: the remat of
# chunk i replays its reload exactly at chunk i's backward.  The "ahead"
# path moves residual management to a tick-level custom_vjp seam
# (parallel/runner.py: prefetch_chunk): the seam's *forward* runs the chunk
# with a capture tag — a dataflow identity that records the (off, keep) row
# split of every tagged tensor — and routes the off rows to host once, as
# the seam's explicit residual; the hand-written backward reloads chunk
# i's rows one event ahead (during chunk i+1's backward) and replays the
# chunk with an inject tag that substitutes the staged copies for the
# recomputed tensors.  ``residual_substitute`` is the gradient seam of that
# substitution: primal = the staged copy (bitwise equal — D2H/H2D round
# trips copy), cotangent routed entirely to the computed branch, so the
# replay differentiates the true producers while XLA can drop their
# forward values.


@jax.custom_vjp
def residual_substitute(computed, staged):
    """Identity-by-value swap: use `staged` (a reloaded residual, bitwise
    equal to `computed`) as the primal, route the cotangent to `computed`'s
    producers — exactly what saving `computed` under a checkpoint policy
    would do, with the residual's placement under caller control."""
    return staged


def _subst_fwd(computed, staged):
    return staged, None


def _subst_bwd(_, ct):
    return ct, jnp.zeros_like(ct)


residual_substitute.defvjp(_subst_fwd, _subst_bwd)


def make_capture_tag(alpha: float, collector: list, *, axis: int = 1,
                     codec: str = "none"):
    """Prefetch-'ahead' forward tag: a dataflow identity that appends the
    (kind, tensor) row split of every tagged tensor to `collector` in
    traversal order — "off" rows destined for host, "keep" rows staying on
    device.  The seam (runner.prefetch_chunk) stacks them over slots and
    performs the single D2H per site.  With a codec the off rows are
    captured *compressed*: the collector gets the ("off", payload) wire
    bytes plus a ("scale", scale) entry; the tag still returns `t`
    unchanged, so the capture forward itself stays exact — only the
    backward replay sees the reconstruction."""
    alpha = float(alpha)

    def capture_off(t):
        if codec in (None, "none"):
            collector.append(("off", t))
            return
        payload, scale = hostmem.quantize(t, codec)
        collector.append(("off", payload))
        collector.append(("scale", scale))

    @scopes.scoped(scopes.OFFLOAD)
    def tag(t):
        rows = t.shape[axis]
        k = split_rows(rows, alpha)
        if k <= 0:
            collector.append(("keep", t))
            return t
        if k >= rows:
            capture_off(t)
            return t
        capture_off(jax.lax.slice_in_dim(t, 0, k, axis=axis))
        collector.append(("keep", jax.lax.slice_in_dim(t, k, rows, axis=axis)))
        return t

    return tag


def make_inject_tag(alpha: float, off_acts, keep_acts, *, axis: int = 1,
                    names: tuple = (OFF_NAME, KEEP_NAME),
                    codec: str = "none", scales=()):
    """Prefetch-'ahead' backward-replay tag: re-walks the same tag sites as
    ``make_capture_tag`` (same α ⇒ same split decisions ⇒ same traversal
    order) and substitutes the staged residuals — `off_acts` reloaded one
    event ahead by the seam, `keep_acts` passed through on device — via
    ``residual_substitute``.  Substituted values carry the checkpoint names
    so the per-slot ``save_only_these_names`` replay saves exactly them.
    With a codec, `off_acts` are the reloaded wire payloads and `scales`
    (device-resident, from the seam's residuals) reconstruct the rows at
    the site before substitution — the same straight-through seam as the
    exec path."""
    alpha = float(alpha)
    off_it = iter(off_acts)
    keep_it = iter(keep_acts)
    scale_it = iter(scales)
    off_name, keep_name = names

    def staged_off(t_part):
        staged = next(off_it)
        if codec in (None, "none"):
            return staged
        return hostmem.dequantize(staged, next(scale_it), codec, t_part.dtype)

    @scopes.scoped(scopes.OFFLOAD)
    def tag(t):
        rows = t.shape[axis]
        k = split_rows(rows, alpha)
        if k <= 0:
            return checkpoint_name(
                residual_substitute(t, next(keep_it)), keep_name)
        if k >= rows:
            return checkpoint_name(
                residual_substitute(t, staged_off(t)), off_name)
        lo = jax.lax.slice_in_dim(t, 0, k, axis=axis)
        hi = jax.lax.slice_in_dim(t, k, rows, axis=axis)
        lo = checkpoint_name(residual_substitute(lo, staged_off(lo)), off_name)
        hi = checkpoint_name(residual_substitute(hi, next(keep_it)), keep_name)
        return jax.lax.concatenate([lo, hi], dimension=axis)

    return tag


def checkpoint_block(fn, *, offload: bool, remat: str = "sppo",
                     mode: str = "explicit",
                     names: tuple = (OFF_NAME, KEEP_NAME),
                     codec: str = "none"):
    """Wrap a layer/slot body with the SPPO two-level policy.

    mode='explicit' (the executed path): residual placement is explicit
    dataflow from the exec tags, so the policy only pins the two named
    classes as saved.  mode='xla': the original remat-offload policy —
    placement delegated to XLA (save_and_offload_only_these_names).
    With a codec the per-row scales join the save set under their own
    name — leaving them out would let the backward replay recompute them
    from the uncompressed rows, silently rematerializing the entire
    act_off tensor on device (see ``scale_name_for``)."""
    if remat == "full":
        return jax.checkpoint(fn)   # save nothing: full recompute baseline
    if remat == "none":
        return fn
    if mode == "xla":
        return jax.checkpoint(fn, policy=sppo_policy(offload, names=names))
    save = list(names)
    if codec not in (None, "none"):
        save.append(scale_name_for(names[0]))
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.save_only_these_names(*save))

"""Executed offloading honesty tests (DESIGN.md §10).

The offload plan must be *executable end to end*: with ``plan.offload`` the
pp>1 tick loop actually routes the act_off row splits through host memory
(memory-kind device_puts, or the staged-copy emulation on backends without
host memory kinds), the tag is numerically an identity (offload on/off
losses and grads agree to fp32 tolerance), the measured per-tick ledger
follows the §5.2 recurrence M_t = M_{t-1} + A_t − α_{t-1}A_{t-1}, and the
simulator's predicted peak brackets the measured ledger peak."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig, get_config
from repro.models.model_zoo import build_model
from repro.parallel.ctx import SINGLE
from repro.parallel.runner import resolve_cell, run_pipeline
from repro.runtime import memledger as ml

ALPHAS = (1.0, 0.7, 0.5, 0.0)   # full / fractional / fractional / reserved


def _mk_cell(mdef, *, pp, data_size=4, model_size=2, offload=True,
             offload_mode="explicit", alphas=ALPHAS, seq=256, batch=4):
    shape = ShapeConfig("t", seq, batch, "train")
    cell = resolve_cell(
        mdef, shape, data_size=data_size, model_size=model_size,
        overrides=dict(pp=pp, dp=data_size // pp, n_chunks=len(ALPHAS),
                       grad_accum=1, partition="length", offload=offload,
                       offload_mode=offload_mode))
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    if offload and alphas is not None:
        cell = dataclasses.replace(cell, alphas=tuple(alphas))
    return cell


def _loss_and_grads(cell, tokens, labels, *, data_size=4, model_size=2):
    """shard_map'd value_and_grad of the tick-loop pipeline — the shared
    scaffold from runtime/memledger.build_step, so the tests assert on the
    same program the memory-gate measures."""
    fn, args = ml.build_step(cell, data_size=data_size,
                             model_size=model_size, tokens=tokens,
                             labels=labels)
    loss, grads = jax.jit(fn)(*args)
    return float(loss), grads


def _tokens(cfg, B=4, S=256):
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    return tokens, jnp.roll(tokens, -1, axis=1)


# ---------------------------------------------------------------------------
# (a) numerics: offload on == offload off
# ---------------------------------------------------------------------------


def test_pp2_offload_on_off_grads_match(eight_devices):
    """The executed tag is slice + concat + host copies — an identity.
    Loss and every stage gradient must agree to <= 1e-5 fp32 between
    offload on (forced fractional alphas) and offload off."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    tokens, labels = _tokens(cfg)
    on = _mk_cell(mdef, pp=2, offload=True)
    off = _mk_cell(mdef, pp=2, offload=False)
    l_on, g_on = _loss_and_grads(on, tokens, labels)
    l_off, g_off = _loss_and_grads(off, tokens, labels)
    np.testing.assert_allclose(l_on, l_off, rtol=0, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_on),
                    jax.tree_util.tree_leaves(g_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)


def test_pp1_offload_on_off_loss_and_grads_match():
    """Same identity law on the pp == 1 FLOPs-balanced chunk loop."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    tokens, labels = _tokens(cfg, B=2)
    key = jax.random.PRNGKey(0)
    sp = mdef.init_stage_params(key, 0, 1, jnp.float32)
    g = mdef.init_globals(key, jnp.float32)

    def grads_for(offload):
        cell = resolve_cell(
            mdef, ShapeConfig("t", 256, 2, "train"), data_size=1,
            model_size=1,
            overrides=dict(n_chunks=4, grad_accum=1, offload=offload,
                           partition="length"))
        cell = dataclasses.replace(cell, dtype=jnp.float32)
        if offload:
            cell = dataclasses.replace(cell, alphas=ALPHAS)

        def loss(sp_, g_):
            out = run_pipeline(cell, SINGLE, sp_, g_, tokens, labels, None,
                               with_loss=True)
            return out["loss"] / jnp.maximum(out["denom"], 1.0)

        return jax.jit(jax.value_and_grad(loss))(sp, g)

    (l_on, g_on), (l_off, g_off) = grads_for(True), grads_for(False)
    np.testing.assert_allclose(float(l_on), float(l_off), rtol=0, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_on),
                    jax.tree_util.tree_leaves(g_off)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-5)


# ---------------------------------------------------------------------------
# the act_off rows really leave device memory space
# ---------------------------------------------------------------------------


def test_exec_path_emits_host_memory_transfers(eight_devices):
    """The differentiated pp>1 program contains memory-kind device_puts
    into a host space for every offloading tick, and none with offload
    disabled."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    tokens, labels = _tokens(cfg)

    def markers(offload):
        cell = _mk_cell(mdef, pp=2, offload=offload)
        fn, args = ml.build_step(cell, data_size=4, model_size=2,
                                 tokens=tokens, labels=labels)
        return str(jax.make_jaxpr(fn)(*args)).count("<host>")

    assert markers(True) >= 10
    assert markers(False) == 0


# ---------------------------------------------------------------------------
# (b) measured ledger follows the §5.2 recurrence
# ---------------------------------------------------------------------------


def test_measured_ledger_follows_recurrence(eight_devices):
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = _mk_cell(mdef, pp=2)
    led = ml.measure(cell, data_size=4, model_size=2, baseline=False)
    assert led.ticks, "ledger recorded no ticks"
    # every tick materialized the same tagged volume (equal-length chunks)
    mats = {r.mat_bytes for r in led.ticks}
    assert len(mats) == 1 and led.ticks[0].mat_bytes > 0
    # off split matches the deployed alpha up to the row-split rounding
    for r in led.ticks:
        frac = r.off_bytes / r.mat_bytes
        assert abs(frac - r.alpha) < 0.1, (r.tick, frac, r.alpha)
    # independent §5.2 replay over the measured bytes
    m, prev_off = 0, 0
    for r in led.ticks:
        m += r.mat_bytes
        assert r.resident == m, f"tick {r.tick}: {r.resident} != {m}"
        m -= prev_off
        prev_off = r.off_bytes
    assert led.peak_bytes == max(r.resident for r in led.ticks)
    # runtime probes saw every tick's forward and backward execute
    assert led.runtime_coverage_ok()


# ---------------------------------------------------------------------------
# (c) the simulator's prediction brackets the measurement
# ---------------------------------------------------------------------------


def test_sim_predicted_peak_brackets_measured(eight_devices):
    """Analytic prediction (costmodel tagged bytes -> simulate.spmd_tick_peak)
    vs measured ledger peak: the CI memory-gate contract, asserted at test
    scale.  The two must agree within the gate's 10% tolerance on the upper
    side and may not overclaim by more than 20% on the lower side."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = _mk_cell(mdef, pp=2)
    led = ml.measure(cell, data_size=4, model_size=2, baseline=False)
    predicted = ml.predicted_spmd_peak(cell)
    assert led.peak_bytes <= 1.1 * predicted, (led.peak_bytes, predicted)
    assert led.peak_bytes >= 0.8 * predicted, (led.peak_bytes, predicted)
    # the shared predictor is dtype-aware: the same cell in bf16 predicts
    # half the fp32 bytes (the estimate is priced in bf16)
    bf16 = dataclasses.replace(cell, dtype=jnp.bfloat16)
    assert ml.predicted_spmd_peak(bf16) == pytest.approx(predicted / 2)


@pytest.mark.optstate
def test_sim_predicted_combined_brackets_measured_with_moments(eight_devices):
    """The same honesty contract extended to the moments channel
    (DESIGN.md §11): measured *combined* activations+moments device peak
    brackets the analytic prediction, moment offload strictly reduces the
    measured combined peak vs the same cell with device-resident moments,
    and the ledger's coverage check demands the update-phase probe."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = _mk_cell(mdef, pp=2)
    cell = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, offload_moments=True))
    led = ml.measure(cell, data_size=4, model_size=2, baseline=False,
                     opt=True)
    assert led.moments is not None and led.moments.offloaded
    assert led.runtime_coverage_ok()      # fwd + bwd + update evidence
    predicted = ml.predicted_combined_peak(cell, data_size=4)
    got = led.combined_peak_bytes
    assert got <= 1.1 * predicted, (got, predicted)
    assert got >= 0.8 * predicted, (got, predicted)
    # executed moment offload must pay off against the resident baseline
    cell_res = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, offload_moments=False))
    led_res = ml.measure(cell_res, data_size=4, model_size=2,
                         baseline=False, opt=True)
    assert got < led_res.combined_peak_bytes, (
        got, led_res.combined_peak_bytes)
    assert led_res.combined_peak_bytes <= 1.1 * ml.predicted_combined_peak(
        cell_res, data_size=4)


# ---------------------------------------------------------------------------
# decode consumes the plan; offloading a decode step is rejected
# ---------------------------------------------------------------------------


def test_decode_plans_never_offload():
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    shape = ShapeConfig("d", 256, 8, "decode")
    cell = resolve_cell(mdef, shape, data_size=4, model_size=2)
    assert cell.plan.offload is False and cell.plan.remat == "none"
    with pytest.raises(AssertionError, match="decode plans must not offload"):
        resolve_cell(mdef, shape, data_size=4, model_size=2,
                     overrides=dict(offload=True))


def test_decode_plans_reject_compressed_residency():
    """Compressed residency rides the offload channels (DESIGN.md §14);
    a decode plan has neither, so requesting a codec must be rejected just
    like requesting offload itself."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    shape = ShapeConfig("d", 256, 8, "decode")
    with pytest.raises(AssertionError, match="compressed residency"):
        resolve_cell(mdef, shape, data_size=4, model_size=2,
                     overrides=dict(offload_dtype="fp8"))
    # an otherwise-valid compressed-moments plan is still a decode error
    with pytest.raises(AssertionError, match="compressed residency"):
        resolve_cell(mdef, shape, data_size=4, model_size=2,
                     overrides=dict(moments_dtype="int8",
                                    offload_moments=True))
    # without its prerequisites the moments codec fails plan validation
    with pytest.raises(AssertionError, match="moments_dtype"):
        resolve_cell(mdef, shape, data_size=4, model_size=2,
                     overrides=dict(moments_dtype="int8"))

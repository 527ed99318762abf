"""Executed optimizer-state offload honesty tests (DESIGN.md §11).

``offload_moments`` must be *executable end to end*, mirroring the PR-3
activation contract: host-resident AdamW moments update to exactly the same
values as device-resident ones (the H2D/H2D round trip is a value-level
identity), the explicit update stages exactly one H2D per moment leaf and
writes back with one D2H, the ledger's moments channel (opt_m@/opt_v@ jaxpr
walk) matches the cost model's closed form, and init births the moments in
host space with zero device materialization (the step-0 peak fix).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import get_config
from repro.core import costmodel as cm
from repro.models.model_zoo import build_model
from repro.optim import adamw
from repro.runtime import hostmem
from repro.runtime import memledger as ml

pytestmark = pytest.mark.optstate


@functools.lru_cache(maxsize=None)
def _params(pp: int):
    """Stacked stage-param tree of the reduced sppo config, the same
    stage-major layout the runner's optimizer updates."""
    cfg = get_config("sppo-gpt-7b").reduced()
    mdef = build_model(cfg)
    key = jax.random.PRNGKey(0)
    stages = [mdef.init_stage_params(key, s, pp, jnp.float32)
              for s in range(pp)]
    return jax.tree_util.tree_map(lambda *ls: jnp.stack(ls), *stages)


def _grads(params, scale: float):
    key = jax.random.PRNGKey(3)
    return jax.tree_util.tree_map(
        lambda p: scale * jax.random.normal(key, p.shape, jnp.float32),
        params)


# ---------------------------------------------------------------------------
# (a) property: offload on == offload off after repeated updates
# ---------------------------------------------------------------------------


@settings(max_examples=10, deadline=None)
@given(st.sampled_from(["float32", "bfloat16"]),
       st.sampled_from([1, 2]),
       st.sampled_from([True, False]))
def test_offload_identity_after_three_steps(opt_dtype, pp, clip_active):
    """With offload_moments on vs off, params and the full AdamWState agree
    to <= 1e-6 fp32 after 3 apply_update steps — across moment dtypes,
    pipeline depths, and clip-active/inactive gradients."""
    dt = jnp.bfloat16 if opt_dtype == "bfloat16" else jnp.float32
    params = _params(pp)
    grads = _grads(params, 1e3 if clip_active else 1e-4)
    p_on, p_off = params, params
    s_on = adamw.init_state(params, dt, offload_moments=True)
    s_off = adamw.init_state(params, dt)
    for _ in range(3):
        p_on, s_on, _ = adamw.apply_update(p_on, grads, s_on, lr=1e-3,
                                           offload_moments=True)
        p_off, s_off, _ = adamw.apply_update(p_off, grads, s_off, lr=1e-3)
    assert int(s_on.step) == int(s_off.step) == 3
    for a, b in zip(jax.tree_util.tree_leaves((p_on, s_on.m, s_on.v)),
                    jax.tree_util.tree_leaves((p_off, s_off.m, s_off.v))):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) the explicit path's jaxpr: host markers + one H2D per moment leaf
# ---------------------------------------------------------------------------


def test_explicit_update_jaxpr_contract():
    params = _params(2)
    grads = _grads(params, 1.0)
    state = adamw.init_state(params, jnp.float32, offload_moments=True)
    n_leaves = len(jax.tree_util.tree_leaves(state.m))

    def fn(p, g, s):
        return adamw.apply_update(p, g, s, lr=1e-3, offload_moments=True)

    cjx = jax.make_jaxpr(fn)(params, grads, state)
    kinds = ml.device_put_kinds(cjx)
    # exactly one H2D per moment leaf per step (m and v trees each)
    assert kinds.get(hostmem.DEVICE_KIND, 0) == 2 * n_leaves, kinds
    # ... and one D2H writes each new moment back to host
    assert kinds.get(hostmem.host_memory_kind(), 0) == 2 * n_leaves, kinds
    assert str(cjx).count("MemorySpace.Host") >= 2 * n_leaves
    # every moment leaf carries its ledger name
    named = ml.moment_bytes_from_jaxpr(cjx)
    assert len(named["leaves"]) == 2 * n_leaves


def test_no_copies_or_names_without_offload():
    params = _params(1)
    grads = _grads(params, 1.0)
    state = adamw.init_state(params, jnp.float32)

    def fn(p, g, s):
        return adamw.apply_update(p, g, s, lr=1e-3)

    cjx = jax.make_jaxpr(fn)(params, grads, state)
    assert ml.device_put_kinds(cjx) == {}
    assert ml.moment_bytes_from_jaxpr(cjx)["leaves"] == {}


# ---------------------------------------------------------------------------
# (c) ledger moments channel == cost-model closed form
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("opt_dtype", ["float32", "bfloat16"])
def test_moment_bytes_match_closed_form(opt_dtype):
    """The jaxpr walk over opt_m@/opt_v@ names must sum to exactly
    n_params * moment_bytes_per_param(opt_dtype) on the reduced cell."""
    dt = jnp.bfloat16 if opt_dtype == "bfloat16" else jnp.float32
    params = _params(2)
    grads = _grads(params, 1.0)
    state = adamw.init_state(params, dt, offload_moments=True)

    def fn(p, g, s):
        return adamw.apply_update(p, g, s, lr=1e-3, offload_moments=True)

    named = ml.moment_bytes_from_jaxpr(jax.make_jaxpr(fn)(params, grads,
                                                          state))
    n_params = sum(int(np.prod(l.shape))
                   for l in jax.tree_util.tree_leaves(params))
    assert named["m"] + named["v"] == \
        n_params * cm.moment_bytes_per_param(opt_dtype)
    # the real state buffers agree with the walk — the names cover every leaf
    real = sum(int(l.nbytes)
               for l in jax.tree_util.tree_leaves((state.m, state.v)))
    assert named["m"] + named["v"] == real


def test_runtime_coverage_requires_update_probe():
    """A ledger with a measured moments channel is only covered once an
    update-phase probe fired — fwd/bwd tick evidence alone is not enough."""
    led = ml.MemLedger()
    led.moments = ml.MomentChannel(
        offloaded=True, opt_dtype="float32",
        host_kind=hostmem.host_memory_kind(), m_bytes=8, v_bytes=8,
        n_leaves=1, max_pair_bytes=16, named_bytes=16, h2d_count=2,
        d2h_count=2, init_dev_bytes=0)
    assert not led.runtime_coverage_ok()
    led.record_runtime("upd", 0)
    assert led.runtime_coverage_ok()
    # without a moments channel the update probe is not required
    led2 = ml.MemLedger()
    assert led2.runtime_coverage_ok()


def test_csv_roundtrip_moments_column(tmp_path):
    led = ml.MemLedger()
    led.load_tagged({"@c0": {"off": 64, "keep": 64},
                     "@c1": {"off": 0, "keep": 128}},
                    [(0, 0, 1), (1, 0, 1)], 1, (0.5, 0.0))
    led.moments = ml.MomentChannel(
        offloaded=False, opt_dtype="float32",
        host_kind=None, m_bytes=300, v_bytes=300, n_leaves=3,
        max_pair_bytes=200, named_bytes=0, h2d_count=0, d2h_count=0,
        init_dev_bytes=600)
    led.opt_time_s = 0.25
    path = str(tmp_path / "led.csv")
    led.to_csv(path)
    back = ml.read_csv(path)
    assert [r["moments_dev_bytes"] for r in back["rows"]] == [600, 600]
    assert [r["resident_bytes"] for r in back["rows"]] == \
        [r.resident for r in led.ticks]
    s = back["summary"]
    assert s["moments_total_bytes"] == 600
    assert s["moments_dev_peak_bytes"] == 600
    assert s["combined_peak_bytes"] == led.combined_peak_bytes
    assert s["moments_offloaded"] == 0
    assert s["opt_time_s"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# (d) init_state births moments in host space: step-0 peak == steady state
# ---------------------------------------------------------------------------


def test_init_state_no_device_spike_regression():
    """The traced init must materialize zero moment bytes in device space
    when offloading (zeros born host-side), so the step-0 combined peak
    equals the steady-state peak; without offload the full set
    materializes on device — the measure is not vacuous."""
    params = _params(2)
    total = 2 * sum(int(np.prod(l.shape)) * 4
                    for l in jax.tree_util.tree_leaves(params))
    assert ml.init_moment_device_bytes(
        params, jnp.float32, offload_moments=True) == 0
    assert ml.init_moment_device_bytes(
        params, jnp.float32, offload_moments=False) == total
    # the concrete arrays really live in the host space
    kind = hostmem.host_memory_kind()
    state = adamw.init_state(params, jnp.float32, offload_moments=True)
    for leaf in jax.tree_util.tree_leaves((state.m, state.v)):
        assert hostmem.memory_kind_of(leaf) == kind
    # ledger arithmetic: steady-state device contribution is the staging
    # pair; step 0 adds init_dev_bytes on top — offloaded init adds nothing
    act_peak = 1000
    steady = act_peak + 16     # max_pair staging
    step0 = steady + ml.init_moment_device_bytes(
        params, jnp.float32, offload_moments=True)
    assert step0 == steady


def test_solver_prices_opt_epilogue():
    """offload_moments adds the unhidden moment round trip to the solver's
    iteration time — strictly positive, linear in the moment volume."""
    from repro.core import simulate as sim
    cfg = get_config("sppo-gpt-7b").reduced()
    from repro.core import solver
    kw = dict(seq_len=256, batch=4, n_params=100_000, pp=2, n=4, sp=2)
    t0, _ = solver.iteration_time(cfg, **kw)
    t1, _ = solver.iteration_time(cfg, **kw, offload_moments=True)
    per = cm.moment_bytes_per_param("float32")
    want = sim.opt_update_transfer(kw["n_params"] / (kw["sp"] * kw["pp"]),
                                   per, cm.V5E.d2h_bw)
    assert t1 - t0 == pytest.approx(want)
    assert want > 0
    # bf16 moments halve the epilogue
    t2, _ = solver.iteration_time(cfg, **kw, offload_moments=True,
                                  opt_dtype="bfloat16")
    assert t2 - t0 == pytest.approx(want / 2)

"""Ring-distributed chunked attention (DESIGN.md §15): the tentpole gate.

Executed law: ring_attention over a real shard_map mesh (sp in {2, 4})
computes the same loss AND gradients as the single-device dense oracle
(kernels/ref.mha_reference) to fp32 <= 1e-5 — both kernel backends, causal
and non-causal, packed-varlen (q_start segment window) included.  Priced
law: the simulator's ring lane and the per-stage memory model admit a
4M-token cell at attn_mode="ring" that attn_mode="local" cannot hold.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig, get_config
from repro.core import costmodel as cm
from repro.core import simulate as sim
from repro.core import solver
from repro.kernels import ops as kops
from repro.kernels.ref import mha_reference
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.parallel import ring
from repro.parallel.ctx import SINGLE, Ctx
from repro.parallel.runner import (_in_specs_for_params, batch_struct,
                                   resolve_cell, run_pipeline, shard_map)

pytestmark = pytest.mark.ring


# ---------------------------------------------------------------------------
# executed ring vs the single-device dense oracle (loss + grads, <= 1e-5)
# ---------------------------------------------------------------------------

def _qkv(seed=0, B=2, T=64, H=4, Hkv=2, hd=16):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, T, H, hd), jnp.float32)
    k = jax.random.normal(kk, (B, T, Hkv, hd), jnp.float32)
    v = jax.random.normal(kv, (B, T, Hkv, hd), jnp.float32)
    return q, k, v, jnp.arange(T, dtype=jnp.int32)


def _ring_value_and_grads(q, k, v, pos, sp, *, causal, q_start=None):
    """Scalar loss (psum of squared ring outputs) + grads on a (1, sp) mesh."""
    mesh = make_mesh((1, sp), ("data", "model"))
    ctx = Ctx(model_axis="model", sp=sp)
    in_specs = [P(None, "model")] * 3 + [P("model")]
    args = [q, k, v, pos]
    if q_start is not None:
        in_specs.append(P("model"))
        args.append(q_start)

    def loss(q, k, v, pos, *rest):
        def body(q_l, k_l, v_l, p_l, *rest_l):
            qs_l = rest_l[0] if rest_l else None
            o = ring.ring_attention(q_l, k_l, v_l, p_l, p_l, ctx,
                                    causal=causal, q_start=qs_l)
            return jax.lax.psum((o.astype(jnp.float32) ** 2).sum(), "model")
        f = shard_map(body, mesh, in_specs=tuple(in_specs), out_specs=P())
        return f(q, k, v, pos, *rest)

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(*args)


def _oracle_value_and_grads(q, k, v, pos, *, causal, q_start=None):
    def loss(q, k, v):
        o = mha_reference(q, k, v, pos, pos, causal=causal, q_start=q_start)
        return (o.astype(jnp.float32) ** 2).sum()
    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("backend", ["jnp", pytest.param("interpret", id="pallas")])
@pytest.mark.parametrize("sp", [2, 4])
@pytest.mark.parametrize("causal", [True, False])
def test_ring_matches_dense_oracle(backend, sp, causal, eight_devices):
    q, k, v, pos = _qkv()
    with kops.backend(backend):
        l1, g1 = _ring_value_and_grads(q, k, v, pos, sp, causal=causal)
    l0, g0 = _oracle_value_and_grads(q, k, v, pos, causal=causal)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    for got, ref in zip(g1, g0):
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=0)


@pytest.mark.parametrize("backend", ["jnp", pytest.param("interpret", id="pallas")])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_packed_varlen_matches_oracle(backend, sp, eight_devices):
    """q_start segment windows (packed documents, DESIGN.md §13) survive the
    rotation: the window is query-side and never moves, while every arriving
    KV block is masked against it inside the kernels."""
    q, k, v, pos = _qkv(seed=3)
    T = pos.shape[0]
    # two packed documents: [0, 24) and [24, T) — queries never look across
    q_start = jnp.where(pos < 24, 0, 24).astype(jnp.int32)
    with kops.backend(backend):
        l1, g1 = _ring_value_and_grads(q, k, v, pos, sp, causal=True,
                                       q_start=q_start)
    l0, g0 = _oracle_value_and_grads(q, k, v, pos, causal=True,
                                     q_start=q_start)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    for got, ref in zip(g1, g0):
        np.testing.assert_allclose(got, ref, atol=1e-5, rtol=0)
    assert T == 64  # the boundary at 24 is sp-misaligned on purpose for sp=4


def test_ring_sp1_degenerates_to_oracle():
    """At sp == 1 the ring is one partial + normalize — the self-oracle
    property every executed attention mode shares."""
    q, k, v, pos = _qkv(seed=5)
    o = ring.ring_attention(q, k, v, pos, pos, SINGLE, causal=True)
    ref = mha_reference(q, k, v, pos, pos, causal=True)
    np.testing.assert_allclose(o, ref, atol=1e-6, rtol=0)


# ---------------------------------------------------------------------------
# full-pipeline composition: ring under pp chunked scheduling + offload
# ---------------------------------------------------------------------------

def _single_loss(mdef, tokens, labels):
    shape = ShapeConfig("t", tokens.shape[1], tokens.shape[0], "train")
    cell = resolve_cell(mdef, shape, data_size=1, model_size=1,
                        overrides=dict(n_chunks=2, grad_accum=1,
                                       partition="length"))
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    sp1 = mdef.init_stage_params(key, 0, 1, jnp.float32)
    g1 = mdef.init_globals(key, jnp.float32)

    def f(sp_, g_):
        out = run_pipeline(cell, SINGLE, sp_, g_, tokens, labels, None,
                           with_loss=True)
        return out["loss"] / jnp.maximum(out["denom"], 1.0)

    return float(jax.jit(f)(sp1, g1))


def _dist_loss(mdef, tokens, labels, *, pp, mesh_shape, extra_overrides):
    data_size, model_size = mesh_shape
    mesh = make_mesh(mesh_shape, ("data", "model"))
    dp = data_size // pp
    B, S = tokens.shape
    overrides = dict(n_chunks=2, grad_accum=1, pp=pp, dp=dp,
                     partition="length")
    overrides.update(extra_overrides)
    cell = resolve_cell(mdef, ShapeConfig("t", S, B, "train"),
                        data_size=data_size, model_size=model_size,
                        overrides=overrides)
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    stages = [mdef.init_stage_params(key, s, pp, jnp.float32)
              for s in range(pp)]
    g_stage = jax.tree_util.tree_map(
        lambda *ls: jnp.stack([ls[i % pp] for i in range(data_size)]),
        *stages)
    gl = mdef.init_globals(key, jnp.float32)
    b_loc = B // dp

    def lay(x):
        return jnp.stack([x[(i // pp) * b_loc:(i // pp + 1) * b_loc]
                          for i in range(data_size)])[None]

    batch = {"tokens": lay(tokens), "labels": lay(labels)}
    pspecs = _in_specs_for_params(cell)
    _, bspecs = batch_struct(cell)

    def body(stage_p, g, b):
        ctx = cell.ctx()
        assert ctx.attn_mode == overrides.get("attn_mode", ctx.attn_mode)
        stage_p = jax.tree_util.tree_map(lambda a: a.reshape(a.shape[1:]),
                                         stage_p)
        tok = b["tokens"].reshape(b["tokens"].shape[2:])
        lab = b["labels"].reshape(b["labels"].shape[2:])
        out = run_pipeline(cell, ctx, stage_p, g, tok, lab, None,
                           with_loss=True)
        num = ctx.psum_loss_all(out["loss"])
        den = ctx.psum_loss_all(out["denom"])
        return num / jnp.maximum(den, 1.0)

    fn = shard_map(body, mesh,
                   in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
                   out_specs=P())
    return float(jax.jit(fn)(g_stage, gl, batch))


@pytest.mark.parametrize("mesh_shape,pp", [((4, 2), 2), ((2, 4), 2)])
def test_ring_pipeline_equals_single(mesh_shape, pp, eight_devices):
    """Ring attention composed with the chunked pipeline + executed offload
    (the default plan) reproduces the single-device loss at sp=2 and sp=4."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    B, S = 4, 256
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    ref = _single_loss(mdef, tokens, labels)
    got = _dist_loss(mdef, tokens, labels, pp=pp, mesh_shape=mesh_shape,
                     extra_overrides=dict(attn_mode="ring"))
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


# ---------------------------------------------------------------------------
# plan threading + validation
# ---------------------------------------------------------------------------

def test_plan_threads_ring_to_ctx():
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = resolve_cell(mdef, ShapeConfig("t", 256, 4, "train"),
                        data_size=4, model_size=2,
                        overrides=dict(pp=2, dp=2, n_chunks=2, grad_accum=1,
                                       partition="length", attn_mode="ring"))
    assert cell.plan.attn_mode == "ring"
    assert cell.ctx().attn_mode == "ring"


def test_plan_rejects_unknown_attn_mode():
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    with pytest.raises(AssertionError, match="attn_mode"):
        resolve_cell(mdef, ShapeConfig("t", 256, 4, "train"),
                     data_size=1, model_size=1,
                     overrides=dict(n_chunks=2, grad_accum=1,
                                    partition="length",
                                    attn_mode="ring_zigzag"))


def test_plan_rejects_local_on_wide_mesh():
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    with pytest.raises(AssertionError, match="local"):
        resolve_cell(mdef, ShapeConfig("t", 256, 4, "train"),
                     data_size=4, model_size=2,
                     overrides=dict(pp=2, dp=2, n_chunks=2, grad_accum=1,
                                    partition="length", attn_mode="local"))


# ---------------------------------------------------------------------------
# pricing: the ring lane, hop fractions, and the 4M admission artifact
# ---------------------------------------------------------------------------

def test_ring_overlap_recurrence():
    """Double-buffer recurrence: hop h+1's transfer is issued at hop h's
    compute start on a serialized link; exposure = arrival past compute."""
    wall, exposed, events = sim.ring_overlap([1.0, 1.0, 1.0],
                                             [0.0, 2.0, 2.0])
    assert (wall, exposed) == (5.0, 2.0)
    assert len([e for e in events if e[0] == "compute"]) == 3
    # fast link: everything hides, wall == pure compute
    wall, exposed, _ = sim.ring_overlap([1.0, 1.0, 1.0], [0.0, 0.1, 0.1])
    assert exposed == 0.0 and wall == 3.0


def test_ring_hop_fractions_causality_pricing():
    for sp in (2, 4, 16):
        block = cm.ring_hop_fractions(sp, layout="block")
        zig = cm.ring_hop_fractions(sp, layout="zigzag")
        assert sum(block) == sp  # late ranks serialize: no causal discount
        np.testing.assert_allclose(sum(zig), sp / 2 + 0.5 / sp)
        assert sum(cm.ring_hop_fractions(sp, causal=False)) == sp
    assert cm.ring_hop_fractions(1) == [1.0]


def test_simulated_ring_lane_prices_the_rotation():
    cfg = get_config("qwen2-7b")
    base_kw = dict(msp=False, offload=True)
    t0, _, r0 = solver.simulate_candidate(cfg, 524288, 1, 7_600_000_000,
                                          4, 8, 16, **base_kw)
    t1, _, r1 = solver.simulate_candidate(cfg, 524288, 1, 7_600_000_000,
                                          4, 8, 16, attn_mode="ring",
                                          **base_kw)
    assert any(ev.lane == sim.RING for ev in r1.trace)
    assert not any(ev.lane == sim.RING for ev in r0.trace)
    assert r1.ring_stall >= 0.0
    assert t1 >= t0  # the rotation can only add exposed time


def test_4m_cell_rejected_local_admitted_ring():
    """THE acceptance artifact: a simulated 4M-token qwen2-7b cell
    (batch=1, pp=4, sp=16) does not fit a 16 GiB stage at attn_mode="local"
    (full visible KV on every device) but is admitted at "ring" (one
    resident shard + two in-flight blocks)."""
    cfg = get_config("qwen2-7b")
    seq, n_params = 4 * 2 ** 20, 7_600_000_000
    adm = solver.admit_attn_mode(cfg, seq, 1, n_params, pp=4, sp=16)
    ok_local, d_local = adm["local"]
    ok_ring, d_ring = adm["ring"]
    assert not ok_local and d_local["total"] > cm.V5E.hbm_bytes
    assert ok_ring and d_ring["total"] <= cm.V5E.hbm_bytes
    # and the full chooser plays out the admitted mode end to end
    mode, report = solver.choose_attn_mode(cfg, seq, 1, n_params,
                                           pp=4, n=32, sp=16,
                                           modes=("local", "ring"))
    assert mode == "ring"
    assert report["local"]["admitted"] is False
    assert report["ring"]["admitted"] and report["ring"]["est_time"] > 0


def test_stage_attn_demand_scales_down_with_sp():
    cfg = get_config("qwen2-7b")
    kw = dict(seq_len=2 ** 20, batch=1, pp=4, n_params=7_600_000_000)
    ring16 = cm.stage_attn_demand(cfg, sp=16, mode="ring", **kw)
    ring8 = cm.stage_attn_demand(cfg, sp=8, mode="ring", **kw)
    local = cm.stage_attn_demand(cfg, sp=16, mode="local", **kw)
    assert ring16["kv_cache"] < ring8["kv_cache"]
    assert local["kv_cache"] == 16 * ring16["kv_cache"]
    gkv = cm.stage_attn_demand(cfg, sp=16, mode="gather_kv", **kw)
    assert gkv["attn_transient"] > ring16["attn_transient"]

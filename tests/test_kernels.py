"""Pallas kernel vs pure-jnp oracle: shape/dtype sweeps + merge properties,
plus the end-to-end training contract: a full train step (loss + grads)
on the Pallas kernels in interpret mode must match the jnp backend
per-parameter — single-device and through the pp>1 tick loop."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.kernels.ref import (attention_partial_ref, merge_partials,
                               mha_reference, normalize)
from repro.kernels import flash_attention as fa
from repro.kernels.flash_attention import flash_attention_partial


def _mk(B, Tq, S, H, Hkv, hd, hv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, Tq, H, hd), dtype)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (B, S, Hkv, hv), dtype)
    return q, k, v


SWEEP = [
    # B, Tq,  S,   H, Hkv, hd, hv, causal, q_off, dtype
    (1, 16, 16, 4, 4, 32, 32, True, 0, jnp.float32),
    (2, 32, 64, 4, 2, 16, 16, True, 32, jnp.float32),
    (1, 8, 128, 8, 1, 64, 32, True, 120, jnp.float32),   # MLA-like hv != hd
    (2, 17, 33, 6, 2, 16, 16, True, 16, jnp.float32),    # ragged sizes
    (1, 16, 48, 4, 4, 32, 32, False, 0, jnp.float32),    # bidirectional
    (1, 1, 64, 4, 2, 32, 32, True, 63, jnp.float32),     # decode: Tq=1
    (1, 32, 32, 4, 4, 32, 32, True, 0, jnp.bfloat16),
]


@pytest.mark.parametrize("B,Tq,S,H,Hkv,hd,hv,causal,qoff,dtype", SWEEP)
def test_ref_blockwise_matches_naive(B, Tq, S, H, Hkv, hd, hv, causal, qoff, dtype):
    q, k, v = _mk(B, Tq, S, H, Hkv, hd, hv, dtype)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + qoff
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    o, m, l = attention_partial_ref(q, k, v, q_pos, kv_pos, causal=causal,
                                    block_k=16)
    got = normalize(o, l)
    want = mha_reference(q, k, v, q_pos, kv_pos, causal=causal)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=tol, atol=tol)


# lengths the shape-chosen tiles split into several blocks, and pad:
# (300, 600) at G 2 takes 128 x 128 (nq 3, nk 5), (1000, 2000) at G 1
# takes 512 x 1024 (nq 2, nk 2)
AUTO_SWEEP = [
    (1, 300, 600, 4, 2, 32, 32, True, 300, jnp.float32),
    (1, 1000, 2000, 2, 2, 16, 16, True, 1000, jnp.float32),
    (1, 1000, 2000, 2, 2, 16, 16, False, 0, jnp.bfloat16),
]


def _case_id(case):
    return "-".join(getattr(x, "__name__", str(x)) for x in case)


# block length 16 everywhere, or None: the tiles the kernel picks itself
BLOCKED = ([pytest.param(*c, 16, id=_case_id(c)) for c in SWEEP]
           + [pytest.param(*c, None, id="auto-" + _case_id(c))
              for c in SWEEP + AUTO_SWEEP])


@pytest.mark.parametrize("B,Tq,S,H,Hkv,hd,hv,causal,qoff,dtype,block", BLOCKED)
def test_pallas_matches_ref(B, Tq, S, H, Hkv, hd, hv, causal, qoff, dtype,
                            block):
    q, k, v = _mk(B, Tq, S, H, Hkv, hd, hv, dtype)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + qoff
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    o1, m1, l1 = attention_partial_ref(q, k, v, q_pos, kv_pos, causal=causal,
                                       block_k=16)
    o2, m2, l2 = flash_attention_partial(q, k, v, q_pos, kv_pos,
                                         causal=causal, block_q=block,
                                         block_k=block, interpret=True)
    got = np.asarray(normalize(o2, l2))
    want = np.asarray(normalize(o1, l1))
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


# ---------------------------------------------------------------------------
# The tile rule: block geometry chosen from the call's static shapes
# ---------------------------------------------------------------------------

CELL_CHUNKS = [(9728, 9728), (6656, 16384)]    # gpt7b-16k-1chip, Tq vs S
RULE_SHAPES = CELL_CHUNKS + [(2048, 2048), (1, 4096), (300, 600), (17, 33),
                             (1000, 2000), (130, 9000)]


def _padding_at_128(Tq, S):
    """(Tqp, Sp) of fixed 128 x 128 blocks, the kernels' old default."""
    bq, bk = min(128, fa._round_up(Tq, 8)), min(128, fa._round_up(S, 8))
    return fa._round_up(Tq, bq), fa._round_up(S, bk)


@pytest.mark.parametrize("G", [1, 7, 16])
@pytest.mark.parametrize("Tq,S", RULE_SHAPES)
def test_tiles_pad_no_more_than_fixed_blocks(Tq, S, G):
    _, _, Tqp, Sp, _, _ = fa._geometry(G, Tq, S, 128, 128, None, None)
    old_q, old_k = _padding_at_128(Tq, S)
    assert Tqp <= old_q and Sp <= old_k
    if (Tq, S) in CELL_CHUNKS:
        assert (Tqp, Sp) == (Tq, S)


def test_tiles_of_the_cell_chunks():
    """The chip sweep's winner at the cell's chunks: 512-row q blocks,
    kv blocks of 512 at S 9728 (19 x 512) and 1024 at S 16384."""
    assert fa._tiles(1, 9728, 9728, 128, 128) == (512, 512)
    assert fa._tiles(1, 6656, 16384, 128, 128) == (512, 1024)


@pytest.mark.parametrize("G", [1, 2, 4, 7, 8, 16])
@pytest.mark.parametrize("Tq,S", RULE_SHAPES)
def test_tiles_cap_folded_rows(Tq, S, G):
    bq, bk = fa._tiles(G, Tq, S, 128, 128)
    assert G * bq <= fa.ROW_CAP and bq % 8 == 0
    assert bk % 128 == 0 or bk == fa._round_up(S, 8)


@pytest.mark.parametrize("hd_k,hd_v,G", [(128, 128, 1), (128, 128, 7),
                                         (128, 128, 16), (576, 512, 1),
                                         (576, 512, 8)])
def test_tiles_fit_the_vmem_budget(hd_k, hd_v, G):
    for Tq, S in RULE_SHAPES:
        bq, bk = fa._tiles(G, Tq, S, hd_k, hd_v)
        assert fa._vmem_bytes(G * bq, bk, hd_k, hd_v) <= fa.VMEM_BUDGET


def test_vmem_estimate_shrinks_kv_first():
    """MLA widths (576/512) at G 8: the q block stays at the row cap while
    the kv block halves to fit the budget."""
    bq, bk = fa._tiles(8, 2048, 2048, 576, 512)
    assert 8 * bq == fa.ROW_CAP and bk < 1024


@pytest.mark.parametrize("G", [1, 7, 16])
def test_tiles_decode_keeps_eight_rows(G):
    assert fa._tiles(G, 1, 4096, 128, 128)[0] == 8


def test_explicit_blocks_are_honoured():
    assert fa._geometry(1, 300, 600, 16, 16, 16, 16)[:2] == (16, 16)
    bq, bk = fa._geometry(1, 2048, 2048, 128, 128, 256, None)[:2]
    assert (bq, bk) == (256, fa._tiles(1, 2048, 2048, 128, 128)[1])
    bq, bk = fa._geometry(1, 2048, 2048, 128, 128, None, 128)[:2]
    assert (bq, bk) == (fa._tiles(1, 2048, 2048, 128, 128)[0], 128)


def test_partial_merge_equals_full():
    """Sharded-KV partials merged == full-KV attention (the psum-merge law)."""
    B, Tq, S, H, Hkv, hd = 2, 16, 64, 4, 2, 32
    q, k, v = _mk(B, Tq, S, H, Hkv, hd, hd, jnp.float32, seed=3)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + (S - Tq)
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    full = mha_reference(q, k, v, q_pos, kv_pos)
    parts = []
    for r in range(4):
        sl = slice(r * 16, (r + 1) * 16)
        parts.append(attention_partial_ref(q, k[:, sl], v[:, sl], q_pos,
                                           kv_pos[sl], block_k=8))
    o, m, l = merge_partials(parts)
    np.testing.assert_allclose(np.asarray(normalize(o, l)), np.asarray(full),
                               rtol=1e-5, atol=1e-5)


def test_empty_kv_rows_are_zero():
    """Fully-masked rows (no visible kv) come back 0, not NaN."""
    B, Tq, S = 1, 4, 8
    q, k, v = _mk(B, Tq, S, 2, 2, 16, 16, jnp.float32)
    q_pos = jnp.arange(Tq, dtype=jnp.int32)          # positions 0..3
    kv_pos = jnp.arange(S, dtype=jnp.int32) + 100    # all in the future
    o, m, l = attention_partial_ref(q, k, v, q_pos, kv_pos, block_k=8)
    out = normalize(o, l)
    assert not np.any(np.isnan(np.asarray(out)))
    np.testing.assert_allclose(np.asarray(out), 0.0)


# ---------------------------------------------------------------------------
# End-to-end training contract: Pallas (interpret) == jnp backend, grads too
# ---------------------------------------------------------------------------


def _make_model():
    from repro.configs.base import get_config
    from repro.models.model_zoo import build_model

    cfg = get_config("qwen2-7b").reduced()
    return cfg, build_model(cfg)


def _single_loss_grads(mdef, tokens, labels):
    """launch/train.py's single-device path: run_pipeline + value_and_grad."""
    from repro.configs.base import ShapeConfig
    from repro.parallel.ctx import SINGLE
    from repro.parallel.runner import resolve_cell, run_pipeline

    B, S = tokens.shape
    cell = resolve_cell(mdef, ShapeConfig("t", S, B, "train"), data_size=1,
                        model_size=1, overrides=dict(n_chunks=2, grad_accum=1,
                                                     partition="length"))
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    sp1 = mdef.init_stage_params(key, 0, 1, jnp.float32)
    g1 = mdef.init_globals(key, jnp.float32)

    def f(sp_, g_):
        out = run_pipeline(cell, SINGLE, sp_, g_, tokens, labels, None,
                           with_loss=True)
        return out["loss"] / jnp.maximum(out["denom"], 1.0)

    loss, grads = jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(sp1, g1)
    return float(loss), grads


def _dist_loss_grads(mdef, tokens, labels, *, pp=2, mesh_shape=(2, 2),
                     extra_overrides=None):
    """The pp>1 tick loop, grads computed exactly as make_train_step does:
    value_and_grad inside shard_map, stage/global psums."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.parallel.runner import (_in_specs_for_params, batch_struct,
                                       resolve_cell, run_pipeline, shard_map)

    data_size, model_size = mesh_shape
    mesh = make_mesh(mesh_shape, ("data", "model"))
    dp = data_size // pp
    B, S = tokens.shape
    overrides = dict(n_chunks=2, grad_accum=1, pp=pp, dp=dp,
                     partition="length")
    overrides.update(extra_overrides or {})
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
        stage_p = jax.tree_util.tree_map(lambda a: a.reshape(a.shape[1:]),
                                         stage_p)
        tok = b["tokens"].reshape(b["tokens"].shape[2:])
        lab = b["labels"].reshape(b["labels"].shape[2:])

        def loss_fn(stage_p, g):
            out = run_pipeline(cell, ctx, stage_p, g, tok, lab, None,
                               with_loss=True)
            num = ctx.psum_loss_all(out["loss"])
            den = ctx.psum_loss_all(out["denom"])
            return num / jnp.maximum(den, 1.0)

        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1))(stage_p, g)
        g_st = jax.tree_util.tree_map(lambda a: a[None],
                                      ctx.psum_grads(grads[0]))
        return loss, g_st, ctx.psum_globals(grads[1])

    fn = shard_map(body, mesh,
                   in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
                   out_specs=(P(), pspecs["stages"], pspecs["globals"]))
    loss, gs, gg = jax.jit(fn)(g_stage, gl, batch)
    return float(loss), (gs, gg)


def _max_abs_diff(ta, tb):
    leaves_a = jax.tree_util.tree_leaves(ta)
    leaves_b = jax.tree_util.tree_leaves(tb)
    assert len(leaves_a) == len(leaves_b) and leaves_a
    return max(float(jnp.max(jnp.abs(a - b)))
               for a, b in zip(leaves_a, leaves_b))


def test_train_step_grads_pallas_equals_jnp_single(kernel_backend):
    """Acceptance: fp32 single-device train step, per-parameter gradients of
    the Pallas (interpret) backend match the jnp backend to <= 1e-4."""
    cfg, mdef = _make_model()
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    with kernel_backend("jnp"):
        loss_j, grads_j = _single_loss_grads(mdef, tokens, labels)
    with kernel_backend("interpret"):
        loss_p, grads_p = _single_loss_grads(mdef, tokens, labels)
    assert abs(loss_p - loss_j) <= 1e-4
    assert _max_abs_diff(grads_p, grads_j) <= 1e-4


def test_train_py_runs_on_pallas_backend(kernel_backend):
    """launch/train.py end-to-end (driver, optimizer, metering) on the
    Pallas backend: two steps must run and agree with the jnp backend on
    the step-0 loss (bf16 model dtype, so a loose tolerance)."""
    from repro.launch.train import main

    args = ["--arch", "qwen2-7b", "--reduced", "--steps", "2",
            "--seq", "64", "--batch", "2", "--mesh", "1x1"]
    with kernel_backend("jnp"):
        hist_j = main(args)
    with kernel_backend("interpret"):
        hist_p = main(args)
    assert np.isfinite(hist_p[-1]["loss"])
    np.testing.assert_allclose(hist_p[0]["loss"], hist_j[0]["loss"],
                               rtol=2e-2, atol=2e-2)


def test_train_step_grads_pallas_equals_jnp_pp2(kernel_backend, eight_devices):
    """Acceptance: the pp>1 tick loop (dp x pp x sp shard_map, psum-merged
    partial softmax) trains identically on the Pallas backend."""
    cfg, mdef = _make_model()
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    with kernel_backend("jnp"):
        loss_j, grads_j = _dist_loss_grads(mdef, tokens, labels)
    with kernel_backend("interpret"):
        loss_p, grads_p = _dist_loss_grads(mdef, tokens, labels)
    assert abs(loss_p - loss_j) <= 1e-4
    assert _max_abs_diff(grads_p, grads_j) <= 1e-4


def test_train_step_grads_pallas_equals_jnp_gather_kv(kernel_backend, eight_devices):
    """The merge-free gather_kv attention mode (KV all-gather, local
    softmax, zero merge collectives) must also train identically — its
    backward reduce-scatters dk/dv through the all_gather transpose."""
    cfg, mdef = _make_model()
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (2, 64), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    ov = dict(attn_mode="gather_kv")
    with kernel_backend("jnp"):
        loss_j, grads_j = _dist_loss_grads(mdef, tokens, labels,
                                           extra_overrides=ov)
    with kernel_backend("interpret"):
        loss_p, grads_p = _dist_loss_grads(mdef, tokens, labels,
                                           extra_overrides=ov)
    assert abs(loss_p - loss_j) <= 1e-4
    assert _max_abs_diff(grads_p, grads_j) <= 1e-4

"""THE integration law: the distributed SPPO pipeline (dp x pp x sp over a
real shard_map mesh) computes the same loss as the single-device reference —
same weights, same tokens, fp32."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ShapeConfig, get_config
from repro.launch.mesh import make_mesh
from repro.models.model_zoo import build_model
from repro.parallel.ctx import SINGLE
from repro.parallel.runner import (_in_specs_for_params, batch_struct,
                                   resolve_cell, run_pipeline, shard_map)


def _single_loss(mdef, cfg, tokens, labels, context):
    shape = ShapeConfig("t", tokens.shape[1], tokens.shape[0], "train")
    cell = resolve_cell(mdef, shape, data_size=1, model_size=1,
                        overrides=dict(n_chunks=2, grad_accum=1,
                                       partition="length"))
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    key = jax.random.PRNGKey(0)
    sp1 = mdef.init_stage_params(key, 0, 1, jnp.float32)
    g1 = mdef.init_globals(key, jnp.float32)

    def f(sp_, g_):
        out = run_pipeline(cell, SINGLE, sp_, g_, tokens, labels, context,
                           with_loss=True)
        return out["loss"] / jnp.maximum(out["denom"], 1.0)

    return float(jax.jit(f)(sp1, g1))


def _dist_loss(mdef, cfg, tokens, labels, context, *, pp, mesh_shape=(4, 2),
               extra_overrides=None):
    data_size, model_size = mesh_shape
    mesh = make_mesh(mesh_shape, ("data", "model"))
    dp = data_size // pp
    B, S = tokens.shape
    shape = ShapeConfig("t", S, B, "train")
    overrides = dict(n_chunks=2, grad_accum=1, pp=pp, dp=dp,
                     partition="length")
    overrides.update(extra_overrides or {})
    cell = resolve_cell(mdef, shape, data_size=data_size,
                        model_size=model_size, overrides=overrides)
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
    if context is not None:
        batch["context"] = lay(context)

    pspecs = _in_specs_for_params(cell)
    _, bspecs = batch_struct(cell)

    def body(stage_p, g, b):
        ctx = cell.ctx()
        stage_p = jax.tree_util.tree_map(lambda a: a.reshape(a.shape[1:]),
                                         stage_p)
        tok = b["tokens"].reshape(b["tokens"].shape[2:])
        lab = b["labels"].reshape(b["labels"].shape[2:])
        cx = (b["context"].reshape(b["context"].shape[2:])
              if "context" in b else None)
        out = run_pipeline(cell, ctx, stage_p, g, tok, lab, cx,
                           with_loss=True)
        num = ctx.psum_loss_all(out["loss"])
        den = ctx.psum_loss_all(out["denom"])
        return num / jnp.maximum(den, 1.0)

    fn = shard_map(body, mesh,
                   in_specs=(pspecs["stages"], pspecs["globals"], bspecs),
                   out_specs=P())
    return float(jax.jit(fn)(g_stage, gl, batch))


CASES = [
    ("qwen2-7b", 2), ("qwen2-7b", 4),
    ("granite-moe-1b-a400m", 2),
    ("zamba2-7b", 2),
    ("whisper-tiny", 1),
    ("rwkv6-3b", 2),
]


def test_optimized_attention_modes_match(eight_devices):
    """§Perf modes (gather_kv auto-switch + bf16 grad reduce-scatter) keep
    the forward loss identical to the paper-faithful gather_q baseline."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    B, S = 4, 256
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    ref = _single_loss(mdef, cfg, tokens, labels, None)
    got = _dist_loss(mdef, cfg, tokens, labels, None, pp=2,
                     extra_overrides=dict(attn_mode="auto",
                                          grad_compress=True))
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


def test_msp_rejects_stateful_recurrence_archs():
    """MSP's full-chunk recompute is idempotent for the position-tagged KV
    cache but would advance SSM/RWKV recurrent state `split` times —
    resolve_cell must refuse (DESIGN.md §2)."""
    cfg = get_config("rwkv6-3b").reduced()
    mdef = build_model(cfg)
    with pytest.raises(AssertionError, match="msp unsupported"):
        resolve_cell(mdef, ShapeConfig("t", 256, 4, "train"), data_size=4,
                     model_size=2,
                     overrides=dict(pp=2, dp=2, n_chunks=4, msp=True,
                                    grad_accum=1, partition="length"))


def test_msp_pipeline_equals_single(eight_devices):
    """Executable MSP (§6.2 ramp schedule in the SPMD tick loop) computes
    the same loss as the single-device reference: the ramp sub-events'
    full-chunk recompute is idempotent and the loss masks tile the chunk."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    B, S = 4, 256
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    ref = _single_loss(mdef, cfg, tokens, labels, None)
    got2 = _dist_loss(mdef, cfg, tokens, labels, None, pp=2,
                      extra_overrides=dict(msp=True))
    np.testing.assert_allclose(got2, ref, rtol=3e-4, atol=3e-4)
    got4 = _dist_loss(mdef, cfg, tokens, labels, None, pp=4,
                      extra_overrides=dict(msp=True, n_chunks=4))
    np.testing.assert_allclose(got4, ref, rtol=3e-4, atol=3e-4)


@pytest.mark.parametrize("arch,pp", CASES)
def test_distributed_equals_single(arch, pp, eight_devices):
    cfg = get_config(arch).reduced()
    if cfg.moe is not None:  # avoid EP-width-dependent capacity drops
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
    mdef = build_model(cfg)
    B, S = 4, 256
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)
    context = None
    if cfg.cross_attn is not None:
        nctx = (cfg.n_frames if cfg.encoder_layers
                else cfg.cross_attn.n_context_tokens)
        npad = -(-nctx // 2) * 2
        context = jax.random.normal(jax.random.PRNGKey(9),
                                    (B, npad, cfg.d_model), jnp.float32)
    ref = _single_loss(mdef, cfg, tokens, labels, context)
    got = _dist_loss(mdef, cfg, tokens, labels, context, pp=pp)
    np.testing.assert_allclose(got, ref, rtol=3e-4, atol=3e-4)


def _step_grads(mdef, tokens, labels, mesh_shape, pp):
    """(loss, grad_norm, per-slot stage grads, global grads) of the real
    train-step gradient (runner.make_grad_step) on an fp32 cell — each
    stage's grads taken once, whatever the dp replication."""
    from jax.sharding import NamedSharding

    from repro.data.pipeline import shard_batch
    from repro.launch.mesh import make_test_mesh
    from repro.launch.train import build_params
    from repro.parallel.runner import make_grad_step

    data_size, model_size = mesh_shape
    B, S = tokens.shape
    overrides = dict(grad_accum=1)
    if pp:
        overrides.update(pp=pp, dp=data_size // pp)
    cell = resolve_cell(mdef, ShapeConfig("t", S, B, "train"),
                        data_size=data_size, model_size=model_size,
                        overrides=overrides)
    cell = dataclasses.replace(cell, dtype=jnp.float32)
    mesh = make_test_mesh(data_size, model_size)
    params, _, _ = build_params(cell, mesh)
    batch = shard_batch(tokens, labels, pods=1, data_size=data_size,
                        pp=cell.plan.pp)
    _, bspecs = batch_struct(cell)
    # transfer-lint: ok (test fixture, batch placement onto the mesh)
    batch = {k: jax.device_put(v, NamedSharding(mesh, bspecs[k]))
             for k, v in batch.items()}
    loss, grads, gnorm = jax.jit(make_grad_step(cell, mesh))(params, batch)
    n = cell.plan.pp
    stages = jax.tree_util.tree_map(
        lambda a: np.asarray(a[:n]).reshape((-1,) + a.shape[2:]),
        grads["stages"])
    return (float(loss), float(gnorm), stages,
            jax.tree_util.tree_map(np.asarray, grads["globals"]))


@pytest.mark.parametrize("mesh_shape,pp,rtol", [
    ((2, 1), 2, 1e-5),
    # the sequence-sharded (sp = 2) backward sits up to 0.4% per leaf from
    # the single-device one in fp32 (ROADMAP D8, cause open); the bound
    # still catches a dropped sequence shard (50%) or a psum-scaled
    # gradient (2x), the faults this test was written for
    ((1, 2), None, 1e-2),
    ((2, 2), 2, 1e-2),
], ids=["pp2", "sp2", "pp2xsp2"])
def test_train_step_grads_match_single_device(mesh_shape, pp, rtol,
                                              eight_devices):
    """The train step's gradient and its clip norm do not depend on the
    mesh: not scaled by the device count (the transpose of the loss psum
    under shard_map), every sequence shard's contribution summed into the
    params replicated over the model axis."""
    cfg = get_config("sppo-gpt-7b").reduced()
    mdef = build_model(cfg)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (1, 128)).astype(np.int32)
    labels = np.roll(tokens, -1, axis=1)
    loss1, norm1, st1, gl1 = _step_grads(mdef, tokens, labels, (1, 1), None)
    loss, norm, st, gl = _step_grads(mdef, tokens, labels, mesh_shape, pp)
    np.testing.assert_allclose(loss, loss1, rtol=1e-6)
    np.testing.assert_allclose(norm, norm1, rtol=rtol)
    for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path((st, gl)),
            jax.tree_util.tree_leaves((st1, gl1))):
        err = np.linalg.norm(a - b)
        assert err <= rtol * np.linalg.norm(b), (jax.tree_util.keystr(path),
                                                 err, np.linalg.norm(b))


def test_grad_norm_counts_dp_replicas_once(eight_devices):
    """At dp = 2 the stacked stage grads hold every stage twice; the clip
    norm counts each parameter once."""
    cfg = get_config("sppo-gpt-7b").reduced()
    mdef = build_model(cfg)
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, cfg.vocab_size, (2, 128)).astype(np.int32)
    _, norm, st, gl = _step_grads(mdef, tokens, np.roll(tokens, -1, axis=1),
                                  (2, 1), None)
    once = np.sqrt(sum(np.sum(np.square(a, dtype=np.float64))
                       for a in jax.tree_util.tree_leaves((st, gl))))
    np.testing.assert_allclose(norm, once, rtol=1e-5)

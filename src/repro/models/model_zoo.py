"""Model zoo: parameter init, shard-dim specs, and ModelDef per architecture.

Shard-dim markers (strings/ints, leaves of a pytree mirroring the params):
  int d      — "ag": stored sharded on dim d over `model`, all-gathered per use
  "keepN"    — stored & used sharded on dim N (embedding, LM head, experts,
               mamba head shards)
  "rep"      — replicated over `model`

All markers describe the *per-slot / per-leaf* layout; the runner adds the
slot-stack and data-stack dims when building global shapes and
PartitionSpecs.
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, get_config
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.parallel.ctx import Ctx
from repro.runtime import scopes


def _key(rng, *tags):
    k = rng
    for t in tags:
        # stable across processes — python's str hash is salted per run,
        # which made parameter init (and hence training losses) differ
        # between otherwise-identical CLI invocations
        k = jax.random.fold_in(k, zlib.crc32(str(t).encode()) % (2**31))
    return k


def keep(d: int) -> str:
    return f"keep{d}"


# ---------------------------------------------------------------------------
# Per-component init + spec builders (init returns FULL unsharded leaves;
# the runner shards on device placement via NamedSharding)
# ---------------------------------------------------------------------------


def _norm(rng, cfg, dtype):
    if cfg.norm == "rmsnorm":
        return {"scale": jnp.zeros((cfg.d_model,), dtype)}
    return {"scale": jnp.ones((cfg.d_model,), dtype),
            "bias": jnp.zeros((cfg.d_model,), dtype)}


def _norm_spec(cfg):
    if cfg.norm == "rmsnorm":
        return {"scale": "rep"}
    return {"scale": "rep", "bias": "rep"}


def _attn(rng, cfg, dtype, out_scale=1.0):
    d, H, Hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    p = {
        "wq": L.dense_init(_key(rng, "wq"), d, H * hd, dtype),
        "wk": L.dense_init(_key(rng, "wk"), d, Hkv * hd, dtype),
        "wv": L.dense_init(_key(rng, "wv"), d, Hkv * hd, dtype),
        "wo": L.dense_init(_key(rng, "wo"), H * hd, d, dtype, std=out_scale / math.sqrt(H * hd)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * hd,), dtype)
        p["bk"] = jnp.zeros((Hkv * hd,), dtype)
        p["bv"] = jnp.zeros((Hkv * hd,), dtype)
    return p


def _attn_spec(cfg):
    s = {"wq": 1, "wk": 1, "wv": 1, "wo": 0}
    if cfg.qkv_bias:
        s.update({"bq": "rep", "bk": "rep", "bv": "rep"})
    return s


def _mla(rng, cfg, dtype, out_scale=1.0):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    dn, dr, dv, dc, qr = (m.nope_head_dim, m.rope_head_dim, m.v_head_dim,
                          m.kv_lora_rank, m.q_lora_rank)
    return {
        "wq_a": L.dense_init(_key(rng, "wq_a"), d, qr, dtype),
        "q_norm": jnp.zeros((qr,), dtype),
        "wq_b": L.dense_init(_key(rng, "wq_b"), qr, H * (dn + dr), dtype),
        "wkv_a": L.dense_init(_key(rng, "wkv_a"), d, dc + dr, dtype),
        "kv_norm": jnp.zeros((dc,), dtype),
        "w_uk": L.trunc_normal(_key(rng, "w_uk"), (H, dn, dc), 1 / math.sqrt(dn), dtype),
        "w_uv": L.trunc_normal(_key(rng, "w_uv"), (H, dc, dv), 1 / math.sqrt(dc), dtype),
        "wo": L.dense_init(_key(rng, "wo"), H * dv, d, dtype,
                           std=out_scale / math.sqrt(H * dv)),
    }


def _mla_spec(cfg):
    return {"wq_a": 1, "q_norm": "rep", "wq_b": 1, "wkv_a": "rep",
            "kv_norm": "rep", "w_uk": 0, "w_uv": 0, "wo": 0}


def _mlp(rng, cfg, dtype, d_ff=None, out_scale=1.0):
    d = cfg.d_model
    ff = d_ff or cfg.d_ff
    p = {"w1": L.dense_init(_key(rng, "w1"), d, ff, dtype),
         "w2": L.dense_init(_key(rng, "w2"), ff, d, dtype,
                            std=out_scale / math.sqrt(ff))}
    if cfg.act in ("swiglu", "geglu"):
        p["w3"] = L.dense_init(_key(rng, "w3"), d, ff, dtype)
    elif cfg.mlp_bias:
        p["b1"] = jnp.zeros((ff,), dtype)
        p["b2"] = jnp.zeros((d,), dtype)
    return p


def _mlp_spec(cfg, gated=None):
    gated = cfg.act in ("swiglu", "geglu") if gated is None else gated
    s = {"w1": 1, "w2": 0}
    if gated:
        s["w3"] = 1
    elif cfg.mlp_bias:
        s.update({"b1": "rep", "b2": "rep"})
    return s


def _moe(rng, cfg, dtype, out_scale=1.0):
    m, d = cfg.moe, cfg.d_model
    E, ff = m.num_experts, m.d_ff_expert
    p = {
        "router": L.dense_init(_key(rng, "router"), d, E, jnp.float32),
        "w1": L.trunc_normal(_key(rng, "ew1"), (E, d, ff), 1 / math.sqrt(d), dtype),
        "w3": L.trunc_normal(_key(rng, "ew3"), (E, d, ff), 1 / math.sqrt(d), dtype),
        "w2": L.trunc_normal(_key(rng, "ew2"), (E, ff, d),
                             out_scale / math.sqrt(ff), dtype),
    }
    if m.n_shared_experts:
        sf = ff * m.n_shared_experts
        p["ws1"] = L.dense_init(_key(rng, "ws1"), d, sf, dtype)
        p["ws3"] = L.dense_init(_key(rng, "ws3"), d, sf, dtype)
        p["ws2"] = L.dense_init(_key(rng, "ws2"), sf, d, dtype,
                                std=out_scale / math.sqrt(sf))
    return p


def _moe_spec(cfg):
    s = {"router": "rep", "w1": keep(0), "w3": keep(0), "w2": keep(0)}
    if cfg.moe.n_shared_experts:
        s.update({"ws1": 1, "ws3": 1, "ws2": 0})
    return s


def _mamba(rng, cfg, dtype):
    ssm = cfg.ssm
    d = cfg.d_model
    d_in = ssm.expand * d
    H = d_in // ssm.head_dim
    ds, W = ssm.d_state, ssm.conv_width
    return {
        "in_x": L.dense_init(_key(rng, "in_x"), d, d_in, dtype),
        "in_bc": L.dense_init(_key(rng, "in_bc"), d, 2 * ds, dtype),
        "in_dt": L.dense_init(_key(rng, "in_dt"), d, H, dtype),
        "in_z": L.dense_init(_key(rng, "in_z"), d, d_in, dtype),
        "dt_bias": jnp.asarray(
            np.log(np.expm1(np.linspace(1e-3, 1e-1, H))), dtype),
        "conv_x": L.trunc_normal(_key(rng, "cx"), (W, d_in), 1 / math.sqrt(W), dtype),
        "conv_bc": L.trunc_normal(_key(rng, "cb"), (W, 2 * ds), 1 / math.sqrt(W), dtype),
        "A_log": jnp.asarray(np.log(np.linspace(1.0, 16.0, H)), jnp.float32),
        "D": jnp.ones((H,), jnp.float32),
        "norm_scale": jnp.zeros((d_in,), dtype),
        "out": L.dense_init(_key(rng, "out"), d_in, d, dtype),
    }


def _mamba_spec():
    return {"in_x": keep(1), "in_bc": "rep", "in_dt": keep(1),
            "in_z": keep(1), "dt_bias": keep(0), "conv_x": keep(1),
            "conv_bc": "rep", "A_log": keep(0), "D": keep(0),
            "norm_scale": keep(0), "out": keep(0)}


def _rwkv_tmix(rng, cfg, dtype):
    d = cfg.d_model
    R = 64
    return {
        "mu_x": jnp.zeros((d,), jnp.float32),
        "ddl_a": L.dense_init(_key(rng, "da"), d, 5 * 32, jnp.float32),
        "ddl_b": L.trunc_normal(_key(rng, "db"), (5 * 32, 5 * d), 0.01, jnp.float32),
        "mu_rkvwg": jnp.zeros((5, d), jnp.float32),
        "wr": L.dense_init(_key(rng, "wr"), d, d, dtype),
        "wk": L.dense_init(_key(rng, "wk"), d, d, dtype),
        "wv": L.dense_init(_key(rng, "wv"), d, d, dtype),
        "wg": L.dense_init(_key(rng, "wg"), d, d, dtype),
        "dec_a": L.dense_init(_key(rng, "dea"), d, R, jnp.float32),
        "dec_b": L.trunc_normal(_key(rng, "deb"), (R, d), 0.01, jnp.float32),
        "w0": jnp.asarray(np.linspace(-6.0, -1.0, d), jnp.float32),
        "u": L.trunc_normal(_key(rng, "u"), (d,), 0.3, jnp.float32),
        "ln_x_scale": jnp.ones((d,), jnp.float32),
        "ln_x_bias": jnp.zeros((d,), jnp.float32),
        "wo": L.dense_init(_key(rng, "wo"), d, d, dtype),
    }


def _rwkv_tmix_spec():
    return {"mu_x": "rep", "ddl_a": "rep", "ddl_b": 1, "mu_rkvwg": "rep",
            "wr": 1, "wk": 1, "wv": 1, "wg": 1, "dec_a": "rep", "dec_b": 1,
            "w0": "rep", "u": "rep", "ln_x_scale": "rep", "ln_x_bias": "rep",
            "wo": 0}


def _rwkv_cmix(rng, cfg, dtype):
    d, ff = cfg.d_model, cfg.d_ff
    return {
        "mu_k": jnp.zeros((d,), jnp.float32),
        "mu_r": jnp.zeros((d,), jnp.float32),
        "wk_c": L.dense_init(_key(rng, "wkc"), d, ff, dtype),
        "wv_c": L.dense_init(_key(rng, "wvc"), ff, d, dtype),
        "wr_c": L.dense_init(_key(rng, "wrc"), d, d, dtype),
    }


def _rwkv_cmix_spec():
    return {"mu_k": "rep", "mu_r": "rep", "wk_c": 1, "wv_c": 0, "wr_c": 1}


# ---------------------------------------------------------------------------
# Slot init per family
# ---------------------------------------------------------------------------


def _out_scale(cfg):
    return 1.0 / math.sqrt(2 * max(cfg.n_layers, 1))


def init_slot(cfg: ModelConfig, rng, slot_idx: int, n_real_slots: int, dtype):
    """Build one slot's params; slots >= n_real_slots are ghosts (gate 0)."""
    fam = cfg.family
    rng = _key(rng, "slot", slot_idx)
    ghost = slot_idx >= n_real_slots
    gate = jnp.float32(0.0 if ghost else 1.0)
    os = _out_scale(cfg)

    if fam in ("dense",):
        return {"ln1": _norm(rng, cfg, dtype), "ln2": _norm(_key(rng, 2), cfg, dtype),
                "attn": _attn(rng, cfg, dtype, os), "mlp": _mlp(rng, cfg, dtype, out_scale=os),
                "gate": gate}
    if fam == "moe":
        attn = (_mla(rng, cfg, dtype, os) if cfg.mla is not None
                else _attn(rng, cfg, dtype, os))
        return {"ln1": _norm(rng, cfg, dtype), "ln2": _norm(_key(rng, 2), cfg, dtype),
                "attn": attn, "moe": _moe(rng, cfg, dtype, os), "gate": gate}
    if fam == "vlm":
        n_self = cfg.cross_attn.every
        selfs = [
            {"ln1": _norm(_key(rng, i, 1), cfg, dtype),
             "ln2": _norm(_key(rng, i, 2), cfg, dtype),
             "attn": _attn(_key(rng, i, 3), cfg, dtype, os),
             "mlp": _mlp(_key(rng, i, 4), cfg, dtype, out_scale=os),
             "gate": gate}
            for i in range(n_self)
        ]
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *selfs)
        xattn = _attn(_key(rng, "x"), cfg, dtype, os)
        return {"self": stacked, "xln1": _norm(_key(rng, 5), cfg, dtype),
                "xln2": _norm(_key(rng, 6), cfg, dtype), "xattn": xattn,
                "xmlp": _mlp(_key(rng, 7), cfg, dtype, out_scale=os),
                "xgate_attn": jnp.zeros((), jnp.float32),
                "xgate_mlp": jnp.zeros((), jnp.float32),
                "gate": gate}
    if fam == "hybrid":
        n_m = cfg.shared_attn_every
        total_mixers = cfg.n_layers
        base = slot_idx * n_m
        mambas = [
            {"ln": _norm(_key(rng, i, 1), cfg, dtype),
             "mix": _mamba(_key(rng, i, 2), cfg, dtype),
             "gate": jnp.float32(1.0 if (base + i) < total_mixers and not ghost else 0.0)}
            for i in range(n_m)
        ]
        stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *mambas)
        return {"mamba": stacked, "gate_shared": gate, "gate": gate}
    if fam == "ssm":
        return {"ln1": _norm(rng, cfg, dtype), "ln2": _norm(_key(rng, 2), cfg, dtype),
                "tmix": _rwkv_tmix(rng, cfg, dtype),
                "cmix": _rwkv_cmix(_key(rng, 3), cfg, dtype), "gate": gate}
    if fam == "audio":
        return {"ln1": _norm(rng, cfg, dtype), "ln2": _norm(_key(rng, 2), cfg, dtype),
                "xln": _norm(_key(rng, 3), cfg, dtype),
                "attn": _attn(rng, cfg, dtype, os),
                "xattn": _attn(_key(rng, 4), cfg, dtype, os),
                "mlp": _mlp(rng, cfg, dtype, out_scale=os), "gate": gate}
    raise ValueError(fam)


def slot_spec(cfg: ModelConfig):
    fam = cfg.family
    if fam == "dense":
        return {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                "attn": _attn_spec(cfg), "mlp": _mlp_spec(cfg), "gate": "rep"}
    if fam == "moe":
        attn = _mla_spec(cfg) if cfg.mla is not None else _attn_spec(cfg)
        return {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                "attn": attn, "moe": _moe_spec(cfg), "gate": "rep"}
    if fam == "vlm":
        selfs = {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                 "attn": _attn_spec(cfg), "mlp": _mlp_spec(cfg), "gate": "rep"}
        # stacked sub-layer dim shifts ag dims by +1
        selfs = _shift_spec(selfs)
        return {"self": selfs, "xln1": _norm_spec(cfg), "xln2": _norm_spec(cfg),
                "xattn": _attn_spec(cfg), "xmlp": _mlp_spec(cfg),
                "xgate_attn": "rep", "xgate_mlp": "rep", "gate": "rep"}
    if fam == "hybrid":
        mamba = _shift_spec({"ln": _norm_spec(cfg), "mix": _mamba_spec(),
                             "gate": "rep"})
        return {"mamba": mamba, "gate_shared": "rep", "gate": "rep"}
    if fam == "ssm":
        return {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                "tmix": _rwkv_tmix_spec(), "cmix": _rwkv_cmix_spec(),
                "gate": "rep"}
    if fam == "audio":
        return {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                "xln": _norm_spec(cfg), "attn": _attn_spec(cfg),
                "xattn": _attn_spec(cfg), "mlp": _mlp_spec(cfg), "gate": "rep"}
    raise ValueError(fam)


def _shift_spec(spec):
    """Shift ag/keep dims by +1 for an extra leading stack dim."""
    def f(m):
        if isinstance(m, int):
            return m + 1
        if isinstance(m, str) and m.startswith("keep"):
            return keep(int(m[4:]) + 1)
        return m
    return jax.tree_util.tree_map(f, spec)


# ---------------------------------------------------------------------------
# Globals: embedding, positions, final norm, head, encoder, shared block
# ---------------------------------------------------------------------------


def init_globals(cfg: ModelConfig, rng, dtype):
    d = cfg.d_model
    vp = L.pad_vocab(cfg.vocab_size, 2048)
    g = {
        "embed": {"table": L.trunc_normal(_key(rng, "emb"), (vp, d), 0.02, dtype)},
        "final_norm": _norm(_key(rng, "fn"), cfg, dtype),
    }
    if not cfg.tie_embeddings:
        g["head"] = {"w": L.trunc_normal(_key(rng, "head"), (d, vp),
                                         1 / math.sqrt(d), dtype)}
    if cfg.pos_emb == "learned":
        g["pos"] = {"table": L.trunc_normal(_key(rng, "pos"),
                                            (cfg.max_position, d), 0.02, dtype)}
    if cfg.shared_attn_every:
        g["shared"] = {"ln1": _norm(_key(rng, "s1"), cfg, dtype),
                       "ln2": _norm(_key(rng, "s2"), cfg, dtype),
                       "attn": _attn(_key(rng, "sa"), cfg, dtype, _out_scale(cfg)),
                       "mlp": _mlp(_key(rng, "sm"), cfg, dtype,
                                   out_scale=_out_scale(cfg))}
    if cfg.encoder_layers:
        encs = [
            {"ln1": _norm(_key(rng, "e", i, 1), cfg, dtype),
             "ln2": _norm(_key(rng, "e", i, 2), cfg, dtype),
             "attn": _attn(_key(rng, "e", i, 3), cfg, dtype, _out_scale(cfg)),
             "mlp": _mlp(_key(rng, "e", i, 4), cfg, dtype,
                         out_scale=_out_scale(cfg))}
            for i in range(cfg.encoder_layers)
        ]
        g["encoder"] = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *encs)
        g["enc_final"] = _norm(_key(rng, "ef"), cfg, dtype)
    return g


def globals_spec(cfg: ModelConfig):
    g = {
        "embed": {"table": keep(0)},
        "final_norm": _norm_spec(cfg),
    }
    if not cfg.tie_embeddings:
        g["head"] = {"w": keep(1)}
    if cfg.pos_emb == "learned":
        g["pos"] = {"table": "rep"}
    if cfg.shared_attn_every:
        g["shared"] = {"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                       "attn": _attn_spec(cfg), "mlp": _mlp_spec(cfg)}
    if cfg.encoder_layers:
        g["encoder"] = _shift_spec({"ln1": _norm_spec(cfg), "ln2": _norm_spec(cfg),
                                    "attn": _attn_spec(cfg), "mlp": _mlp_spec(cfg)})
        g["enc_final"] = _norm_spec(cfg)
    return g


# ---------------------------------------------------------------------------
# Per-slot state init (caches / recurrent states)
# ---------------------------------------------------------------------------


def init_slot_state(cfg: ModelConfig, ctx: Ctx, batch: int, cache_loc: int,
                    dtype, p_slot_full=None, context=None):
    fam = cfg.family
    Hkv, hd = cfg.n_kv_heads, cfg.hd
    if fam in ("dense",):
        return {"kv": A.init_cache(batch, cache_loc, Hkv, hd, hd, dtype)}
    if fam == "moe":
        if cfg.mla is not None:
            m = cfg.mla
            w = m.kv_lora_rank + m.rope_head_dim
            return {"kv": A.KVCache(
                k=jnp.zeros((batch, cache_loc, 1, w), dtype),
                v=jnp.zeros((batch, 1, 1, 1), dtype),   # latent is both k and v
                pos=jnp.full((cache_loc,), A.PAD, jnp.int32))}
        return {"kv": A.init_cache(batch, cache_loc, Hkv, hd, hd, dtype)}
    if fam == "vlm":
        n_self = cfg.cross_attn.every
        kv = A.init_cache(batch, cache_loc, Hkv, hd, hd, dtype)
        kvs = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_self,) + a.shape), kv)
        xkv = A.make_cross_kv(context, p_slot_full["xattn"], cfg, ctx,
                              cfg.cross_attn.n_context_tokens)
        return {"self": kvs, "xkv": xkv}
    if fam == "hybrid":
        n_m = cfg.shared_attn_every
        ms = S.mamba2_init_state(cfg, batch, ctx.sp)
        mstack = jax.tree_util.tree_map(
            lambda a: jnp.broadcast_to(a[None], (n_m,) + a.shape), ms)
        return {"mamba": mstack,
                "shared_kv": A.init_cache(batch, cache_loc, Hkv, hd, hd, dtype)}
    if fam == "ssm":
        return {"rwkv": S.rwkv6_init_state(cfg, batch, ctx.sp)}
    if fam == "audio":
        xkv = A.make_cross_kv(context, p_slot_full["xattn"], cfg, ctx,
                              cfg.n_frames)
        return {"kv": A.init_cache(batch, cache_loc, Hkv, hd, hd, dtype),
                "xkv": xkv}
    raise ValueError(fam)


# ---------------------------------------------------------------------------
# ModelDef
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ModelDef:
    cfg: ModelConfig
    n_slots: int              # real slots (pre ghost-padding)
    layers_per_slot: int

    # ---- structure ---------------------------------------------------------
    def slots_per_stage(self, pp: int) -> int:
        return -(-self.n_slots // pp)

    def padded_slots(self, pp: int) -> int:
        return self.slots_per_stage(pp) * pp

    # ---- init --------------------------------------------------------------
    def init_stage_params(self, rng, stage: int, pp: int, dtype=jnp.bfloat16):
        spp = self.slots_per_stage(pp)
        slots = [init_slot(self.cfg, rng, stage * spp + i, self.n_slots, dtype)
                 for i in range(spp)]
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *slots)

    def init_globals(self, rng, dtype=jnp.bfloat16):
        return init_globals(self.cfg, rng, dtype)

    def stage_spec(self):
        return slot_spec(self.cfg)

    def globals_spec(self):
        return globals_spec(self.cfg)

    # ---- execution pieces ---------------------------------------------------
    @scopes.scoped(scopes.EMBED)
    def embed(self, g, ids, q_pos_local, ctx: Ctx, *, decode=False):
        table = g["embed"]["table"]
        if decode:
            vloc = table.shape[0]
            lo = ctx.model_index() * vloc
            idx = jnp.clip(ids - lo, 0, vloc - 1)
            hit = ((ids >= lo) & (ids < lo + vloc))[..., None]
            x = ctx.psum_model(
                jnp.where(hit, jnp.take(table, idx, axis=0), 0)
                .astype(table.dtype))
        else:
            x = L.embed_tokens(ids, table, ctx, out_dtype=table.dtype)
        if self.cfg.pos_emb == "learned":
            pos = jnp.clip(q_pos_local, 0, self.cfg.max_position - 1)
            emb = jnp.take(g["pos"]["table"], pos, axis=0)
            # positions are [T] (shared) or [B, T] (per-request paged decode)
            x = x + (emb if pos.ndim == 2 else emb[None])
        return x

    @scopes.scoped(scopes.HEAD_LOSS)
    def head_loss(self, g, x_loc, labels, mask, ctx: Ctx):
        x_loc = L.apply_norm(x_loc, g["final_norm"], self.cfg.norm)
        head = (g["embed"]["table"].T if self.cfg.tie_embeddings
                else g["head"]["w"])
        return L.vocab_parallel_xent(x_loc, head, labels, mask, ctx,
                                     real_vocab=self.cfg.vocab_size)

    def head_logits(self, g, x, ctx: Ctx):
        """Decode: full-vocab logits (gathered over model) for sampling."""
        x = L.apply_norm(x, g["final_norm"], self.cfg.norm)
        head = (g["embed"]["table"].T if self.cfg.tie_embeddings
                else g["head"]["w"])
        logits = (x @ head).astype(jnp.float32)
        logits = ctx.all_gather_model(logits, axis=2)
        return logits[..., :self.cfg.vocab_size]

    def encode(self, g, frames_loc, ctx: Ctx):
        """Whisper encoder over stub frame embeddings [B, F_loc, d]."""
        if not self.cfg.encoder_layers:
            return frames_loc
        spec = {"ln1": _norm_spec(self.cfg), "ln2": _norm_spec(self.cfg),
                "attn": _attn_spec(self.cfg), "mlp": _mlp_spec(self.cfg)}

        def body(x, p_layer):
            p = T.gather_params(p_layer, spec, ctx)
            return T.encoder_layer(self.cfg, p, x, ctx, self.cfg.n_frames), None

        x, _ = jax.lax.scan(body, frames_loc, g["encoder"])
        return L.apply_norm(x, g["enc_final"], self.cfg.norm)

    def init_state(self, stage_params_local, g, ctx: Ctx, batch: int,
                   cache_loc: int, dtype, context=None, spp: int = None):
        """Stacked per-slot state for this stage; cross-attn KV is computed
        here (chunk-invariant) from gathered per-slot projections."""
        spp = spp if spp is not None else jax.tree_util.tree_leaves(
            stage_params_local)[0].shape[0]
        spec = self.stage_spec()
        states = []
        for i in range(spp):
            p_full = None
            if self.cfg.family in ("vlm", "audio"):
                p_i = jax.tree_util.tree_map(lambda a: a[i], stage_params_local)
                p_full = T.gather_params(p_i, spec, ctx)
            states.append(init_slot_state(self.cfg, ctx, batch, cache_loc,
                                          dtype, p_full, context))
        return jax.tree_util.tree_map(lambda *a: jnp.stack(a), *states)

    def _extras(self, g, ctx):
        if not self.cfg.shared_attn_every:
            return None
        shared_spec = {"ln1": _norm_spec(self.cfg), "ln2": _norm_spec(self.cfg),
                       "attn": _attn_spec(self.cfg), "mlp": _mlp_spec(self.cfg)}
        return {"shared": T.gather_params(g["shared"], shared_spec, ctx)}

    def stage_apply(self, stage_params, state, x, ctx, meta, g, *,
                    offload=True, remat="sppo", offload_mode="explicit",
                    offload_dtype="none"):
        return T.stage_apply(self.cfg, self.cfg.family, stage_params,
                             self.stage_spec(), state, x, ctx, meta,
                             self._extras(g, ctx), offload=offload,
                             remat=remat, offload_mode=offload_mode,
                             offload_dtype=offload_dtype)

    def stage_apply_capture(self, stage_params, state, x, ctx, meta, g, *,
                            alpha: float, offload_dtype="none"):
        """Prefetch-'ahead' forward (DESIGN.md §12): returns the stage
        output plus the captured (off, keep, scale) residual sets."""
        return T.stage_apply_capture(self.cfg, self.cfg.family, stage_params,
                                     self.stage_spec(), state, x, ctx, meta,
                                     alpha, self._extras(g, ctx),
                                     offload_dtype=offload_dtype)

    def stage_apply_inject(self, stage_params, state, x, ctx, meta, g, *,
                           alpha: float, off_acts, keep_acts,
                           offload_dtype="none", scales=()):
        """Prefetch-'ahead' backward replay over staged residuals."""
        return T.stage_apply_inject(self.cfg, self.cfg.family, stage_params,
                                    self.stage_spec(), state, x, ctx, meta,
                                    alpha, off_acts, keep_acts,
                                    self._extras(g, ctx),
                                    offload_dtype=offload_dtype,
                                    scales=scales)


def build_model(name_or_cfg) -> ModelDef:
    cfg = (name_or_cfg if isinstance(name_or_cfg, ModelConfig)
           else get_config(name_or_cfg))
    fam = cfg.family
    if fam == "vlm":
        group = cfg.cross_attn.every
        n_slots = -(-cfg.n_layers // group)
        return ModelDef(cfg, n_slots, group + 1)
    if fam == "hybrid":
        group = cfg.shared_attn_every
        n_slots = -(-cfg.n_layers // group)
        return ModelDef(cfg, n_slots, group + 1)
    return ModelDef(cfg, cfg.n_layers, 1)

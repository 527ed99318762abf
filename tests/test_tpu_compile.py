"""The Pallas attention kernels compile for a TPU v5e, described not attached.

Interpret mode accepts block layouts that the TPU compiler refuses, and
more VMEM than a v5e core has, so the forward, dq and dkv kernels are
compiled here for one chip of a described ``v5e:2x2``, at the tiles the
kernels choose from the call's shapes (``_tiles``).  At head width 128:
grouped-query factors G = 1 (sppo-gpt-7b, 32 heads), G = 7 (qwen2-7b, 28
query heads over 4 KV heads) and G = 16 (glm4-9b, 32 over 2) at T = 2048;
the two chunk shapes of the benchmark cell ``gpt7b-16k-1chip`` (Tq 9728
against S 9728, Tq 6656 against S 16384); lengths the tiles pad (G = 7, Tq
1000 against S 3000); and a decode forward (Tq = 1).  Also MLA's absorbed
widths (key 576, value 512) at G = 8.  Each compiled program must hold its
Mosaic kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the fixture skips where
the topology cannot be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention import _bwd_impl, flash_attention_partial

T = 2048           # query and key length of the training cases
HD = 128           # head width of all three models
HEADS = {1: (32, 32), 7: (28, 4), 16: (32, 2)}   # G -> (H, Hkv)
# id -> (B, Tq, S, H, Hkv)
SHAPES = {f"G{g}": (1, T, T, H, Hkv) for g, (H, Hkv) in HEADS.items()}
SHAPES["chunk0"] = (1, 9728, 9728, 32, 32)
SHAPES["chunk1"] = (1, 6656, 16384, 32, 32)
SHAPES["ragged-G7"] = (2, 1000, 3000, 28, 4)   # both lengths padded
# MLA's absorbed form: one latent KV head, key width 576, value width 512;
# 8 query heads, 128 over a 16-way model axis
SHAPES["mla-G8"] = (1, T, T, 8, 1, 576, 512)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip cannot be read back from the
    # persistent cache without one: keep these compiles out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _inputs(sharding, B, Tq, S, H, Hkv, hdk=HD, hdv=HD):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return dict(q=sds((B, Tq, H, hdk), jnp.bfloat16),
                k=sds((B, S, Hkv, hdk), jnp.bfloat16),
                v=sds((B, S, Hkv, hdv), jnp.bfloat16),
                q_pos=sds((B, Tq), jnp.int32), kv_pos=sds((S,), jnp.int32),
                q_start=sds((B, Tq), jnp.int32),
                do=sds((B, Tq, H, hdv), jnp.float32),
                m=sds((B, Tq, H), jnp.float32),
                dl=sds((B, Tq, H), jnp.float32))


def _fwd(x):
    return flash_attention_partial(x["q"], x["k"], x["v"], x["q_pos"],
                                   x["kv_pos"], q_start=x["q_start"])


def _bwd(x):
    return _bwd_impl(x["q"], x["k"], x["v"], x["q_pos"], x["kv_pos"],
                     x["q_start"], x["do"], x["m"], x["dl"], True,
                     HD ** -0.5, None, None, False)


KERNELS = {
    "fwd": _fwd,
    "dq": lambda x: _bwd(x)[0],          # the dkv call is dead code here
    "dkv": lambda x: _bwd(x)[1:],        # ... and the dq call here
}


def _custom_calls(fn, x) -> int:
    return jax.jit(fn).lower(x).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("shape", list(SHAPES))
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, shape):
    x = _inputs(one_chip, *SHAPES[shape])
    assert _custom_calls(KERNELS[kernel], x) == 1


@pytest.mark.parametrize("g", sorted(HEADS), ids=lambda g: f"G{g}")
def test_decode_forward_compiles_for_v5e(one_chip, g):
    H, Hkv = HEADS[g]
    x = _inputs(one_chip, 4, 1, 4096, H, Hkv)
    assert _custom_calls(_fwd, x) == 1

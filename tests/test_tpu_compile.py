"""The Pallas attention kernels compile for a TPU v5e, described not attached.

Interpret mode accepts block layouts that the TPU compiler refuses, so the
forward, dq and dkv kernels are compiled here for one chip of a described
``v5e:2x2`` at head width 128, for grouped-query factors G = 1 (sppo-gpt-7b,
32 heads) and G = 7 (qwen2-7b, 28 query heads over 4 KV heads), plus a
decode forward (Tq = 1).  Each compiled program must hold its Mosaic kernel
(``tpu_custom_call``).  Nothing runs.

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
HD = 128           # head width of both models
HEADS = {1: (32, 32), 7: (28, 4)}   # G -> (H, Hkv)


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


def _inputs(sharding, B, Tq, S, H, Hkv):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return dict(q=sds((B, Tq, H, HD), jnp.bfloat16),
                k=sds((B, S, Hkv, HD), jnp.bfloat16),
                v=sds((B, S, Hkv, HD), jnp.bfloat16),
                q_pos=sds((B, Tq), jnp.int32), kv_pos=sds((S,), jnp.int32),
                q_start=sds((B, Tq), jnp.int32),
                do=sds((B, Tq, H, HD), jnp.float32),
                m=sds((B, Tq, H), jnp.float32),
                dl=sds((B, Tq, H), jnp.float32))


def _fwd(x):
    return flash_attention_partial(x["q"], x["k"], x["v"], x["q_pos"],
                                   x["kv_pos"], q_start=x["q_start"])


def _bwd(x):
    return _bwd_impl(x["q"], x["k"], x["v"], x["q_pos"], x["kv_pos"],
                     x["q_start"], x["do"], x["m"], x["dl"], True,
                     HD ** -0.5, 128, 128, False)


KERNELS = {
    "fwd": _fwd,
    "dq": lambda x: _bwd(x)[0],          # the dkv call is dead code here
    "dkv": lambda x: _bwd(x)[1:],        # ... and the dq call here
}


def _custom_calls(fn, x) -> int:
    return jax.jit(fn).lower(x).compile().as_text().count("tpu_custom_call")


@pytest.mark.parametrize("g", sorted(HEADS), ids=lambda g: f"G{g}")
@pytest.mark.parametrize("kernel", sorted(KERNELS))
def test_kernel_compiles_for_v5e(one_chip, kernel, g):
    H, Hkv = HEADS[g]
    x = _inputs(one_chip, 1, T, T, H, Hkv)
    assert _custom_calls(KERNELS[kernel], x) == 1


@pytest.mark.parametrize("g", sorted(HEADS), ids=lambda g: f"G{g}")
def test_decode_forward_compiles_for_v5e(one_chip, g):
    H, Hkv = HEADS[g]
    x = _inputs(one_chip, 4, 1, 4096, H, Hkv)
    assert _custom_calls(_fwd, x) == 1

"""The main path's Pallas kernels compile for a TPU v5e at real widths.

Nothing here runs on a chip: each kernel is lowered and compiled for a
described (not attached) v5e chip, which raises what the chip's compiler
would raise — unaligned blocks, too much VMEM — and the compiled text must
hold the Mosaic kernel (``tpu_custom_call``). The topology is described
inside a fixture, never at import: only one process at a time may load
the TPU library, and every pytest worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import DPConfig
from repro.kernels import fused_round
from repro.kernels.dual_matmul import dual_matmul_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.zo_update import zo_update_pallas

# qwen1.5-0.5b's embedding split over 4 parties: vocab x d_model / q
EMBED_SHARD = 151_936 * 256


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield jax.sharding.SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _spec(sharding):
    return lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                  sharding=sharding)


@pytest.mark.parametrize("n", [EMBED_SHARD, 1000])
def test_zo_update_compiles(one_chip, n):
    S = _spec(one_chip)
    hlo = _compiled_text(
        lambda w, b, s: zo_update_pallas(w, b, s, interpret=False),
        S((n,), jnp.float32), S((n,), jnp.uint32), S((), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_dual_matmul_compiles(one_chip):
    S = _spec(one_chip)
    hlo = _compiled_text(
        lambda x, w, u: dual_matmul_pallas(x, w, u, mu=1e-2,
                                           interpret=False),
        S((1024, 1024), jnp.bfloat16), S((1024, 1024), jnp.bfloat16),
        S((1024, 1024), jnp.float32))
    assert "tpu_custom_call" in hlo


def test_flash_attention_compiles(one_chip):
    S = _spec(one_chip)
    qkv = S((16, 2048, 64), jnp.float32)
    hlo = _compiled_text(
        lambda q, k, v: flash_attention_pallas(q, k, v, causal=True,
                                               interpret=False),
        qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("codec", ["f32", "int8"])
@pytest.mark.parametrize("shape", [(8, 64, 256), (1000,), (1,)])
def test_defended_encode_compiles(one_chip, codec, shape):
    """clip -> Gaussian noise -> codec in one kernel; int8 adds the
    block-absmax pass."""
    S = _spec(one_chip)
    dp = DPConfig(noise_multiplier=1.1, clip=0.7, mechanism="gaussian")

    def encode(c, dp_bits, rnd_bits, z):
        return fused_round.defended_encode(
            c, dp_bits, rnd_bits if codec == "int8" else None, dp, codec,
            impl="pallas", interpret=False, z=z)

    hlo = _compiled_text(encode, S(shape, jnp.float32),
                         S(shape, jnp.uint32), S(shape, jnp.uint32),
                         S((), jnp.uint32))
    assert "tpu_custom_call" in hlo

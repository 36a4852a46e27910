"""Ahead-of-time compiles of the main-path Pallas kernels for a TPU v5e.

Interpret mode, which every other kernel test runs, never meets Mosaic's
block-layout rules or the chip's memory limits.  These tests lower each
kernel through its public op at production widths (Llama-3-8B heads:
32 query / 8 KV heads of 128; Mamba2-130M's SSD) for a *described*
``v5e:2x2`` topology — the TPU compiler runs here with no chip attached —
and check that the compiled program really contains the Mosaic kernel.

The topology is described inside a module-scoped fixture (never at import
time): only one process may load the TPU runtime, and every test worker
imports every test file.  The persistent compilation cache is off around
these compiles, since an entry written for a chip cannot be read back
without one.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash import ops as flash_ops
from repro.kernels.kvq import ops as kvq_ops
from repro.kernels.pack import ops as pack_ops
from repro.kernels.ssd import ops as ssd_ops

# Llama-3-8B attention geometry
H, HKV, D = 32, 8, 128
# Mamba2-130M SSD geometry: d_inner 1536 = 24 heads x 64, d_state 128
SSD_H, SSD_P, SSD_N, SSD_CHUNK = 24, 64, 128, 128


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; returns the program text."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding)
            for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _kernels(text: str) -> int:
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_fwd_compiles(one_chip, window):
    text = _compile(
        lambda q, k, v: flash_ops.flash_attention(
            q, k, v, window=window, backend="pallas"),
        one_chip, ((1, H, 2048, D), jnp.bfloat16),
        ((1, HKV, 2048, D), jnp.bfloat16), ((1, HKV, 2048, D), jnp.bfloat16))
    assert _kernels(text) >= 1


@pytest.mark.parametrize("window", [0, 1024])
def test_flash_fwd_bwd_compiles(one_chip, window):
    def loss(q, k, v):
        o = flash_ops.flash_attention(q, k, v, window=window,
                                      backend="pallas")
        return jnp.sum(o.astype(jnp.float32) ** 2)

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    ((1, H, 2048, D), jnp.bfloat16),
                    ((1, HKV, 2048, D), jnp.bfloat16),
                    ((1, HKV, 2048, D), jnp.bfloat16))
    assert _kernels(text) >= 4          # fwd, delta, dQ, dKV


@pytest.mark.parametrize("s,d", [(256, 32), (40, 64)])
def test_flash_small_shapes_compile(one_chip, s, d):
    """A head_dim under the 128-lane tile and a sequence under one block
    lower too: their blocks span the whole array dimension."""
    def loss(q, k, v):
        return jnp.sum(flash_ops.flash_attention(
            q, k, v, backend="pallas").astype(jnp.float32))

    text = _compile(jax.grad(loss, argnums=(0, 1, 2)), one_chip,
                    *[((1, 2, s, d), jnp.bfloat16)] * 3)
    assert _kernels(text) >= 4


@pytest.mark.parametrize("splits", [1, 4])
@pytest.mark.parametrize("with_lengths", [False, True])
def test_kvq_decode_compiles(one_chip, splits, with_lengths):
    b, s = 8, 2048

    def decode(q, kq, ks, vq, vs, lengths):
        return kvq_ops.decode_attention(
            q, kq, ks, vq, vs, backend="pallas", splits=splits,
            lengths=lengths if with_lengths else None)

    text = _compile(decode, one_chip, ((b, H, D), jnp.bfloat16),
                    ((b, HKV, s, D), jnp.int8), ((b, HKV, s), jnp.float32),
                    ((b, HKV, s, D), jnp.int8), ((b, HKV, s), jnp.float32),
                    ((b,), jnp.int32))
    assert _kernels(text) == 1


def test_pack_encode_compiles(one_chip):
    text = _compile(lambda x: pack_ops.encode(x, backend="pallas"), one_chip,
                    ((1024, 32, 32, 3), jnp.uint8))
    assert _kernels(text) == 1


def test_pack_decode_compiles(one_chip):
    text = _compile(lambda x: pack_ops.decode(x, backend="pallas"), one_chip,
                    ((256, 32, 32, 3), jnp.uint32))
    assert _kernels(text) == 1


def test_ssd_fwd_compiles(one_chip):
    b, L = 2, 2048

    def run(x, dt, a, bm, cm, d):
        return ssd_ops.ssd(x, dt, a, bm, cm, d, chunk=SSD_CHUNK,
                           backend="pallas")

    text = _compile(run, one_chip,
                    ((b, L, SSD_H, SSD_P), jnp.float32),
                    ((b, L, SSD_H), jnp.float32), ((SSD_H,), jnp.float32),
                    ((b, L, SSD_N), jnp.float32), ((b, L, SSD_N), jnp.float32),
                    ((SSD_H,), jnp.float32))
    assert _kernels(text) == 1

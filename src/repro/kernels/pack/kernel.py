"""Pallas TPU kernels for the E-D codec (paper's custom decode layer).

The decode is HBM-bandwidth-bound: each uint32 read expands to four
normalized float32 pixels.  Packing therefore cuts the HBM (and host->device)
traffic of the input stream 4x at the cost of two VPU ops per pixel —
exactly the paper's trade ("compression reduces at-least 20% training
time"), re-tiled for VMEM:

  * input tile  (BR, BC)      uint32  -> 4*BR*BC bytes in VMEM
  * output tile (4, BR, BC)   float32 -> 16*BR*BC bytes in VMEM

Default BR=64, BC=512 keeps a tile pair < 5 MiB (double-buffered) in the
~16 MiB VMEM of a v5e core, with the last dim a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.pack.ref import LANES

DEFAULT_BR = 64
DEFAULT_BC = 512


def _decode_kernel(packed_ref, out_ref, *, scale: float, shift: float):
    # Mosaic has no uint32 -> float32 cast: reinterpret the bits as int32
    # (the 0xFF mask drops the sign extension of the arithmetic shift) and
    # convert from there.
    x = jax.lax.bitcast_convert_type(packed_ref[...], jnp.int32)  # (BR, BC)
    for i in range(LANES):  # unrolled VPU shifts/masks
        lane = ((x >> (8 * i)) & 0xFF).astype(jnp.float32)
        out_ref[i, :, :] = lane * scale + shift


def _encode_kernel(lanes_ref, out_ref):
    acc = jnp.zeros(out_ref.shape, jnp.uint32)
    for i in range(LANES):
        acc = acc | (lanes_ref[i, :, :].astype(jnp.uint32) << jnp.uint32(8 * i))
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("scale", "shift", "br", "bc", "interpret"))
def decode_pallas(packed: jax.Array, *, scale: float = 1.0 / 255.0,
                  shift: float = 0.0, br: int = DEFAULT_BR, bc: int = DEFAULT_BC,
                  interpret: bool = False) -> jax.Array:
    """(R, C) uint32 -> (4, R, C) f32; R % br == 0, C % bc == 0 (ops.py pads)."""
    r, c = packed.shape
    br, bc = min(br, r), min(bc, c)
    assert r % br == 0 and c % bc == 0, (r, c, br, bc)
    grid = (r // br, c // bc)
    return pl.pallas_call(
        functools.partial(_decode_kernel, scale=scale, shift=shift),
        grid=grid,
        in_specs=[pl.BlockSpec((br, bc), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((LANES, br, bc), lambda i, j: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((LANES, r, c), jnp.float32),
        interpret=interpret,
    )(packed)


@functools.partial(jax.jit, static_argnames=("br", "bc", "interpret"))
def encode_pallas(lanes_u8: jax.Array, *, br: int = DEFAULT_BR,
                  bc: int = DEFAULT_BC, interpret: bool = False) -> jax.Array:
    """(4, R, C) uint8 -> (R, C) uint32."""
    _, r, c = lanes_u8.shape
    br, bc = min(br, r), min(bc, c)
    assert r % br == 0 and c % bc == 0, (r, c, br, bc)
    grid = (r // br, c // bc)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((LANES, br, bc), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((br, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.uint32),
        interpret=interpret,
    )(lanes_u8)

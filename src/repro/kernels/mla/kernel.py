"""Pallas absorbed latent-attention decode over a bf16 latent cache.

Grid ``(B, ns)``: one row per slot, then the slot's cached positions in
tiles of ``bs``.  The layer index and each slot's length ride the
scalar-prefetch lane (``pltpu.PrefetchScalarGridSpec``), so the kernel
reads the stacked cache in place, the layer it is given: no slice of the
cache is copied out for it.  The latent leaf is ``(L, B, S, C)``; the
rope keys lie sequence-minor, ``(L, B, R, S)``, as a TPU lays out a
64-wide minor axis anyway, so that no layout copy precedes the kernel.

Per live tile, every head at once: the scores are the one
``(H, C + R) x (C + R, bs)`` product, taken as its latent and rotary
parts against the two cache leaves, in the leaves' dtype with float32
accumulation; an online softmax in float32 scratch; and ``P . lat``
(``(H, bs) x (bs, C)``) with P rounded to the cache's dtype.  Tiles past
a slot's length are skipped (``pl.when``), and their index maps clamp to
the last live tile (``tiling.decode_last_live_tile``), so no DMA is
issued for them.  The straddling tile is masked with an iota compare.
Output: ``(B, H, C)`` float32; the caller applies the value
up-projection.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tiling
from repro.kernels.tiling import NEG_INF

#: cached positions per tile: 1 MiB of a 512-wide bf16 latent
DEFAULT_BS = 1024
#: the kernel's name in compiled programs and device traces
KERNEL_NAME = "mla_decode_pallas"


def _dot_t(a, b):
    """a (m, k) . b (n, k)^T -> (m, n), float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _mla_decode_kernel(layer_ref, len_ref, qa_ref, qr_ref, lat_ref,
                       rope_ref, o_ref, m_ref, l_ref, acc_ref, *, sm_scale,
                       bs, ns):
    del layer_ref                       # used by the index maps only
    i = pl.program_id(0)
    t = pl.program_id(1)
    length = len_ref[i]

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(t * bs < length)
    def _step():
        lat = lat_ref[...][0, 0]                              # (bs, C)
        s = (_dot_t(qa_ref[...][0], lat)
             + jnp.dot(qr_ref[...][0], rope_ref[...][0, 0],
                       preferred_element_type=jnp.float32)) * sm_scale
        kpos = t * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < length, s, NEG_INF)              # (H, bs)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(lat.dtype), lat, preferred_element_type=jnp.float32)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1)
        m_ref[...] = m_new

    @pl.when(t == ns - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / denom[:, None])[None]


@functools.partial(jax.jit, static_argnames=("sm_scale", "block_s",
                                             "interpret"))
def mla_decode_pallas(q_abs, q_rope, lat, rope, lengths, layer, *,
                      sm_scale: float, block_s: int = DEFAULT_BS,
                      interpret: bool = False):
    """Shapes as in ``ref.mla_decode_ref``; the queries are taken in the
    cache's dtype.  ``block_s`` shrinks to divide S."""
    b, h, c = q_abs.shape
    r = q_rope.shape[-1]                # rope: (L, B, R, S)
    s_len = lat.shape[2]
    bs, ns, _, _ = tiling.resolve_decode_grid(s_len, block_s=block_s)

    def tile(i, t, lr):
        return jnp.minimum(t, tiling.decode_last_live_tile(lr[i], bs=bs,
                                                           ns=ns))

    q_map = lambda i, t, ly, lr: (i, 0, 0)
    lat_map = lambda i, t, ly, lr: (ly[0], i, tile(i, t, lr), 0)
    rope_map = lambda i, t, ly, lr: (ly[0], i, 0, tile(i, t, lr))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, ns),
        in_specs=[pl.BlockSpec((1, h, c), q_map),
                  pl.BlockSpec((1, h, r), q_map),
                  pl.BlockSpec((1, 1, bs, c), lat_map),
                  pl.BlockSpec((1, 1, r, bs), rope_map)],
        out_specs=pl.BlockSpec((1, h, c), q_map),
        scratch_shapes=[pltpu.VMEM((h,), jnp.float32),       # m
                        pltpu.VMEM((h,), jnp.float32),       # l
                        pltpu.VMEM((h, c), jnp.float32)])    # acc
    kern = functools.partial(_mla_decode_kernel, sm_scale=sm_scale, bs=bs,
                             ns=ns)
    return pl.pallas_call(
        kern, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, c), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret, name=KERNEL_NAME)(
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)),
        jnp.asarray(lengths, jnp.int32),
        q_abs.astype(lat.dtype), q_rope.astype(rope.dtype), lat, rope)

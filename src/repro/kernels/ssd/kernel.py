"""Pallas TPU kernel for the SSD intra-chunk computation (mamba2 hotspot).

The intra-chunk term is the FLOPs-dominant part of SSD — two (Q,N)x(N,Q)
/ (Q,Q)x(Q,P) matmuls per chunk, MXU-shaped when Q, N, P are multiples of
the 128 lane width (we use Q=128 chunks, N=128 state, P=64.. heads).
The O(L) inter-chunk state recurrence is tiny ((N,P) per head) and stays in
a lax.scan outside the kernel.

Grid: (G, T) over folded batch*heads and chunks — fully parallel, no
cross-step scratch.  VMEM per step (Q=128, N=128, P=64):
  C,B blocks 2*Q*N*4 = 128 KiB; x,y Q*P*4 = 32 KiB each; decay Q*Q*4 = 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(c_ref, b_ref, x_ref, da_ref, y_ref, st_ref):
    c = c_ref[...][0, 0]        # (Q, N)
    b = b_ref[...][0, 0]        # (Q, N)
    x = x_ref[...][0, 0]        # (Q, P)
    da = da_ref[...][0, 0]      # (1, Q) inclusive cumulative log-decay
    q = c.shape[0]

    # da arrives as a lane row; the column copy comes from transposing its
    # (Q, Q) broadcast, since Mosaic relayouts whole tiles, not vectors.
    da_row = jnp.broadcast_to(da, (q, q))                 # [i, j] = da[j]
    da_col = da_row.T                                     # [i, j] = da[i]
    scores = jnp.dot(c, b.T, preferred_element_type=jnp.float32)     # (Q, Q)
    decay = jnp.exp(da_col - da_row)
    rows = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    g = jnp.where(rows >= cols, scores * decay, 0.0)
    y_ref[...] = jnp.dot(g, x, preferred_element_type=jnp.float32)[None, None]

    w = jnp.exp(da_row[:, q - 1:] - da_col[:, :1])                   # (Q, 1)
    st_ref[...] = jnp.dot(b.T, x * w,
                          preferred_element_type=jnp.float32)[None, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_pallas(c, b, xbar, acum, *, interpret: bool = False):
    """Shapes as in ref.ssd_chunk_ref: (G,T,Q,N)x2, (G,T,Q,P), (G,T,Q)."""
    g_sz, t, q, n = c.shape
    p = xbar.shape[-1]
    grid = (g_sz, t)
    spec_qn = pl.BlockSpec((1, 1, q, n), lambda i, j: (i, j, 0, 0))
    spec_qp = pl.BlockSpec((1, 1, q, p), lambda i, j: (i, j, 0, 0))
    return pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[spec_qn, spec_qn, spec_qp,
                  pl.BlockSpec((1, 1, 1, q), lambda i, j: (i, j, 0, 0))],
        out_specs=[spec_qp,
                   pl.BlockSpec((1, 1, n, p), lambda i, j: (i, j, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((g_sz, t, q, p), jnp.float32),
                   jax.ShapeDtypeStruct((g_sz, t, n, p), jnp.float32)],
        interpret=interpret,
    )(c, b, xbar, acum.reshape(g_sz, t, 1, q))

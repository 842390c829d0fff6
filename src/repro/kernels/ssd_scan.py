"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

One grid step processes one (batch, head, chunk) cell:
  * the intra-chunk quadratic term  ((C B^T) o L) @ (dt*x)  runs on the MXU
    with the chunk fully VMEM-resident (chunk x state and chunk x head_dim
    tiles, 128-aligned for the default chunk=256 / N=128 / P=64);
  * the running state S (P x N, fp32) lives in VMEM scratch and carries
    across the chunk axis — TPU grids execute the innermost axis
    sequentially, which realizes the inter-chunk recurrence without any HBM
    round-trip for the state.

B/C are group-mapped to heads through the BlockSpec index_map (the SSD
analogue of GQA), so grouped B/C tensors are never materialized per head.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["ssd_scan_pallas"]


def _kernel(x_ref, dt_ref, dtc_ref, a_ref, b_ref, c_ref, y_ref, state_out_ref,
            state, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    a = a_ref[pl.program_id(1)]                   # scalar A for this head
    la_row = dt_ref[0, 0].astype(jnp.float32) * a   # (1, Q) log-decay per step
    la_col = dtc_ref[0, 0].astype(jnp.float32) * a  # (Q, 1) the same, as a column
    bmat = b_ref[0, 0].astype(jnp.float32)       # (Q, N)
    cmat = c_ref[0, 0].astype(jnp.float32)       # (Q, N)

    # inclusive prefix sums as triangular matmuls (the TPU lowering has no
    # cumsum), in both layouts so no vector is transposed in the kernel
    li = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    lj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = li >= lj
    ones = lambda m: jnp.where(m, 1.0, 0.0).astype(jnp.float32)
    hi = jax.lax.Precision.HIGHEST
    cum_col = jax.lax.dot(ones(tri), la_col, precision=hi,
                          preferred_element_type=jnp.float32)       # (Q, 1)
    cum_row = jax.lax.dot(la_row, ones(li <= lj), precision=hi,
                          preferred_element_type=jnp.float32)       # (1, Q)
    # the chunk's whole log-decay, already broadcast along the axis each
    # use needs (Mosaic cannot broadcast a (1, 1) along both axes)
    n = bmat.shape[1]
    cum_end_col = jax.lax.dot(jnp.ones((chunk, chunk), jnp.float32), la_col,
                              precision=hi,
                              preferred_element_type=jnp.float32)   # (Q, 1)
    cum_end_row = jax.lax.dot(la_row, jnp.ones((chunk, n), jnp.float32),
                              precision=hi,
                              preferred_element_type=jnp.float32)   # (1, N)
    dax = x * dtc_ref[0, 0].astype(jnp.float32)   # (Q, P) dt-weighted input

    # intra-chunk: L_ij = exp(cum_i - cum_j) (i >= j)
    L = jnp.where(tri, jnp.exp(cum_col - cum_row), 0.0)
    scores = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32) * L
    y = jax.lax.dot(scores, dax, preferred_element_type=jnp.float32)

    # inter-chunk: y_i += exp(cum_i) * C_i . S_prev^T   (S_prev: (P, N))
    y = y + jnp.exp(cum_col) * jax.lax.dot_general(
        cmat, state[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    # state update: S = exp(cum_end) * S + sum_j exp(cum_end - cum_j) dax_j B_j^T
    w = jnp.exp(cum_end_col - cum_col)            # (Q, 1)
    new_state = state[...] * jnp.exp(cum_end_row) + jax.lax.dot_general(
        dax * w, bmat, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    state[...] = new_state

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _emit_state():
        state_out_ref[0, 0] = new_state.astype(state_out_ref.dtype)


def ssd_scan_pallas(
    x: jax.Array,      # (B, H, S, P)
    dt: jax.Array,     # (B, H, 1, S)
    a: jax.Array,      # (H,)
    bmat: jax.Array,   # (B, G, S, N)
    cmat: jax.Array,   # (B, G, S, N)
    *,
    chunk: int = 256,
    interpret: bool = False,
):
    """Returns (y (B,H,S,P), final_state (B,H,P,N))."""
    b, h, s, p = x.shape
    g, n = bmat.shape[1], bmat.shape[3]
    rep = h // g
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    grid = (b, h, nc)

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda ib, ih, ic: (ib, ih, 0, ic)),
            pl.BlockSpec((1, 1, chunk, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            # the (H,) decay rates sit whole in SMEM; a (1,) block of a
            # rank-1 array is below the TPU's 128-lane tiling
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic, r=rep: (ib, ih // r, ic, 0)),
            pl.BlockSpec((1, 1, chunk, n), lambda ib, ih, ic, r=rep: (ib, ih // r, ic, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, p, n), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
            jax.ShapeDtypeStruct((b, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(x, dt, jnp.swapaxes(dt, 2, 3), a, bmat, cmat)

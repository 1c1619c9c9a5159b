"""Mamba2 SSD chunk-scan Pallas TPU kernel.

Computes, per (batch, head), the chunked state-space-duality recurrence with
the chunk dimension as the innermost sequential grid axis; the running state
[P, N] lives in VMEM scratch across chunk steps (the same carried-scratch
pattern as the flash kernel — the TPU analogue of a persistent-CTA loop).

Per chunk of length Q:
    da       = dt * a                 [Q]     (a read from SMEM per head)
    csum     = cumsum(da)             [Q]     (masked triangular sums)
    L[j,i]   = exp(csum_j - csum_i) for i <= j
    y_intra  = ((C Bᵀ) ⊙ L) @ (dt ⊙ x)
    y_inter  = exp(csum_j) * C_j · state
    state    = exp(csum_Q) * state + Σ_i exp(csum_Q - csum_i) dt_i B_i ⊗ x_i

All matmuls are MXU shapes ([Q,N]x[N,Q], [Q,Q]x[Q,P], [Q,P]ᵀ...); Q=N=128
tiles exactly.  Every block obeys the (8, 128) tiling rule and nothing needs
a lowering Mosaic lacks (cumsum, dynamic slices), so the kernel compiles for
the chip at mamba2-2.7b widths, not only in interpret mode.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_row_ref, dt_col_ref, a_ref, b_ref, c_ref, y_ref,
                state_scr):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    a = a_ref[pl.program_id(1)]                   # this head's scalar (SMEM)
    x = x_ref[0, 0, 0].astype(jnp.float32)        # [Q, P]
    da_row = dt_row_ref[0, 0, 0].astype(jnp.float32) * a   # [1, Q]
    dt_col = dt_col_ref[0, 0, 0].astype(jnp.float32)       # [Q, 1]
    da_col = dt_col * a                                    # [Q, 1]
    b = b_ref[0, 0].astype(jnp.float32)           # [Q, N]
    c = c_ref[0, 0].astype(jnp.float32)           # [Q, N]

    q = x.shape[0]
    iq = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    ik = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    causal = ik <= iq
    # inclusive prefix sums as masked triangular reductions (Mosaic has no
    # cumsum), once along each axis so no transpose is needed
    csum_col = jnp.sum(jnp.where(causal, da_row, 0.0), axis=1,
                       keepdims=True)             # [Q, 1]
    csum_row = jnp.sum(jnp.where(iq <= ik, da_col, 0.0), axis=0,
                       keepdims=True)             # [1, Q]
    total = jnp.sum(da_row, axis=1, keepdims=True)  # [1, 1] chunk total

    l_mat = jnp.where(causal, jnp.exp(csum_col - csum_row), 0.0)  # [Q, Q]

    cb = jax.lax.dot_general(c, b, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # [Q,Q]
    scores = cb * l_mat
    dx = dt_col * x                               # [Q, P]
    y_intra = jax.lax.dot_general(scores, dx, (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)

    state = state_scr[...]                        # [P, N]
    y_inter = jax.lax.dot_general(c, state, (((1,), (1,)), ((), ())),
                                  preferred_element_type=jnp.float32
                                  ) * jnp.exp(csum_col)           # [Q, P]

    weighted_x = dx * jnp.exp(total - csum_col)   # [Q, P]
    s_chunk = jax.lax.dot_general(weighted_x, b, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)  # [P,N]
    state_scr[...] = jnp.exp(total) * state + s_chunk

    y_ref[0, 0, 0] = (y_intra + y_inter).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_in: jax.Array,
             c_in: jax.Array, *, chunk: int = 128,
             interpret: bool = False) -> jax.Array:
    """x: [B,S,H,P]; dt: [B,S,H] (post-softplus); a: [H]; b_in/c_in: [B,S,N].

    Returns y [B,S,H,P].  S must be a multiple of ``chunk``.
    """
    bsz, s, h, p = x.shape
    n = b_in.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk

    xr = x.transpose(0, 2, 1, 3).reshape(bsz, h, nc, chunk, p)
    dtr = dt.transpose(0, 2, 1).reshape(bsz, h, nc, chunk)
    br = b_in.reshape(bsz, nc, chunk, n)
    cr = c_in.reshape(bsz, nc, chunk, n)

    grid = (bsz, h, nc)
    out = pl.pallas_call(
        _ssd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, p),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            # dt twice, as a row and as a column: both blocks end in a
            # full-size unit dim, which the (8, 128) tiling rule accepts
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, n),
                         lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, chunk, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, nc, chunk, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
    )(xr, dtr[:, :, :, None, :], dtr[..., None], a.astype(jnp.float32), br,
      cr)
    return out.reshape(bsz, h, s, p).transpose(0, 2, 1, 3)

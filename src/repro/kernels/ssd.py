"""Mamba2 SSD chunked-scan kernel (Pallas).

Grid: (batch, heads, chunks) with chunks 'arbitrary' (sequential).  Per
chunk the kernel computes the intra-chunk dual quadratic form on the MXU
(two (Q,Q)x(Q,P) matmuls) and carries the (P,N) inter-chunk SSM state in
f32 VMEM scratch — the same math as models/ssm.ssd_chunked, but the decay
matrix never leaves VMEM.

Inputs are pre-projected per head and laid out head-major so that every
block's last two dimensions are a (chunk, width) tile: xd = x*dt
(B,H,S,P); B/C (B,S,N) shared across heads; and the within-chunk
cumulative decay cum(dt*A) twice, as a (B,H,S,1) column and a (B,H,1,S)
row, so the (Q,Q) segment sums are one broadcast subtraction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def _ssd_kernel(xd_ref, cc_ref, cr_ref, b_ref, c_ref, y_ref, hlast_ref,
                state_scr, *, block_q: int, n_chunks: int):
    cb = pl.program_id(2)

    @pl.when(cb == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    xd = xd_ref[0, 0]                                 # (Q, P)
    cum_c = cc_ref[0, 0]                              # (Q, 1)
    cum_r = cr_ref[0, 0]                              # (1, Q)
    bm = b_ref[0].astype(jnp.float32)                 # (Q, N)
    cm = c_ref[0].astype(jnp.float32)                 # (Q, N)

    rows = lax.broadcasted_iota(jnp.int32, (block_q, block_q), 0)
    cols = lax.broadcasted_iota(jnp.int32, (block_q, block_q), 1)
    decay = jnp.exp(jnp.where(cols <= rows, cum_c - cum_r, -jnp.inf))
    w = lax.dot_general(cm, bm, _NT,
                        preferred_element_type=jnp.float32) * decay
    y_intra = jnp.dot(w, xd, preferred_element_type=jnp.float32)

    state = state_scr[...]                            # (P, N)
    y_inter = jnp.exp(cum_c) * lax.dot_general(
        cm, state, _NT, preferred_element_type=jnp.float32)   # (Q, P)

    # the chunk's total decay, as a reduction: a (1, 1) slice of the last
    # lane has a layout Mosaic cannot broadcast
    lane = lax.broadcasted_iota(jnp.int32, cum_r.shape, 1)
    last = jnp.sum(jnp.where(lane == block_q - 1, cum_r, 0.0), axis=1,
                   keepdims=True)                     # (1, 1)
    tail = jnp.exp(last - cum_c)                      # (Q, 1)
    s_c = lax.dot_general(xd * tail, bm, _TN,
                          preferred_element_type=jnp.float32)  # (P, N)
    state = jnp.exp(last) * state + s_c
    state_scr[...] = state

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    @pl.when(cb == n_chunks - 1)
    def _final():
        hlast_ref[0, 0] = state.astype(hlast_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_pallas(x, dt, A, Bm, Cm, chunk: int = 128,
               interpret: bool | None = None):
    """x (B,S,H,P), dt (B,S,H), A (H,), Bm/Cm (B,S,N) -> (y, final_state).

    Matches models/ssm.ssd_chunked (the oracle).
    """
    interpret = resolve_interpret(interpret)
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0
    nc = s // chunk
    grid = (b, h, nc)

    xd = (x * dt[..., None]).astype(jnp.float32).transpose(0, 2, 1, 3)
    da = (dt * A).astype(jnp.float32).reshape(b, nc, chunk, h)
    cum = jnp.cumsum(da, axis=2).reshape(b, s, h).transpose(0, 2, 1)

    xd_spec = pl.BlockSpec((1, 1, chunk, p),
                           lambda bb, hh, cc: (bb, hh, cc, 0))
    col_spec = pl.BlockSpec((1, 1, chunk, 1),
                            lambda bb, hh, cc: (bb, hh, cc, 0))
    row_spec = pl.BlockSpec((1, 1, 1, chunk),
                            lambda bb, hh, cc: (bb, hh, 0, cc))
    bc_spec = pl.BlockSpec((1, chunk, n),
                           lambda bb, hh, cc: (bb, cc, 0))
    hl_spec = pl.BlockSpec((1, 1, p, n),
                           lambda bb, hh, cc: (bb, hh, 0, 0))

    y, hlast = pl.pallas_call(
        functools.partial(_ssd_kernel, block_q=chunk, n_chunks=nc),
        grid=grid,
        in_specs=[xd_spec, col_spec, row_spec, bc_spec, bc_spec],
        out_specs=[xd_spec, hl_spec],
        out_shape=[jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
                   jax.ShapeDtypeStruct((b, h, p, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(xd, cum[..., None], cum[:, :, None, :], Bm, Cm)
    return y.transpose(0, 2, 1, 3), hlast

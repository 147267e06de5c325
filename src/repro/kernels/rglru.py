"""RG-LRU blocked scan kernel (Pallas).

The linear recurrence h_t = a_t h_{t-1} + b_t is elementwise across the
width dimension, so the natural TPU layout is: grid (batch, width_blocks,
time_blocks) with time 'arbitrary' (sequential), a (1, block_w) f32 carry in
VMEM scratch, and an in-kernel fori_loop over the block's time steps running
on the VPU.  Each step reads and writes one row of the block through
``pl.ds``, so the loop index never becomes a value-level dynamic slice.
Width blocks are lane-aligned (multiples of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _rglru_kernel(loga_ref, b_ref, h_ref, carry_scr, *, block_t: int):
    tb = pl.program_id(2)

    @pl.when(tb == 0)
    def _init():
        carry_scr[...] = jnp.zeros_like(carry_scr)

    def step(t, h):
        row = pl.ds(t, 1)
        a = jnp.exp(loga_ref[0, row, :].astype(jnp.float32))   # (1, bw)
        h = a * h + b_ref[0, row, :].astype(jnp.float32)
        h_ref[0, row, :] = h.astype(h_ref.dtype)
        return h

    carry_scr[...] = jax.lax.fori_loop(0, block_t, step, carry_scr[...])


@functools.partial(jax.jit, static_argnames=("block_w", "block_t",
                                             "interpret"))
def rglru_pallas(log_a, b, block_w: int = 512, block_t: int = 128,
                 interpret: bool | None = None):
    """log_a, b (B,S,W) f32 -> h (B,S,W) f32."""
    interpret = resolve_interpret(interpret)
    bsz, s, w = log_a.shape
    block_w = min(block_w, w)
    block_t = min(block_t, s)
    assert w % block_w == 0 and s % block_t == 0
    grid = (bsz, w // block_w, s // block_t)

    spec = pl.BlockSpec((1, block_t, block_w),
                        lambda bb, wb, tb: (bb, tb, wb))

    return pl.pallas_call(
        functools.partial(_rglru_kernel, block_t=block_t),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        interpret=interpret,
        name="rglru_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(log_a, b)

"""Blocked causal/GQA flash attention (Pallas, TPU-targeted).

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
'arbitrary' (sequential) — the online-softmax state (m, l, acc) lives in
VMEM scratch and is carried across kv-block steps; the output block is
written on the last kv step.  GQA maps q-head h to kv-head h // group in the
k/v BlockSpec index maps, so kv blocks are fetched once per group.

Causal + sliding-window masking is applied per (q_block, kv_block) tile;
fully-masked tiles still visit the grid (simplicity > the ~2x skip win;
the hillclimb log covers the trade-off).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, scale: float, causal: bool, window: int,
                 block_q: int, block_k: int, n_kv_blocks: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)

    @pl.when(kb == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)               # (bq, d)
    k = k_ref[0, 0].astype(jnp.float32)               # (bk, d)
    v = v_ref[0, 0].astype(jnp.float32)
    s = (q @ k.T) * scale                             # (bq, bk)

    qpos = qb * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 0)
    kpos = kb * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                   (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= (qpos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + p @ v
    m_scr[...] = m_new

    @pl.when(kb == n_kv_blocks - 1)
    def _out():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)[:, None]
                       ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """q (B,S,Hq,D); k,v (B,T,Hkv,D) -> (B,S,Hq,D)."""
    interpret = resolve_interpret(interpret)
    b, s, hq, d = q.shape
    t, hkv = k.shape[1], k.shape[2]
    g = hq // hkv
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0
    grid = (b, hq, s // block_q, t // block_k)

    qt = q.transpose(0, 2, 1, 3)                      # (B,Hq,S,D)
    kt = k.transpose(0, 2, 1, 3)                      # (B,Hkv,T,D)
    vt = v.transpose(0, 2, 1, 3)

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bb, h, qb, kb: (bb, h, qb, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d),
                          lambda bb, h, qb, kb: (bb, h // g, kb, 0))
    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bb, h, qb, kb: (bb, h, qb, 0))

    kernel = functools.partial(
        _attn_kernel, scale=1.0 / math.sqrt(d), causal=causal,
        window=window, block_q=block_q, block_k=block_k,
        n_kv_blocks=grid[3])

    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, k_spec, k_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q,), jnp.float32),
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_attention",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)

"""Blocked causal/GQA flash attention (Pallas, TPU-targeted).

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension
'arbitrary' (sequential) — the online-softmax state (m, l, acc) lives in
VMEM scratch and is carried across kv-block steps.  GQA maps q-head h to
kv-head h // group in the k/v BlockSpec index maps; the head axis of the
grid is the q head, so each kv block is fetched once per q head.

Causal and sliding-window masks remove whole tiles: q block ``qb``
attends only to the kv blocks ``live_kv_blocks`` gives, from the first
that holds a key inside the window to the last that holds a key at or
before its last query.  The other steps of the kv axis compute nothing
(``pl.when``), and the k/v index maps clamp them to the nearest live
block, so the pipeline sees an unchanged block index and issues no DMA
for them.  The state is reset on the first live step and the output
block written on the last.  Only a tile that the diagonal or the
window's edge crosses builds the position mask; a tile wholly inside
both computes its scores unmasked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def live_kv_blocks(qb, *, causal: bool, window: int, block_q: int,
                   block_k: int, n_kv_blocks: int, xp=jnp):
    """(first, last) kv block that q block ``qb`` attends to.

    ``xp`` is ``jnp`` for the grid index the kernel and its index maps
    see, ``np`` for block numbers known when the call is traced.
    """
    q0 = qb * block_q
    first = xp.maximum(q0 - window + 1, 0) // block_k if window else 0
    last = (xp.minimum((q0 + block_q - 1) // block_k, n_kv_blocks - 1)
            if causal else n_kv_blocks - 1)
    return first, last


def kv_block(qb, kb, **blocks):
    """The kv block that grid step (qb, kb) holds: ``kb`` on a live step,
    else the nearest live one, so a skipped step repeats the block index
    of the step before or after it and the pipeline fetches nothing."""
    first, last = live_kv_blocks(qb, **blocks)
    return jnp.minimum(jnp.maximum(kb, first), last)


def skipped_share(s: int, t: int, *, causal: bool, window: int,
                  block_q: int, block_k: int) -> float:
    """Share of the (q block, kv block) grid steps the kernel skips."""
    nq, nk = s // block_q, t // block_k
    first, last = live_kv_blocks(np.arange(nq), causal=causal,
                                 window=window, block_q=block_q,
                                 block_k=block_k, n_kv_blocks=nk, xp=np)
    live = np.broadcast_to(np.asarray(last) - first + 1, (nq,)).sum()
    return float(1 - live / (nq * nk))


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                 *, scale: float, causal: bool, window: int,
                 block_q: int, block_k: int, n_kv_blocks: int):
    qb = pl.program_id(2)
    kb = pl.program_id(3)
    first, last = live_kv_blocks(qb, causal=causal, window=window,
                                 block_q=block_q, block_k=block_k,
                                 n_kv_blocks=n_kv_blocks)

    @pl.when(kb == first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def tile(masked: bool):
        q = q_ref[0, 0].astype(jnp.float32)           # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)           # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = (q @ k.T) * scale                         # (bq, bk)

        if masked:
            qpos = qb * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            kpos = kb * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
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

    if not (causal or window):
        tile(masked=False)                            # every step is live
    else:
        # the tile holds a masked position: a key after its first query
        # (the diagonal crosses it) or one a window or more before its last
        q_lo, k_lo = qb * block_q, kb * block_k
        edge = False
        if causal:
            edge = k_lo + block_k - 1 > q_lo
        if window:
            edge = edge | (q_lo + block_q - 1 - k_lo >= window)
        live = (kb >= first) & (kb <= last)
        pl.when(live & edge)(lambda: tile(masked=True))
        pl.when(live & jnp.logical_not(edge))(lambda: tile(masked=False))

    @pl.when(kb == last)
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
    blocks = dict(causal=causal, window=window, block_q=block_q,
                  block_k=block_k, n_kv_blocks=grid[3])

    qt = q.transpose(0, 2, 1, 3)                      # (B,Hq,S,D)
    kt = k.transpose(0, 2, 1, 3)                      # (B,Hkv,T,D)
    vt = v.transpose(0, 2, 1, 3)

    def kv_index(bb, h, qb, kb):
        return bb, h // g, kv_block(qb, kb, **blocks), 0

    q_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bb, h, qb, kb: (bb, h, qb, 0))
    k_spec = pl.BlockSpec((1, 1, block_k, d), kv_index)
    o_spec = pl.BlockSpec((1, 1, block_q, d),
                          lambda bb, h, qb, kb: (bb, h, qb, 0))

    kernel = functools.partial(_attn_kernel, scale=1.0 / math.sqrt(d),
                               **blocks)

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

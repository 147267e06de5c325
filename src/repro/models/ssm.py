"""Mamba2 (SSD — state-space duality) block, pure JAX.

Implements the chunked SSD algorithm (arXiv:2405.21060): the sequence is
split into chunks; within a chunk the quadratic dual form runs on the MXU,
across chunks a small recurrent state (H, P, N) is carried by ``lax.scan``.
The 'pallas' destination routes the chunk computation to the SSD kernel in
``repro/kernels/ssd.py`` (same math, VMEM-tiled).

Decode is the pure recurrence: ``h = exp(dt·A)·h + dt·B·x``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, PlanConfig
from repro.models.layers import _normal, pdtype, cdtype


def init_mamba2(key, cfg: ArchConfig):
    d, di, n, h, k = (cfg.d_model, cfg.d_inner, cfg.ssm_state,
                      cfg.ssm_nheads, cfg.ssm_conv)
    dt = pdtype(cfg.plan)
    ks = jax.random.split(key, 4)
    in_width = 2 * di + 2 * n + h            # z, x, B, C, dt
    p = {
        "in_proj": _normal(ks[0], (d, in_width), dt, 1 / math.sqrt(d)),
        "conv_w": _normal(ks[1], (k, di + 2 * n), dt, 1 / math.sqrt(k)),
        "conv_b": jnp.zeros((di + 2 * n,), dt),
        "A_log": jnp.zeros((h,), dt),        # A = -exp(A_log) = -1
        "D": jnp.ones((h,), dt),
        "dt_bias": jnp.zeros((h,), dt),
        "norm": jnp.ones((di,), dt),
        "out_proj": _normal(ks[3], (di, d), dt, 1 / math.sqrt(di)),
    }
    return p


def _causal_conv(x, w, b, state=None):
    """Depthwise causal conv. x (B,S,C), w (K,C). state: (B,K-1,C) or None."""
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((x.shape[0], k - 1, x.shape[2]), x.dtype)
    else:
        pad = state.astype(x.dtype)
    xp = jnp.concatenate([pad, x], axis=1)
    out = jnp.zeros_like(x)
    for i in range(k):
        out = out + xp[:, i:i + x.shape[1]] * w[i]
    new_state = xp[:, -(k - 1):] if k > 1 else None
    return out + b, new_state


def _split_proj(zxbcdt, cfg: ArchConfig):
    di, n, h = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * n]
    dt = zxbcdt[..., 2 * di + 2 * n:]
    assert dt.shape[-1] == h
    return z, xbc, dt


def ssd_chunked(x, dt, A, Bm, Cm, chunk: int):
    """Chunked SSD scan.

    x (B,S,H,P)  dt (B,S,H)  A (H,)  Bm,Cm (B,S,N)  ->  y (B,S,H,P)
    Scans over chunks so only one (B,H,Q,Q) decay block is live at a time.
    """
    b, s, h, p = x.shape
    n = Bm.shape[-1]
    q = chunk if s % chunk == 0 else math.gcd(s, chunk) or s
    nc = s // q

    dA = dt * A                                            # (B,S,H) negative
    xd = x * dt[..., None]                                 # dt-weighted input

    def reshape_c(a):
        return a.reshape(b, nc, q, *a.shape[2:]).transpose(1, 0, *range(2, a.ndim + 1))

    xs = (reshape_c(xd), reshape_c(dA),
          reshape_c(Bm), reshape_c(Cm))

    def body(hstate, inputs):
        xdc, dac, bc, cc = inputs                          # (B,Q,...) per chunk
        cum = jnp.cumsum(dac.astype(jnp.float32), axis=1)  # (B,Q,H)
        # intra-chunk (dual quadratic form)
        cb = jnp.einsum("bsn,brn->bsr", cc.astype(jnp.float32),
                        bc.astype(jnp.float32))            # (B,Q,Q)
        # mask before exp: above the diagonal the segment sum is positive
        # and overflows at long chunks (inf * 0 would poison the sum)
        tri = jnp.tril(jnp.ones((q, q), bool))[None, :, :, None]
        decay = jnp.exp(jnp.where(tri, cum[:, :, None, :] - cum[:, None, :, :],
                                  -jnp.inf))               # (B,Q,Q,H)
        w = cb[..., None] * decay                          # (B,Q,Q,H)
        y_intra = jnp.einsum("bsrh,brhp->bshp", w, xdc.astype(jnp.float32))
        # contribution of the carried state
        y_inter = jnp.einsum("bsn,bhpn,bsh->bshp",
                             cc.astype(jnp.float32), hstate,
                             jnp.exp(cum))
        # next chunk state
        tail = jnp.exp(cum[:, -1:, :] - cum)               # (B,Q,H)
        s_c = jnp.einsum("bshp,bsn,bsh->bhpn",
                         xdc.astype(jnp.float32), bc.astype(jnp.float32), tail)
        hstate = jnp.exp(cum[:, -1, :])[..., None, None] * hstate + s_c
        return hstate, (y_intra + y_inter).astype(x.dtype)

    h0 = jnp.zeros((b, h, p, n), jnp.float32)
    hstate, yc = lax.scan(body, h0, xs)
    y = yc.transpose(1, 0, 2, 3, 4).reshape(b, s, h, p)
    return y, hstate


def run_mamba2(params, x, cfg: ArchConfig, plan: PlanConfig,
               cache=None, decode=False):
    """Mamba2 mixing block. Returns (y, new_cache)."""
    dt_c = cdtype(plan)
    di, n, h, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_nheads, cfg.ssm_headdim
    zxbcdt = jnp.einsum("bsd,dw->bsw", x, params["in_proj"].astype(dt_c))
    z, xbc, dtt = _split_proj(zxbcdt, cfg)
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    dt_act = jax.nn.softplus(dtt.astype(jnp.float32)
                             + params["dt_bias"].astype(jnp.float32))

    conv_state = cache.get("conv") if cache else None
    if decode:
        xbc, new_conv = _causal_conv(xbc, params["conv_w"].astype(dt_c),
                                     params["conv_b"].astype(dt_c), conv_state)
        xin = jax.nn.silu(xbc[..., :di]).reshape(x.shape[0], 1, h, p)
        Bm = xbc[..., di:di + n]
        Cm = xbc[..., di + n:]
        hs = cache["ssm"]                                   # (B,H,P,N)
        da = jnp.exp(dt_act[:, 0, :] * A)                   # (B,H)
        dbx = jnp.einsum("bhp,bn,bh->bhpn",
                         xin[:, 0].astype(jnp.float32), Bm[:, 0].astype(jnp.float32),
                         dt_act[:, 0])
        hs = da[..., None, None] * hs + dbx
        y = jnp.einsum("bn,bhpn->bhp", Cm[:, 0].astype(jnp.float32), hs)
        y = y + params["D"].astype(jnp.float32)[None, :, None] * xin[:, 0].astype(jnp.float32)
        y = y[:, None].astype(dt_c)                         # (B,1,H,P)
        new_cache = {"conv": new_conv, "ssm": hs}
    else:
        xbc, new_conv = _causal_conv(xbc, params["conv_w"].astype(dt_c),
                                     params["conv_b"].astype(dt_c), None)
        xin = jax.nn.silu(xbc[..., :di])
        Bm = xbc[..., di:di + n]
        Cm = xbc[..., di + n:]
        xh = xin.reshape(x.shape[0], x.shape[1], h, p)
        if plan.ssm_impl == "pallas":
            from repro.kernels import ops as kops
            y, hstate = kops.ssd(xh, dt_act, A, Bm, Cm, chunk=cfg.ssm_chunk)
        else:
            y, hstate = ssd_chunked(xh, dt_act, A, Bm, Cm, cfg.ssm_chunk)
        y = y + params["D"].astype(y.dtype)[None, None, :, None] * xh
        new_cache = None
        if cache is not None:
            new_cache = {"conv": new_conv, "ssm": hstate}

    y = y.reshape(x.shape[0], -1, di)
    # gated RMSNorm (mamba2)
    y32 = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    y32 = y32 * lax.rsqrt(jnp.mean(jnp.square(y32), -1, keepdims=True) + 1e-6)
    y = (y32 * params["norm"].astype(jnp.float32)).astype(dt_c)
    out = jnp.einsum("bsw,wd->bsd", y, params["out_proj"].astype(dt_c))
    return out, new_cache


def init_ssm_cache(cfg: ArchConfig, batch: int, dtype=jnp.float32):
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv - 1,
                           cfg.d_inner + 2 * cfg.ssm_state), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_nheads, cfg.ssm_headdim,
                          cfg.ssm_state), jnp.float32),
    }

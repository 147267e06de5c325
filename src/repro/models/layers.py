"""Core transformer layers: norms, RoPE, GQA attention, MLP, MoE.

Every temporal-mixing site supports multiple *destinations* (paper §3):
  attention : 'xla' (naive), 'xla_chunked' (online-softmax scan), 'pallas'
  mlp       : 'xla', 'pallas' (fused swiglu)
  moe       : 'xla' (sort-based capacity dispatch)

All functions take (params, x, ...) with params a plain dict pytree; weights
live in ``cfg.plan.param_dtype`` and compute happens in
``cfg.plan.compute_dtype`` with f32 softmax/norm accumulation.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ArchConfig, PlanConfig

NEG_INF = -1e30


def cdtype(plan: PlanConfig):
    return jnp.dtype(plan.compute_dtype)


def pdtype(plan: PlanConfig):
    return jnp.dtype(plan.param_dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def init_norm(cfg: ArchConfig, width: Optional[int] = None):
    w = width or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((w,), pdtype(cfg.plan)),
                "bias": jnp.zeros((w,), pdtype(cfg.plan))}
    return {"scale": jnp.ones((w,), pdtype(cfg.plan))}


def apply_norm(params, x, cfg: ArchConfig):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mu) * lax.rsqrt(var + 1e-6)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    else:
        ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * lax.rsqrt(ms + 1e-6) * params["scale"].astype(jnp.float32)
    return y.astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope(x, positions, theta: float):
    """x: (..., S, H, D) with positions (..., S) or (S,)."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., :, None].astype(jnp.float32) * freq  # (..., S, half)
    cos = jnp.cos(ang)[..., :, None, :]
    sin = jnp.sin(ang)[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense projections
# ---------------------------------------------------------------------------


def _normal(key, shape, dtype, scale):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def init_attention(key, cfg: ArchConfig):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    dt = pdtype(cfg.plan)
    ks = jax.random.split(key, 4)
    s_in = 1.0 / math.sqrt(d)
    s_out = 1.0 / math.sqrt(hq * dh)
    p = {
        "wq": _normal(ks[0], (d, hq, dh), dt, s_in),
        "wk": _normal(ks[1], (d, hkv, dh), dt, s_in),
        "wv": _normal(ks[2], (d, hkv, dh), dt, s_in),
        "wo": _normal(ks[3], (hq, dh, d), dt, s_out),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq, dh), dt)
        p["bk"] = jnp.zeros((hkv, dh), dt)
        p["bv"] = jnp.zeros((hkv, dh), dt)
    return p


def _qkv(params, x, cfg: ArchConfig, positions):
    dt = cdtype(cfg.plan)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    return q, k, v


def _group(q, n_kv: int):
    """(B,S,Hq,D) -> (B,S,Hkv,G,D)."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, n_kv, hq // n_kv, d)


def _mask(qpos, kpos, causal: bool, window: int):
    """qpos (Q,), kpos (K,) -> (Q,K) additive f32 mask."""
    m = jnp.zeros((qpos.shape[0], kpos.shape[0]), jnp.float32)
    if causal:
        m = jnp.where(kpos[None, :] <= qpos[:, None], m, NEG_INF)
    if window:
        m = jnp.where(qpos[:, None] - kpos[None, :] < window, m, NEG_INF)
    return m


def attention_naive(q, k, v, qpos, kpos, causal=True, window=0):
    """Grouped full attention. q (B,S,Hq,D); k,v (B,T,Hkv,D)."""
    n_kv = k.shape[2]
    qg = _group(q, n_kv)
    scale = 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bsngd,btnd->bngst", qg, k).astype(jnp.float32) * scale
    s = s + _mask(qpos, kpos, causal, window)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bngst,btnd->bsngd", p, v)
    return o.reshape(q.shape)


def attention_chunked(q, k, v, qpos, kpos, causal=True, window=0, chunk=1024):
    """Online-softmax attention, scanned over KV chunks (memory-bounded).

    This is the 'xla_chunked' destination: same math as flash attention but
    expressed in stock XLA ops; the Pallas kernel is the 'pallas' rung.
    """
    b, s_q, hq, d = q.shape
    t = k.shape[1]
    if t % chunk != 0:
        chunk = math.gcd(t, chunk) or t
    n_kv = k.shape[2]
    qg = _group(q, n_kv)
    scale = 1.0 / math.sqrt(d)

    kc = k.reshape(b, t // chunk, chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, t // chunk, chunk, n_kv, d).transpose(1, 0, 2, 3, 4)
    pc = kpos.reshape(t // chunk, chunk)

    def body(carry, xs):
        m, l, acc = carry
        kb, vb, pb = xs
        s = jnp.einsum("bsngd,btnd->bngst", qg, kb).astype(jnp.float32) * scale
        s = s + _mask(qpos, pb, causal, window)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bngst,btnd->bngsd", p.astype(q.dtype), vb
        ).astype(jnp.float32)
        return (m_new, l, acc), None

    g = hq // n_kv
    m0 = jnp.full((b, n_kv, g, s_q), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, s_q), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, s_q, d), jnp.float32)
    (m, l, acc), _ = lax.scan(body, (m0, l0, a0), (kc, vc, pc))
    o = acc / jnp.maximum(l, 1e-30)[..., None]
    return o.transpose(0, 3, 1, 2, 4).reshape(b, s_q, hq, d).astype(q.dtype)


def _kv_quant(x):
    """bf16 (B,S,H,D) -> (int8 values, f32 scale (B,S,H,1))."""
    s = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                keepdims=True) / 127.0 + 1e-9
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / s), -127, 127)
    return q.astype(jnp.int8), s.astype(jnp.float32)


def _kv_dequant(q, s, dtype):
    return (q.astype(jnp.float32) * s).astype(dtype)


def run_attention(params, x, cfg: ArchConfig, plan: PlanConfig, positions,
                  cache=None, decode=False, window=0):
    """Temporal-mixing site. Returns (y, new_cache).

    The KV cache is a rolling buffer of length T (= min(window, seq) for
    local attention, full seq otherwise) with an explicit per-slot position
    array ``kpos`` (-1 = empty); decode writes slot ``pos % T``.  Keys are
    stored post-RoPE.  ``kv_cache_dtype='int8'`` stores per-(pos, head)
    absmax-quantized values + f32 scales (halves cache bytes AND the
    cross-TP cache all-gather payload — a §Perf lever).
    """
    q, k, v = _qkv(params, x, cfg, positions)
    causal = not cfg.is_encoder
    int8_cache = cache is not None and cache["k"].dtype == jnp.int8

    if decode:
        ck, cv, kpos = cache["k"], cache["v"], cache["kpos"]
        t = ck.shape[1]
        pos = positions[0]
        slot = lax.rem(pos, t)
        with jax.named_scope("kv_write"):
            if int8_cache:
                kq, ks = _kv_quant(k)
                vq, vs = _kv_quant(v)
                ck = lax.dynamic_update_slice(ck, kq, (0, slot, 0, 0))
                cv = lax.dynamic_update_slice(cv, vq, (0, slot, 0, 0))
                k_sc = lax.dynamic_update_slice(cache["k_scale"], ks,
                                                (0, slot, 0, 0))
                v_sc = lax.dynamic_update_slice(cache["v_scale"], vs,
                                                (0, slot, 0, 0))
            else:
                ck = lax.dynamic_update_slice(ck, k.astype(ck.dtype),
                                              (0, slot, 0, 0))
                cv = lax.dynamic_update_slice(cv, v.astype(cv.dtype),
                                              (0, slot, 0, 0))
            kpos = lax.dynamic_update_slice(kpos, pos[None], (slot,))
        if int8_cache:
            kk = _kv_dequant(ck, k_sc, q.dtype)
            vv = _kv_dequant(cv, v_sc, q.dtype)
        else:
            kk, vv = ck.astype(q.dtype), cv.astype(q.dtype)
        valid = (kpos >= 0) & (kpos <= pos)
        kpos_m = jnp.where(valid, kpos, pos + t + 10)  # fails causal rule
        qpos = jnp.full((q.shape[1],), pos)
        if plan.attn_impl == "xla" or t <= plan.attn_chunk:
            o = attention_naive(q, kk, vv, qpos, kpos_m, True, window)
        else:
            o = attention_chunked(q, kk, vv, qpos, kpos_m, True, window,
                                  plan.attn_chunk)
        new_cache = {"k": ck, "v": cv, "kpos": kpos}
        if int8_cache:
            new_cache["k_scale"] = k_sc
            new_cache["v_scale"] = v_sc
    else:
        kpos = qpos = positions
        impl = plan.attn_impl
        if impl == "pallas":
            from repro.kernels import ops as kops
            o = kops.flash_attention(q, k, v, causal=causal, window=window)
        elif impl == "xla_chunked" and x.shape[1] > plan.attn_chunk:
            o = attention_chunked(q, k, v, qpos, kpos, causal, window,
                                  plan.attn_chunk)
        else:
            o = attention_naive(q, k, v, qpos, kpos, causal, window)
        new_cache = None
        if cache is not None:  # prefill: keep the last T positions
            with jax.named_scope("kv_write"):
                t = cache["k"].shape[1]
                s = k.shape[1]
                ktail, vtail = k[:, -t:], v[:, -t:]
                tailpos = jnp.arange(max(s - t, 0), s, dtype=jnp.int32)
                slots = tailpos % t
                if int8_cache:
                    kq, ks = _kv_quant(ktail)
                    vq, vs = _kv_quant(vtail)
                    new_cache = {
                        "k": cache["k"].at[:, slots].set(kq),
                        "v": cache["v"].at[:, slots].set(vq),
                        "k_scale": cache["k_scale"].at[:, slots].set(ks),
                        "v_scale": cache["v_scale"].at[:, slots].set(vs),
                        "kpos": cache["kpos"].at[slots].set(tailpos),
                    }
                else:
                    new_cache = {
                        "k": cache["k"].at[:, slots].set(
                            ktail.astype(cache["k"].dtype)),
                        "v": cache["v"].at[:, slots].set(
                            vtail.astype(cache["v"].dtype)),
                        "kpos": cache["kpos"].at[slots].set(tailpos),
                    }

    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(o.dtype))
    return y, new_cache


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None):
    d, f = cfg.d_model, d_ff or cfg.d_ff
    dt = pdtype(cfg.plan)
    ks = jax.random.split(key, 3)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    if cfg.act == "swiglu":
        return {
            "wi": _normal(ks[0], (d, f), dt, s_in),
            "wg": _normal(ks[1], (d, f), dt, s_in),
            "wo": _normal(ks[2], (f, d), dt, s_out),
        }
    return {
        "wi": _normal(ks[0], (d, f), dt, s_in),
        "bi": jnp.zeros((f,), dt),
        "wo": _normal(ks[2], (f, d), dt, s_out),
        "bo": jnp.zeros((d,), dt),
    }


def run_mlp(params, x, cfg: ArchConfig, plan: PlanConfig, layer=None):
    """With ``layer`` (an integer scalar) the swiglu weights are stacked
    per layer, (L,d,f) and (L,f,d), in the compute dtype, and the Pallas
    kernel runs their layer ``layer`` where it lies in the stacks."""
    dt = cdtype(plan)
    if layer is not None:
        from repro.kernels import ops as kops
        return kops.fused_swiglu(x, params["wi"], params["wg"], params["wo"],
                                 layer)
    if cfg.act == "swiglu":
        if plan.mlp_impl == "pallas":
            from repro.kernels import ops as kops
            return kops.fused_swiglu(x, params["wi"].astype(dt),
                                     params["wg"].astype(dt),
                                     params["wo"].astype(dt))
        h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(dt))
        g = jnp.einsum("bsd,df->bsf", x, params["wg"].astype(dt))
        h = jax.nn.silu(g) * h
        return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dt))
    h = jnp.einsum("bsd,df->bsf", x, params["wi"].astype(dt)) + params["bi"].astype(dt)
    h = jax.nn.gelu(h)
    return jnp.einsum("bsf,fd->bsd", h, params["wo"].astype(dt)) + params["bo"].astype(dt)


# ---------------------------------------------------------------------------
# MoE — sort-based capacity dispatch (TPU-friendly, O(T·k) memory)
# ---------------------------------------------------------------------------


def init_moe(key, cfg: ArchConfig):
    assert cfg.moe is not None
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.d_ff_expert
    dt = pdtype(cfg.plan)
    ks = jax.random.split(key, 4)
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    return {
        "router": _normal(ks[0], (d, e), dt, s_in),
        "wi": _normal(ks[1], (e, d, f), dt, s_in),
        "wg": _normal(ks[2], (e, d, f), dt, s_in),
        "wo": _normal(ks[3], (e, f, d), dt, s_out),
    }


def moe_capacity(cfg: ArchConfig, n_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(m.top_k * n_tokens / m.n_experts * m.capacity_factor))
    return max(8, -(-c // 8) * 8)  # round up to 8 for TPU-friendly shapes


def run_moe(params, x, cfg: ArchConfig, plan: PlanConfig):
    """Token-choice top-k routing with capacity; returns (y, aux_loss)."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    k = m.top_k
    e = m.n_experts
    cap = moe_capacity(cfg, t)
    dt = cdtype(plan)

    xt = x.reshape(t, d)
    logits = jnp.einsum("td,de->te", xt, params["router"].astype(dt)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = lax.top_k(probs, k)                     # (t,k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)

    # load-balance auxiliary loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32), axis=0)
    density_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(density * density_prob)

    # --- capacity assignment via sort (no (T,E,C) dense dispatch tensor) ----
    eid = idx.reshape(-1)                                # (t*k,)
    order = jnp.argsort(eid)                             # stable
    sorted_eid = eid[order]
    run_start = jnp.searchsorted(sorted_eid, jnp.arange(e), side="left")
    pos_sorted = jnp.arange(t * k) - run_start[sorted_eid]
    pos = jnp.zeros((t * k,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))
    keep = pos < cap
    slot = jnp.where(keep, eid * cap + pos, e * cap)     # dropped -> overflow slot

    tok = jnp.repeat(jnp.arange(t), k)                   # token of each assignment
    buf = jnp.zeros((e * cap + 1, d), dt).at[slot].add(xt[tok].astype(dt))
    buf = buf[:-1].reshape(e, cap, d)

    # expert FFN (vmapped over experts; EP shards the leading axis)
    h = jnp.einsum("ecd,edf->ecf", buf, params["wi"].astype(dt))
    g = jnp.einsum("ecd,edf->ecf", buf, params["wg"].astype(dt))
    h = jax.nn.silu(g) * h
    yb = jnp.einsum("ecf,efd->ecd", h, params["wo"].astype(dt))

    # combine
    yfl = jnp.concatenate([yb.reshape(e * cap, d),
                           jnp.zeros((1, d), dt)], axis=0)
    y_assign = yfl[slot] * (gate.reshape(-1, 1).astype(dt) * keep[:, None])
    y = jnp.zeros((t, d), dt).at[tok].add(y_assign)
    return y.reshape(b, s, d), aux

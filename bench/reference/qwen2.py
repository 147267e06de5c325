"""Plain float32 Qwen2 (arXiv:2407.10671; Qwen/Qwen2-7B config.json).

Pre-norm decoder: x += Wo·attn(RoPE(Wq x̂ + bq), RoPE(Wk x̂ + bk), Wv x̂ + bv)
with grouped-query heads and a causal softmax, then
x += W_down (silu(W_gate x̂) * (W_up x̂)); RMSNorm before each, before the
untied head, eps `rms_norm_eps`.  RoPE rotates the two halves of each head
(HF `rotate_half`) with inverse frequencies theta^(-2i/d).

The weights are the benchmark's own, made from the seed by `make_params`
in the layout the program reads (layers stacked under `scan.l0`):

    embed (V,d)  lm_head (d,V)  final_norm.scale (d,)
    scan.l0.norm1.scale / norm2.scale (L,d)
    scan.l0.mixer.wq (L,d,Hq,D) wk,wv (L,d,Hkv,D) wo (L,Hq,D,d)
    scan.l0.mixer.bq (L,Hq,D) bk,bv (L,Hkv,D)
    scan.l0.mlp.wi [up] wg [gate] (L,d,F) wo [down] (L,F,d)

The reference runs one sequence at a time and one layer per call, so its
largest temporary is one sequence's attention scores.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.common import mm, rmsnorm, silu

#: `arch` widths at which the CPU tests check this reference against the
#: program's forward pass (`bench/tests/test_bench_references.py`)
SMALL_ARCH = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_head=16,
                  d_ff=96, vocab_size=128)


def published_sizes(f: dict) -> dict:
    """Each `arch` size that the published `config.json` keys of the
    configuration file `f` fix, mapped to the value they require."""
    hidden, heads = f["hidden_size"], f["num_attention_heads"]
    if hidden % heads:
        raise ValueError(f"hidden_size {hidden} is no multiple of "
                         f"num_attention_heads {heads}")
    return {"d_model": hidden, "d_ff": f["intermediate_size"],
            "n_heads": heads, "n_kv_heads": f["num_key_value_heads"],
            "n_layers": f["num_hidden_layers"],
            "vocab_size": f["vocab_size"], "rope_theta": f["rope_theta"],
            "d_head": hidden // heads}


def _sizes(config: dict):
    a = config["arch"]
    return (a["n_layers"], a["d_model"], a["n_heads"], a["n_kv_heads"],
            a["d_head"], a["d_ff"], a["vocab_size"])


def make_params(config: dict, key):
    """Every weight, from one key, in bfloat16 (call under `jax.jit`)."""
    n_layers, d, hq, hkv, dh, f, v = _sizes(config)
    init = config["init"]
    std, nstd, bstd = init["std"], init["norm_scale_std"], init["bias_std"]
    dt = jnp.dtype(config["plan"]["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape, dt) * s).astype(dt)

    def scale(shape):
        return (1 + normal(shape, nstd)).astype(dt)

    L = n_layers
    return {
        "embed": normal((v, d), std),
        "lm_head": normal((d, v), std),
        "final_norm": {"scale": scale((d,))},
        "scan": {"l0": {
            "norm1": {"scale": scale((L, d))},
            "norm2": {"scale": scale((L, d))},
            "mixer": {"wq": normal((L, d, hq, dh), std),
                      "wk": normal((L, d, hkv, dh), std),
                      "wv": normal((L, d, hkv, dh), std),
                      "wo": normal((L, hq, dh, d), std),
                      "bq": normal((L, hq, dh), bstd),
                      "bk": normal((L, hkv, dh), bstd),
                      "bv": normal((L, hkv, dh), bstd)},
            "mlp": {"wi": normal((L, d, f), std),
                    "wg": normal((L, d, f), std),
                    "wo": normal((L, f, d), std)}}},
    }


def rope(x, theta: float):
    """x (S, H, D): rotate the halves of each head by position."""
    s, _, d = x.shape
    half = d // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2 / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv        # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("eps", "theta", "mode"))
def _layer(stack, i, h, *, eps: float, theta: float, mode: str):
    """One decoder layer over one sequence h (S, d) float32."""
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
    at, ml = p["mixer"], p["mlp"]
    x = rmsnorm(h, p["norm1"]["scale"], eps)
    q = rope(mm("sd,dhk->shk", x, at["wq"], mode) + at["bq"], theta)
    k = rope(mm("sd,dhk->shk", x, at["wk"], mode) + at["bk"], theta)
    v = mm("sd,dhk->shk", x, at["wv"], mode) + at["bv"]
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)              # q head j reads kv j // g
    v = jnp.repeat(v, group, axis=1)
    s = mm("shk,thk->hst", q, k, mode) / math.sqrt(q.shape[-1])
    n = h.shape[0]
    causal = jnp.tril(jnp.ones((n, n), bool))
    s = jnp.where(causal[None], s, -jnp.inf)
    o = mm("hst,thk->shk", jax.nn.softmax(s, axis=-1), v, mode)
    h = h + mm("shk,hkd->sd", o, at["wo"], mode)
    x = rmsnorm(h, p["norm2"]["scale"], eps)
    up = mm("sd,df->sf", x, ml["wi"], mode)
    gate = mm("sd,df->sf", x, ml["wg"], mode)
    return h + mm("sf,fd->sd", silu(gate) * up, ml["wo"], mode)


@partial(jax.jit, static_argnames=("eps", "n_last", "mode"))
def _head(params, h, *, eps: float, n_last: int, mode: str):
    x = rmsnorm(h[-n_last:], params["final_norm"]["scale"], eps)
    return mm("sd,dv->sv", x, params["lm_head"], mode)


def last_logits(config: dict, params, tokens, n_last: int,
                mode: str = "f32"):
    """Logits (R, n_last, V) float32 at the last n_last positions of each
    row of tokens (R, S), each row a sequence from position 0."""
    eps = config["rms_norm_eps"]
    theta = config["rope_theta"]
    n_layers = config["arch"]["n_layers"]
    stack = params["scan"]["l0"]
    out = []
    with jax.default_matmul_precision("highest"):
        for row in tokens:
            h = params["embed"][row].astype(jnp.float32)
            for i in range(n_layers):
                h = _layer(stack, i, h, eps=eps, theta=theta, mode=mode)
            out.append(_head(params, h, eps=eps, n_last=n_last, mode=mode))
    return jnp.stack(out)

"""Plain float32 Granite-4.0-H (ibm-granite/granite-4.0-h-micro config.json,
model_type granitemoehybrid): Mamba-2 and attention layers in one stack.

    h = embedding_multiplier · embed[tokens]
    per layer, the mixer `layer_types` names, then the shared MLP:
        h += residual_multiplier · mixer(RMSNorm(h))
        h += residual_multiplier · W_down(silu(W_gate x̂) * (W_up x̂)),
             x̂ = RMSNorm(h)
    logits = RMSNorm(h) · embedᵀ / logits_scaling

Mamba-2, one group: [z, xBC, dt] = in_proj(x̂); xBC = silu(conv(xBC) + b);
x, B, C = split(xBC); dt = softplus(dt + dt_bias), A = -exp(A_log);
h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_tᵀ and y_t = h_t C_t + D x_t per
head (the plain recurrence, `reference.mamba2._scan`); out =
out_proj(RMSNorm(y · silu(z)) · norm).  Attention: grouped-query heads, no
positional encoding (`position_embedding_type` "nope"), causal softmax of
q·kᵀ · attention_multiplier.  Every RMSNorm at `rms_norm_eps`; the
residual stream in float32.

The weights are the benchmark's own, made from the seed by `make_params`
in the layout the program reads: the stack is P periods of a unit of U
layers (`arch.layer_pattern`), and `scan.l<j>` holds slot j of every
period, stacked (P, ...):

    embed (V,d)  final_norm.scale (d,)
    scan.l<j>.norm1.scale / norm2.scale (P,d)
    Mamba-2 slot:   mixer.in_proj (P,d,2Di+2N+H)  conv_w (P,K,Di+2N)
                    conv_b (P,Di+2N)  A_log, D, dt_bias (P,H)  norm (P,Di)
                    out_proj (P,Di,d)
    attention slot: mixer.wq (P,d,Hq,D) wk,wv (P,d,Hkv,D) wo (P,Hq,D,d)
    every slot:     mlp.wi [up] wg [gate] (P,d,F) wo [down] (P,F,d)

One call per layer over every row, each mixer and MLP one row at a time
and attention in blocks of queries, so that a layer's temporaries stay
under ~3.5 GB at 4 rows of 16511 positions beside the 6.4 GB of weights.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.common import mm, rmsnorm, silu
from reference.mamba2 import _conv, _scan

#: `arch` widths at which the CPU tests check this reference against the
#: program's forward pass (`bench/tests/test_bench_references.py`): two
#: periods of the unit
SMALL_ARCH = dict(n_layers=20, d_model=64, n_heads=4, n_kv_heads=2,
                  d_head=16, d_ff=96, vocab_size=128, ssm_state=8,
                  ssm_headdim=16, ssm_chunk=8)

#: queries per block of attention: a block's scores, (heads, block, keys)
#: in float32, are 1.1 GB at 32 heads over 16511 keys
Q_BLOCK = 512

#: the program's name of each published layer type
KINDS = {"mamba": "ssm", "attention": "attn"}


def period(types: list) -> list:
    """The shortest unit whose repeats make the list `types`."""
    n = len(types)
    for u in range(1, n + 1):
        if n % u == 0 and all(types[i] == types[i % u] for i in range(n)):
            return types[:u]
    raise ValueError("empty layer list")


def published_sizes(f: dict) -> dict:
    """Each `arch` value that the published `config.json` keys of the
    configuration file `f` fix, mapped to the value they require.  What
    the program cannot run (several groups of B and C, projection biases,
    a conv without bias, another activation) is an error."""
    hidden, heads = f["hidden_size"], f["num_attention_heads"]
    if hidden % heads:
        raise ValueError(f"hidden_size {hidden} is no multiple of "
                         f"num_attention_heads {heads}")
    if f["mamba_n_heads"] * f["mamba_d_head"] != f["mamba_expand"] * hidden:
        raise ValueError("mamba_n_heads x mamba_d_head is not the "
                         "expanded width")
    runs = {"mamba_n_groups": 1, "mamba_conv_bias": True,
            "mamba_proj_bias": False, "hidden_act": "silu",
            "normalization_function": "rmsnorm"}
    off = {k: f[k] for k, v in runs.items() if f[k] != v}
    if off:
        raise ValueError(f"the program runs {runs}; the config has {off}")
    return {"d_model": hidden, "n_layers": f["num_hidden_layers"],
            "n_heads": heads, "n_kv_heads": f["num_key_value_heads"],
            "d_head": hidden // heads, "d_ff": f["shared_intermediate_size"],
            "vocab_size": f["vocab_size"],
            "tie_embeddings": f["tie_word_embeddings"],
            "qkv_bias": f["attention_bias"],
            "rope": f["position_embedding_type"] != "nope",
            "norm_eps": f["rms_norm_eps"],
            "embed_scale": f["embedding_multiplier"],
            "residual_scale": f["residual_multiplier"],
            "attn_scale": f["attention_multiplier"],
            "logit_scale": 1 / f["logits_scaling"],
            "ssm_state": f["mamba_d_state"], "ssm_expand": f["mamba_expand"],
            "ssm_headdim": f["mamba_d_head"], "ssm_conv": f["mamba_d_conv"],
            "ssm_chunk": f["mamba_chunk_size"], "ssm_act_bc": True,
            "layer_pattern": [KINDS[t] for t in period(f["layer_types"])]}


def make_params(config: dict, key):
    """Every weight, from one key, in bfloat16 (call under `jax.jit`).

    A and dt follow Mamba-2's published initialisation: A uniform in
    [1, 16], dt log-uniform in [0.001, 0.1] through the inverse softplus.
    wq and wk take `init.qk_std`, and the embedding `init.std` over the
    embedding multiplier (the configuration's `assumed`)."""
    a = config["arch"]
    unit = a["layer_pattern"]
    P = a["n_layers"] // len(unit)
    d, f, v = a["d_model"], a["d_ff"], a["vocab_size"]
    hq, hkv, dh = a["n_heads"], a["n_kv_heads"], a["d_head"]
    di, n, k = a["ssm_expand"] * d, a["ssm_state"], a["ssm_conv"]
    h = di // a["ssm_headdim"]
    init = config["init"]
    std, nstd, bstd = init["std"], init["norm_scale_std"], init["bias_std"]
    dt = jnp.dtype(config["plan"]["param_dtype"])
    keys = iter(jax.random.split(key, 2 + 16 * len(unit)))

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape, dt) * s).astype(dt)

    def scale(shape):
        return (1 + normal(shape, nstd)).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def mamba():
        a_ = uniform((P, h), 1.0, 16.0)
        step = jnp.exp(uniform((P, h), math.log(1e-3), math.log(1e-1)))
        return {"in_proj": normal((P, d, 2 * di + 2 * n + h), std),
                "conv_w": normal((P, k, di + 2 * n), 1 / math.sqrt(k)),
                "conv_b": normal((P, di + 2 * n), bstd),
                "A_log": jnp.log(a_).astype(dt),
                "D": scale((P, h)),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "norm": scale((P, di)),
                "out_proj": normal((P, di, d), std)}

    def attention():
        return {"wq": normal((P, d, hq, dh), init["qk_std"]),
                "wk": normal((P, d, hkv, dh), init["qk_std"]),
                "wv": normal((P, d, hkv, dh), std),
                "wo": normal((P, hq, dh, d), std)}

    scan = {}
    for j, kind in enumerate(unit):
        scan[f"l{j}"] = {
            "norm1": {"scale": scale((P, d))},
            "norm2": {"scale": scale((P, d))},
            "mixer": mamba() if kind == "ssm" else attention(),
            "mlp": {"wi": normal((P, d, f), std),
                    "wg": normal((P, d, f), std),
                    "wo": normal((P, f, d), std)}}
    # the multiplier brings the embedding's output to `std`
    embed = normal((v, d), std / config["embedding_multiplier"])
    return {"embed": embed, "final_norm": {"scale": scale((d,))},
            "scan": scan}


def _mamba(m, x, eps: float, mode: str):
    """The Mamba-2 mixer over rows x (R, S, d), one row at a time."""
    nh = m["A_log"].shape[0]
    di = m["norm"].shape[0]
    n = (m["conv_w"].shape[1] - di) // 2

    def row(xr):                                            # (S, d)
        zxbcdt = mm("sd,dw->sw", xr, m["in_proj"], mode)
        z, xbc, dt = (zxbcdt[:, :di], zxbcdt[:, di:2 * di + 2 * n],
                      zxbcdt[:, 2 * di + 2 * n:])
        xbc = silu(_conv(xbc[None], m["conv_w"], m["conv_b"])[0])
        xs, B, C = xbc[:, :di], xbc[:, di:di + n], xbc[:, di + n:]
        dt = jax.nn.softplus(dt + m["dt_bias"])
        xh = xs.reshape(-1, nh, di // nh)
        y = _scan(xh[None], dt[None], -jnp.exp(m["A_log"]), B[None],
                  C[None])[0] + m["D"][:, None] * xh
        y = rmsnorm(y.reshape(-1, di) * silu(z), m["norm"], eps)
        return mm("sw,wd->sd", y, m["out_proj"], mode)

    return jax.lax.map(row, x)


def _attention(at, x, scale: float, mode: str):
    """Causal grouped-query attention, one row x (S, d) at a time, its
    queries in blocks of `Q_BLOCK`."""
    q = mm("sd,dhk->shk", x, at["wq"], mode)
    k = mm("sd,dhk->shk", x, at["wk"], mode)
    v = mm("sd,dhk->shk", x, at["wv"], mode)
    group = q.shape[1] // k.shape[1]
    k = jnp.repeat(k, group, axis=1)              # q head j reads kv j // g
    v = jnp.repeat(v, group, axis=1)
    s = x.shape[0]
    qb = min(Q_BLOCK, s)
    nb = -(-s // qb)
    qs = jnp.pad(q, ((0, nb * qb - s), (0, 0), (0, 0)))
    qs = qs.reshape(nb, qb, *q.shape[1:])
    keys = jnp.arange(s)

    def block(args):
        i, qi = args
        rows = i * qb + jnp.arange(qb)
        sc = mm("qhk,thk->hqt", qi, k, mode) * scale
        sc = jnp.where(keys[None, None, :] <= rows[None, :, None], sc,
                       -jnp.inf)
        return mm("hqt,thk->qhk", jax.nn.softmax(sc, axis=-1), v, mode)

    o = jax.lax.map(block, (jnp.arange(nb), qs))
    o = o.reshape(nb * qb, *q.shape[1:])[:s]
    return mm("shk,hkd->sd", o, at["wo"], mode)


@partial(jax.jit, static_argnames=("kind", "eps", "rs", "scale", "mode"))
def _layer(slot, p, h, *, kind: str, eps: float, rs: float, scale: float,
           mode: str):
    """Layer `p` of a unit slot's stacks over rows h (R, S, d) float32."""
    w = jax.tree.map(lambda a: a[p].astype(jnp.float32), slot)
    x = rmsnorm(h, w["norm1"]["scale"], eps)
    if kind == "ssm":
        mix = _mamba(w["mixer"], x, eps, mode)
    else:
        mix = jax.lax.map(lambda xr: _attention(w["mixer"], xr, scale, mode),
                          x)
    h = h + rs * mix
    ml = w["mlp"]

    def mlp(xr):
        up = mm("sd,df->sf", xr, ml["wi"], mode)
        gate = mm("sd,df->sf", xr, ml["wg"], mode)
        return mm("sf,fd->sd", silu(gate) * up, ml["wo"], mode)

    x = rmsnorm(h, w["norm2"]["scale"], eps)
    return h + rs * jax.lax.map(mlp, x)


@partial(jax.jit, static_argnames=("mult",))
def _embed(embed, tokens, *, mult: float):
    return embed[tokens].astype(jnp.float32) * mult


@partial(jax.jit, static_argnames=("eps", "n_last", "div", "mode"))
def _head(params, h, *, eps: float, n_last: int, div: float, mode: str):
    x = rmsnorm(h[:, -n_last:], params["final_norm"]["scale"], eps)
    return mm("rsd,vd->rsv", x, params["embed"], mode) / div


def last_logits(config: dict, params, tokens, n_last: int,
                mode: str = "f32"):
    """Logits (R, n_last, V) float32 at the last n_last positions of each
    row of tokens (R, S), each row a sequence from position 0."""
    types = [KINDS[t] for t in
             config["layer_types"][:config["arch"]["n_layers"]]]
    unit = len(period(types))
    eps = config["rms_norm_eps"]
    kw = dict(eps=eps, rs=config["residual_multiplier"],
              scale=config["attention_multiplier"], mode=mode)
    with jax.default_matmul_precision("highest"):
        h = _embed(params["embed"], jnp.asarray(tokens),
                   mult=config["embedding_multiplier"])
        for i, kind in enumerate(types):
            h = _layer(params["scan"][f"l{i % unit}"], i // unit, h,
                       kind=kind, **kw)
        return _head(params, h, eps=eps, n_last=n_last,
                     div=config["logits_scaling"], mode=mode)

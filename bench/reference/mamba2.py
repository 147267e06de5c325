"""Plain float32 Mamba-2 (arXiv:2405.21060; state-spaces/mamba2-1.3b).

Each layer: x += out_proj(gated_rmsnorm(ssm(conv(xBC)), z)) on x̂ = RMSNorm(x),
where in_proj(x̂) = [z, xBC, dt] and, per head h with scalar A_h:

    xBC = act(causal_depthwise_conv(xBC) + b);  x, B, C = split(xBC)
    dt  = softplus(dt + dt_bias);  A = -exp(A_log)
    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T      (state (P, N) per head)
    y_t = h_t C_t + D x_t
    out = RMSNorm(y * silu(z)) * norm_weight          (one group)

run as the plain recurrence, one position at a time, with the residual
stream in float32 as published.  The published block applies `act`
(silu) to all of xBC and its RMSNorms take eps 1e-5; the configuration's
`departures` say what the program runs instead.  `last_logits` follows
what is run, and with `published=True` the published equations, for the
day the program follows them.  The tied head reads the embedding.

The weights are the benchmark's own, made from the seed by `make_params`
in the layout the program reads (layers stacked under `scan.l0`):

    embed (V,d)  final_norm.scale (d,)  scan.l0.norm1.scale (L,d)
    scan.l0.mixer.in_proj (L,d,2*Di+2N+H)  conv_w (L,K,Di+2N)  conv_b (L,Di+2N)
    scan.l0.mixer.A_log, D, dt_bias (L,H)  norm (L,Di)  out_proj (L,Di,d)
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from reference.common import mm, rmsnorm, silu

#: `arch` widths at which the CPU tests check this reference against the
#: program's forward pass (`bench/tests/test_bench_references.py`)
SMALL_ARCH = dict(n_layers=2, d_model=32, ssm_state=8, ssm_headdim=8,
                  ssm_chunk=8, vocab_size=96)


def published_sizes(f: dict) -> dict:
    """Each `arch` size that the published `config.json` keys of the
    configuration file `f` fix, mapped to the value they require: the
    vocabulary's rows are padded to `pad_vocab_size_multiple`."""
    pad = f["pad_vocab_size_multiple"]
    return {"d_model": f["d_model"], "n_layers": f["n_layer"],
            "vocab_size": -(-f["vocab_size"] // pad) * pad}


def _sizes(config: dict):
    a = config["arch"]
    d = a["d_model"]
    di = a["ssm_expand"] * d
    return (a["n_layers"], d, di, a["ssm_state"], di // a["ssm_headdim"],
            a["ssm_headdim"], a["ssm_conv"], a["vocab_size"])


def make_params(config: dict, key):
    """Every weight, from one key, in bfloat16 (call under `jax.jit`).

    A and dt follow the published initialisation: A uniform in [1, 16],
    dt log-uniform in [0.001, 0.1] through the inverse softplus."""
    L, d, di, n, h, _, k, v = _sizes(config)
    init = config["init"]
    std, nstd, bstd = init["std"], init["norm_scale_std"], init["bias_std"]
    dt = jnp.dtype(config["plan"]["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, s):
        return (jax.random.normal(next(keys), shape, dt) * s).astype(dt)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    a = uniform((L, h), 1.0, 16.0)
    step = jnp.exp(uniform((L, h), math.log(1e-3), math.log(1e-1)))
    return {
        "embed": normal((v, d), std),
        "final_norm": {"scale": (1 + normal((d,), nstd)).astype(dt)},
        "scan": {"l0": {
            "norm1": {"scale": (1 + normal((L, d), nstd)).astype(dt)},
            "mixer": {
                "in_proj": normal((L, d, 2 * di + 2 * n + h), std),
                "conv_w": normal((L, k, di + 2 * n), 1 / math.sqrt(k)),
                "conv_b": normal((L, di + 2 * n), bstd),
                "A_log": jnp.log(a).astype(dt),
                "D": (1 + normal((L, h), nstd)).astype(dt),
                "dt_bias": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                "norm": (1 + normal((L, di), nstd)).astype(dt),
                "out_proj": normal((L, di, d), std)}}},
    }


def _conv(x, w, b):
    """Causal depthwise conv: x (R,S,C), w (K,C); output t sees t-K+1..t."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(xp[:, i:i + x.shape[1]] * w[i] for i in range(k)) + b


def _scan(xh, dt, A, B, C):
    """The recurrence over positions. xh (R,S,H,P) dt (R,S,H) B,C (R,S,N)."""
    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * A)[..., None, None]              # (R,H,1,1)
        state = decay * state + jnp.einsum(
            "rhp,rn->rhpn", x_t * dt_t[..., None], b_t,
            precision=jax.lax.Precision.HIGHEST)
        y = jnp.einsum("rhpn,rn->rhp", state, c_t,
                       precision=jax.lax.Precision.HIGHEST)
        return state, y

    r, _, h, p = xh.shape
    state = jnp.zeros((r, h, p, B.shape[-1]), jnp.float32)
    tm = lambda a: jnp.swapaxes(a, 0, 1)                        # time-major
    _, y = jax.lax.scan(step, state, (tm(xh), tm(dt), tm(B), tm(C)))
    return tm(y)


@partial(jax.jit, static_argnames=("eps", "silu_bc", "mode"))
def _layer(stack, i, h, *, eps: float, silu_bc: bool, mode: str):
    """One Mamba-2 layer over sequences h (R, S, d) float32."""
    p = jax.tree.map(lambda a: a[i].astype(jnp.float32), stack)
    m = p["mixer"]
    nh = m["A_log"].shape[0]
    di = m["norm"].shape[0]
    n = (m["conv_w"].shape[1] - di) // 2
    x = rmsnorm(h, p["norm1"]["scale"], eps)
    zxbcdt = mm("rsd,dw->rsw", x, m["in_proj"], mode)
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    xbc = _conv(xbc, m["conv_w"], m["conv_b"])
    xs, B, C = xbc[..., :di], xbc[..., di:di + n], xbc[..., di + n:]
    xs = silu(xs)
    if silu_bc:
        B, C = silu(B), silu(C)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])
    r, s = h.shape[:2]
    xh = xs.reshape(r, s, nh, di // nh)
    y = _scan(xh, dt, A, B, C) + m["D"][:, None] * xh
    y = rmsnorm(y.reshape(r, s, di) * silu(z), m["norm"], eps)
    return h + mm("rsw,wd->rsd", y, m["out_proj"], mode)


@partial(jax.jit, static_argnames=("eps", "n_last", "mode"))
def _head(params, h, *, eps: float, n_last: int, mode: str):
    x = rmsnorm(h[:, -n_last:], params["final_norm"]["scale"], eps)
    return mm("rsd,vd->rsv", x, params["embed"], mode)


def last_logits(config: dict, params, tokens, n_last: int,
                mode: str = "f32", published: bool = False):
    """Logits (R, n_last, V) float32 at the last n_last positions of each
    row of tokens (R, S), each row a sequence from position 0."""
    which = "published" if published else "as_run"
    eps = config["departures"]["norm_eps"][which]
    silu_bc = config["departures"]["silu_on_BC"][which]
    stack = params["scan"]["l0"]
    with jax.default_matmul_precision("highest"):
        h = params["embed"][tokens].astype(jnp.float32)
        for i in range(config["arch"]["n_layers"]):
            h = _layer(stack, i, h, eps=eps, silu_bc=silu_bc, mode=mode)
        return _head(params, h, eps=eps, n_last=n_last, mode=mode)

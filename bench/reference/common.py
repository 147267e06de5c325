"""Pieces every plain reference shares: the matmul in float32 or in the
control's float8, RMSNorm, and the served-token gap.

Nothing here imports the program.  Every matmul runs at
`jax.default_matmul_precision("highest")`: on a TPU a float32 matmul is
otherwise rounded to bfloat16.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: float8 format of the lower-precision control, and its largest finite
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def q8(x, axes):
    """Round x to float8 e4m3 with one scale per slice over `axes` (the
    contracted axes), and back to float32: what an fp8 matmul sees."""
    x = x.astype(jnp.float32)
    s = jnp.max(jnp.abs(x), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(F8).astype(jnp.float32) * s


def mm(spec: str, a, b, mode: str = "f32"):
    """einsum of two operands in float32; with mode "fp8" both operands are
    first rounded to float8 over their contracted axes."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "fp8":
        ins, out = spec.split("->")
        sa, sb = ins.split(",")
        a = q8(a, tuple(i for i, c in enumerate(sa) if c not in out))
        b = q8(b, tuple(i for i, c in enumerate(sb) if c not in out))
    elif mode != "f32":
        raise ValueError(mode)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + eps) * scale.astype(jnp.float32)


def silu(x):
    return x * jax.nn.sigmoid(x)


@jax.jit
def served_gaps(logits, served):
    """How far below the reference's best logit each served token lies.

    logits (R, T, V) float32 from the reference; served (R, T) int32."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, served[..., None], axis=-1)[..., 0]
    return best - got


@jax.jit
def control_gaps(logits, control_logits):
    """Gap, under the reference, of the token the control puts first."""
    return served_gaps(logits, jnp.argmax(control_logits, axis=-1)
                       .astype(jnp.int32))

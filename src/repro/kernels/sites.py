"""The five Pallas kernels at the widths of the models that call them.

Each ``Site`` names the published configuration whose width it takes,
its operands at that width, the ``repro.kernels.ops`` call the model
layers make, the oracle from ``repro.kernels.ref``, and the raw kernel
call with the blocks ``ops`` picks when it compiles for the chip.
``chip_smoke.py`` runs the sites on the chip against their oracles;
``tests/test_tpu_compile.py`` compiles them for a described v5e.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mriq import mriq_pallas
from repro.kernels.rglru import rglru_pallas
from repro.kernels.ssd import ssd_pallas
from repro.kernels.swiglu import swiglu_pallas

SEQ = 4096              # tokens per sequence at every sequence site
SWIGLU_TOKENS = 2048    # tokens through the MLP site
MRIQ_VOXELS = 64 ** 3   # the paper's MRI-Q problem: a 64^3 volume ...
MRIQ_SAMPLES = 3072     # ... against 3072 k-space samples

_sds = jax.ShapeDtypeStruct


@dataclass(frozen=True)
class Site:
    name: str
    source: str             # configuration the widths come from
    operands: tuple         # jax.ShapeDtypeStruct per operand
    op: Callable            # the ops call: operands -> output(s)
    oracle: Callable        # the ref call, given float32 operands
    kernel: Callable        # the compiled kernel with the blocks ops picks
    make: Callable          # PRNG key -> operands
    tol: float              # max |op - oracle| over max |oracle|


def _normals(key, operands, scales=None):
    keys = jax.random.split(key, len(operands))
    scales = scales or [1.0] * len(operands)
    return tuple((jax.random.normal(k, a.shape, jnp.float32) * sc
                  ).astype(a.dtype)
                 for k, a, sc in zip(keys, operands, scales))


def _flash() -> Site:
    cfg = get_config("qwen2-7b")
    q = _sds((1, SEQ, cfg.n_heads, cfg.d_head), jnp.bfloat16)
    kv = _sds((1, SEQ, cfg.n_kv_heads, cfg.d_head), jnp.bfloat16)
    bq, bk = ops.flash_blocks(SEQ, SEQ, compiled=True)
    return Site(
        "flash_attention", cfg.name, (q, kv, kv),
        op=lambda q, k, v: ops.flash_attention(q, k, v, causal=True),
        oracle=lambda q, k, v: ref.flash_attention_ref(q, k, v, True, 0),
        kernel=lambda q, k, v: flash_attention(
            q, k, v, causal=True, block_q=bq, block_k=bk, interpret=False),
        make=lambda key: _normals(key, (q, kv, kv)),
        tol=1e-2)


def _swiglu() -> Site:
    cfg = get_config("qwen2-7b")
    d, f = cfg.d_model, cfg.d_ff
    ops_ = (_sds((SWIGLU_TOKENS, d), jnp.bfloat16),
            _sds((d, f), jnp.bfloat16), _sds((d, f), jnp.bfloat16),
            _sds((f, d), jnp.bfloat16))
    bt, bf = ops.swiglu_blocks(SWIGLU_TOKENS, f, compiled=True)
    return Site(
        "swiglu", cfg.name, ops_,
        op=ops.fused_swiglu,
        oracle=ref.swiglu_ref,
        kernel=lambda x, wi, wg, wo: swiglu_pallas(
            x, wi, wg, wo, block_t=bt, block_f=bf, interpret=False),
        make=lambda key: _normals(
            key, ops_, [1.0, d ** -0.5, d ** -0.5, f ** -0.5]),
        tol=1e-2)


def _ssd() -> Site:
    cfg = get_config("mamba2-1.3b")
    h, p, n = cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state
    ops_ = (_sds((1, SEQ, h, p), jnp.bfloat16),
            _sds((1, SEQ, h), jnp.float32), _sds((h,), jnp.float32),
            _sds((1, SEQ, n), jnp.bfloat16), _sds((1, SEQ, n), jnp.bfloat16))
    chunk = ops.ssd_chunk(SEQ, cfg.ssm_chunk, compiled=True)

    def make(key):
        x, dt, a, bm, cm = _normals(key, ops_)
        return (x, jax.nn.softplus(dt - 2.0), -jnp.exp(0.2 * a), bm, cm)

    return Site(
        "ssd", cfg.name, ops_,
        op=lambda *a: ops.ssd(*a, chunk=cfg.ssm_chunk),
        oracle=lambda *a: ref.ssd_ref(*a, chunk=cfg.ssm_chunk),
        kernel=lambda *a: ssd_pallas(*a, chunk=chunk, interpret=False),
        make=make, tol=1e-2)


def _rglru() -> Site:
    cfg = get_config("recurrentgemma-9b")
    ops_ = (_sds((1, SEQ, cfg.lru_width), jnp.float32),) * 2
    bt, bw = ops.rglru_blocks(SEQ, cfg.lru_width, compiled=True)

    def make(key):
        log_a, b = _normals(key, ops_)
        return -0.2 * jnp.abs(log_a), 0.5 * b

    return Site(
        "rglru", cfg.name, ops_,
        op=ops.rglru, oracle=ref.rglru_ref,
        kernel=lambda log_a, b: rglru_pallas(
            log_a, b, block_w=bw, block_t=bt, interpret=False),
        make=make, tol=1e-4)


def _mriq() -> Site:
    ks = (_sds((MRIQ_SAMPLES,), jnp.float32),) * 4
    vox = (_sds((MRIQ_VOXELS,), jnp.float32),) * 3
    bn, bm = ops.mriq_blocks(MRIQ_VOXELS, MRIQ_SAMPLES, compiled=True)

    def make(key):
        k = jax.random.split(key, 4)
        kxyz = [jax.random.uniform(k[i], (MRIQ_SAMPLES,), jnp.float32,
                                   -32.0, 32.0) for i in range(3)]
        phi = jax.random.uniform(k[3], (MRIQ_SAMPLES,), jnp.float32)
        side = round(MRIQ_VOXELS ** (1 / 3))
        axis = (jnp.arange(side, dtype=jnp.float32) - side / 2) / side
        grid = jnp.meshgrid(axis, axis, axis, indexing="ij")
        return (*kxyz, phi, *(g.reshape(-1) for g in grid))

    def oracle(*a):
        # voxel blocks keep the (N, M) phase matrix off the device
        kx, ky, kz, phi, x, y, z = a
        blocks = jnp.stack([x, y, z]).reshape(3, 16, -1).transpose(1, 0, 2)
        qr, qi = jax.lax.map(
            lambda v: ref.mriq_ref(kx, ky, kz, phi, v[0], v[1], v[2]),
            blocks)
        return qr.reshape(-1), qi.reshape(-1)

    return Site(
        "mriq", "parboil-mri-q-64", ks + vox,
        op=ops.mriq, oracle=oracle,
        kernel=lambda *a: mriq_pallas(*a, block_n=bn, block_m=bm,
                                      interpret=False),
        make=make, tol=1e-3)


def sites() -> list[Site]:
    """The five sites, in the order the model stack reaches them."""
    return [_flash(), _swiglu(), _ssd(), _rglru(), _mriq()]


@jax.jit
def rel_err(out, want):
    """max |out - want| over max |want|, worst across every output."""
    def one(o, w):
        w = w.astype(jnp.float32)
        d = jnp.max(jnp.abs(o.astype(jnp.float32) - w))
        return d / jnp.maximum(jnp.max(jnp.abs(w)), math.ulp(1.0))
    return jnp.max(jnp.stack(jax.tree.leaves(jax.tree.map(one, out, want))))

"""jit'd public wrappers for the Pallas kernels (the 'pallas' destination).

These are what the model layers call when the offload plan selects the
Pallas rung.  Each wrapper normalizes layouts, picks block shapes and
falls back to the pure-jnp oracle when the shape cannot be tiled.  Whether
a kernel is compiled for the chip or run in the Pallas interpreter is
decided from the platform when the call is traced
(``repro.kernels.resolve_interpret``).  A compiled kernel gets only
tiling-aligned blocks: a multiple of the 8-row sublane or 128-wide lane
tile, as the dimension requires, or the whole dimension.  Each fallback
to the oracle counts on ``repro.obs`` as ``kernel_fallback_<kernel>``
(once per trace, not per call).

Every op carries a ``jax.custom_vjp``: the forward runs the Pallas kernel,
the backward differentiates the pure-jnp oracle (rematerialized) — so the
'pallas' destination is usable in train plans, not just inference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import ref as _ref
from repro.kernels import resolve_interpret
from repro.kernels.flash_attention import flash_attention as _flash
from repro.kernels.flash_attention import skipped_share
from repro.kernels.mriq import mriq_pallas as _mriq
from repro.kernels.rglru import rglru_pallas as _rglru
from repro.kernels.ssd import ssd_pallas as _ssd
from repro.kernels.swiglu import swiglu_pallas as _swiglu

SUBLANE, LANE = 8, 128      # the TPU tile of a block's last two dimensions


def _blk(n: int, target: int, align: int = 1) -> int:
    """The whole dimension when it is at most ``target``, else the largest
    divisor of n that is <= target and a multiple of ``align``; 0 when
    there is none."""
    if n <= target:
        return n
    b = target - target % align
    while b > 0 and n % b:
        b -= align
    return b


def _compiled() -> bool:
    return not resolve_interpret(None)


def _align(tile: int, compiled: bool) -> int:
    return tile if compiled else 1


def _fallback(kernel: str) -> None:
    obs.METRICS.counter(
        f"kernel_fallback_{kernel}",
        "traces that ran the jnp oracle: no block tiles the shape").inc()


def flash_blocks(s: int, t: int, compiled: bool) -> tuple[int, int]:
    """(block_q, block_k) for S queries over T keys: up to 512 queries
    over 1024 keys, so that each grid step's fixed cost is spread over
    half a million scores; the largest that fit the default scoped VMEM
    at d_head 256, and on a v5e the fastest such at 2048 and 16384
    positions."""
    a = _align(SUBLANE, compiled)
    return _blk(s, 512, a), _blk(t, 1024, a)


def mriq_blocks(n: int, m: int, compiled: bool) -> tuple[int, int]:
    """(block_n, block_m) for N voxels (lanes) and M k-samples (rows)."""
    return (_blk(n, 512, _align(LANE, compiled)),
            _blk(m, 512, _align(SUBLANE, compiled)))


def rglru_blocks(s: int, w: int, compiled: bool) -> tuple[int, int]:
    """(block_t, block_w) for S steps over width W."""
    return (_blk(s, 128, _align(SUBLANE, compiled)),
            _blk(w, 512, _align(LANE, compiled)))


def ssd_chunk(s: int, chunk: int, compiled: bool) -> int:
    """Chunk length for S steps (a lane row of the decay in the kernel)."""
    return _blk(s, chunk, _align(LANE, compiled))


def swiglu_blocks(t: int, f: int, compiled: bool) -> tuple[int, int]:
    """(block_t, block_f) for T tokens through a d_ff of F."""
    return (_blk(t, 256, _align(SUBLANE, compiled)),
            _blk(f, 256, _align(LANE, compiled)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _flash_op(q, k, v, causal, window):
    bq, bk = flash_blocks(q.shape[1], k.shape[1], _compiled())
    if bq < 8 or bk < 8:
        _fallback("flash_attention")
        return _ref.flash_attention_ref(q, k, v, causal, window)
    obs.METRICS.gauge(
        "kernel_flash_skipped_tiles",
        "share of the flash kernel's grid steps, in the last call traced, "
        "that skip a tile the mask removes whole").set(skipped_share(
            q.shape[1], k.shape[1], causal=causal, window=window,
            block_q=bq, block_k=bk))
    return _flash(q, k, v, causal=causal, window=window,
                  block_q=bq, block_k=bk)


def _flash_fwd(q, k, v, causal, window):
    return _flash_op(q, k, v, causal, window), (q, k, v)


def _flash_bwd(causal, window, res, g):
    q, k, v = res
    _, vjp = jax.vjp(lambda a, b, c:
                     _ref.flash_attention_ref(a, b, c, causal, window),
                     q, k, v)
    return vjp(g)


_flash_op.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = True, window: int = 0):
    return _flash_op(q, k, v, causal, window)


def mriq(kx, ky, kz, phi_mag, x, y, z):
    bn, bm = mriq_blocks(x.shape[0], kx.shape[0], _compiled())
    if not (bn and bm):
        _fallback("mriq")
        return _ref.mriq_ref(kx, ky, kz, phi_mag, x, y, z)
    return _mriq(kx, ky, kz, phi_mag, x, y, z, block_n=bn, block_m=bm)


@jax.custom_vjp
def rglru(log_a, b):
    _, s, w = log_a.shape
    bt, bw = rglru_blocks(s, w, _compiled())
    if bw < 8 or bt < 8:
        _fallback("rglru")
        return _ref.rglru_ref(log_a, b)
    return _rglru(log_a.astype(jnp.float32), b.astype(jnp.float32),
                  block_w=bw, block_t=bt)


def _rglru_fwd(log_a, b):
    return rglru(log_a, b), (log_a, b)


def _rglru_bwd(res, g):
    log_a, b = res
    _, vjp = jax.vjp(_ref.rglru_ref, log_a, b)
    return vjp(g.astype(jnp.float32))


rglru.defvjp(_rglru_fwd, _rglru_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _ssd_op(x, dt, A, Bm, Cm, chunk):
    q = ssd_chunk(x.shape[1], chunk, _compiled())
    if q < 8:
        _fallback("ssd")
        return _ref.ssd_ref(x, dt, A, Bm, Cm, max(q, 1))
    return _ssd(x, dt, A, Bm, Cm, chunk=q)


def _ssd_fwd(x, dt, A, Bm, Cm, chunk):
    return _ssd_op(x, dt, A, Bm, Cm, chunk), (x, dt, A, Bm, Cm)


def _ssd_bwd(chunk, res, g):
    x, dt, A, Bm, Cm = res
    _, vjp = jax.vjp(lambda *a: _ref.ssd_ref(*a, chunk=max(chunk, 1)),
                     x, dt, A, Bm, Cm)
    return vjp(g)


_ssd_op.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x, dt, A, Bm, Cm, chunk: int = 128):
    return _ssd_op(x, dt, A, Bm, Cm, chunk)


def _layer_panels(wi, wg, wo, layer):
    """The (d,f), (d,f), (f,d) panels of one layer: the weights themselves,
    or layer ``layer`` of stacked weights."""
    if layer is None:
        return wi, wg, wo
    return wi[layer], wg[layer], wo[layer]


@jax.custom_vjp
def _swiglu_op(xf, wi, wg, wo, layer):
    t = xf.shape[0]
    compiled = _compiled()
    # the compiled kernel takes fewer rows than a sublane tile (a decode
    # step of a small batch) padded to one
    rows = max(t, SUBLANE) if compiled else t
    bt, bf = swiglu_blocks(rows, wi.shape[-1], compiled)
    if bt < 8 or bf < 8:
        _fallback("swiglu")
        return _ref.swiglu_ref(xf, *_layer_panels(wi, wg, wo, layer))
    if layer is not None:
        obs.METRICS.counter(
            "kernel_stacked_swiglu",
            "traces whose swiglu read its panels from the stacked weights "
            "by layer index").inc()
    if rows == t:
        return _swiglu(xf, wi, wg, wo, layer, block_t=bt, block_f=bf)
    xf = jnp.pad(xf, ((0, rows - t), (0, 0)))
    return _swiglu(xf, wi, wg, wo, layer, block_t=bt, block_f=bf)[:t]


def _swiglu_fwd(xf, wi, wg, wo, layer):
    return _swiglu_op(xf, wi, wg, wo, layer), (xf, wi, wg, wo, layer)


def _swiglu_bwd(res, g):
    xf, wi, wg, wo, layer = res
    _, vjp = jax.vjp(lambda x, *w: _ref.swiglu_ref(
        x, *_layer_panels(*w, layer)), xf, wi, wg, wo)
    return (*vjp(g), None)


_swiglu_op.defvjp(_swiglu_fwd, _swiglu_bwd)


def fused_swiglu(x, wi, wg, wo, layer=None):
    """x (..., d) -> (..., d); flattens leading dims for the kernel.

    With ``layer`` (an integer scalar) wi, wg, wo are stacked per layer,
    (L,d,f), (L,d,f), (L,f,d), and layer ``layer`` runs: its panels are
    read where they lie in the stacks, with no slice of them made.
    """
    lead = x.shape[:-1]
    d = x.shape[-1]
    t = math.prod(lead)
    y = _swiglu_op(x.reshape(t, d), wi, wg, wo, layer)
    return y.reshape(*lead, d)

"""MRI-Q Pallas kernel — the paper's own evaluated application (Parboil).

The paper offloads MRI-Q's hot loop nest (16 processable loops) to an FPGA
and measures 14 s -> 2 s, 1690 W*s -> 223 W*s.  The TPU-native datapath:
tile voxels into VMEM blocks (grid dim 0, parallel), stream k-space points
in chunks (grid dim 1, arbitrary/sequential) and accumulate Q_r/Q_i in f32
— sin/cos run on the VPU over a (k x voxel) phase tile.  Voxels ride the
lanes as (1, N) rows and k-space points the sublanes as (M, 1) columns, so
every block is a 2-D tile the chip's layout accepts.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

DEF_BLOCK_N = 512       # voxels per block
DEF_BLOCK_M = 512       # k-space points per chunk


def _mriq_kernel(x_ref, y_ref, z_ref, kx_ref, ky_ref, kz_ref, phi_ref,
                 qr_ref, qi_ref):
    kb = pl.program_id(1)

    @pl.when(kb == 0)
    def _init():
        qr_ref[...] = jnp.zeros_like(qr_ref)
        qi_ref[...] = jnp.zeros_like(qi_ref)

    ang = (x_ref[...].astype(jnp.float32) * kx_ref[...]
           + y_ref[...].astype(jnp.float32) * ky_ref[...]
           + z_ref[...].astype(jnp.float32) * kz_ref[...])
    ang = 2.0 * math.pi * ang                   # (bm, bn)
    phi = phi_ref[...]                          # (bm, 1)
    qr_ref[...] += jnp.sum(phi * jnp.cos(ang), axis=0, keepdims=True)
    qi_ref[...] += jnp.sum(phi * jnp.sin(ang), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("block_n", "block_m",
                                             "interpret"))
def mriq_pallas(kx, ky, kz, phi_mag, x, y, z,
                block_n: int = DEF_BLOCK_N, block_m: int = DEF_BLOCK_M,
                interpret: bool | None = None):
    """k-space (M,) and voxel (N,) coordinates -> (Q_r, Q_i), each (N,)."""
    interpret = resolve_interpret(interpret)
    n, m = x.shape[0], kx.shape[0]
    block_n = min(block_n, n)
    block_m = min(block_m, m)
    assert n % block_n == 0 and m % block_m == 0, (n, block_n, m, block_m)
    grid = (n // block_n, m // block_m)

    vox_spec = pl.BlockSpec((1, block_n), lambda i, j: (0, i))
    k_spec = pl.BlockSpec((block_m, 1), lambda i, j: (j, 0))

    qr, qi = pl.pallas_call(
        _mriq_kernel,
        grid=grid,
        in_specs=[vox_spec] * 3 + [k_spec] * 4,
        out_specs=[vox_spec, vox_spec],
        out_shape=[jax.ShapeDtypeStruct((1, n), jnp.float32)] * 2,
        interpret=interpret,
        name="mriq_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(*(a.reshape(1, n) for a in (x, y, z)),
      *(a.astype(jnp.float32).reshape(m, 1) for a in (kx, ky, kz, phi_mag)))
    return qr.reshape(n), qi.reshape(n)

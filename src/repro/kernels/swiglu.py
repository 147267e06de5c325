"""Fused SwiGLU MLP kernel (Pallas).

y = (silu(x @ wg) * (x @ wi)) @ wo with the (T, d_ff) intermediate never
leaving VMEM: grid (token_blocks, ff_blocks) with ff 'arbitrary'
(sequential), accumulating the second matmul into a (block_t, d) f32
scratch.  The VMEM working set is 2 weight panels + x/y blocks — the
narrowing resource pre-check rejects configs whose panels exceed VMEM
(exactly the FPGA FF/LUT rejection of the paper).  ``vmem_bytes`` is
that working set; the kernel asks the compiler for it explicitly, since
the whole ``d`` sits in every block and published widths overflow the
default scoped VMEM limit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

#: VMEM of one TPU v5e core, and the compiler's default scoped limit
VMEM_CAPACITY = 128 * 2**20
VMEM_DEFAULT_LIMIT = 16 * 2**20


def vmem_bytes(block_t: int, block_f: int, d: int, x_itemsize: int,
               w_itemsize: int) -> int:
    """Bytes of VMEM the kernel holds at these blocks."""
    io = 2 * 2 * block_t * d * x_itemsize       # x in + y out, 2 buffers
    panels = 2 * 3 * d * block_f * w_itemsize   # wi, wg, wo, 2 buffers
    acc = block_t * d * 4                       # f32 accumulator
    temps = 3 * block_t * block_f * 4           # h, g, silu(g) * h in f32
    return io + panels + acc + temps


def _swiglu_kernel(x_ref, wi_ref, wg_ref, wo_ref, y_ref, acc_scr,
                   *, n_ff_blocks: int):
    fb = pl.program_id(1)

    @pl.when(fb == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # operands enter the MXU in their own dtype, products accumulate in
    # f32.  Sub-f32 operands multiply exactly in one pass, and Mosaic
    # takes no f32 contract precision for them: keep a caller's
    # "highest" for f32 operands only.
    x = x_ref[...]                                    # (bt, d)
    prec = None if x.dtype == jnp.float32 else lax.Precision.DEFAULT
    dot = functools.partial(jnp.dot, precision=prec,
                            preferred_element_type=jnp.float32)
    h = dot(x, wi_ref[...])
    g = dot(x, wg_ref[...])
    act = (g * jax.nn.sigmoid(g) * h).astype(wo_ref.dtype)   # (bt, bf)
    acc_scr[...] += dot(act, wo_ref[...])

    @pl.when(fb == n_ff_blocks - 1)
    def _out():
        y_ref[...] = acc_scr[...].astype(y_ref.dtype)


def _stacked_kernel(layer_ref, *refs, n_ff_blocks: int):
    # the layer index is used by the index maps alone
    del layer_ref
    _swiglu_kernel(*refs, n_ff_blocks=n_ff_blocks)


@functools.partial(jax.jit, static_argnames=("block_t", "block_f",
                                             "interpret"))
def swiglu_pallas(x, wi, wg, wo, layer=None, block_t: int = 256,
                  block_f: int = 256, interpret: bool | None = None):
    """x (T,d); wi,wg (d,f); wo (f,d) -> (T,d).

    With ``layer`` (an integer scalar) the weights are stacks, wi,wg
    (L,d,f) and wo (L,f,d), and the kernel DMAs layer ``layer``'s panels
    straight out of them: the caller slices and copies nothing.
    """
    interpret = resolve_interpret(interpret)
    t, d = x.shape
    f = wi.shape[-1]
    block_t = min(block_t, t)
    block_f = min(block_f, f)
    assert t % block_t == 0 and f % block_f == 0
    grid = (t // block_t, f // block_f)

    # the index maps take the prefetched layer index, where there is one,
    # after the grid indices
    x_spec = pl.BlockSpec((block_t, d), lambda tb, fb, *_: (tb, 0))
    y_spec = pl.BlockSpec((block_t, d), lambda tb, fb, *_: (tb, 0))
    if layer is None:
        wi_spec = pl.BlockSpec((d, block_f), lambda tb, fb: (0, fb))
        wo_spec = pl.BlockSpec((block_f, d), lambda tb, fb: (fb, 0))
    else:
        # the layer dimension is squeezed away: the kernel sees the same
        # (d, block_f) / (block_f, d) panels as from unstacked weights
        wi_spec = pl.BlockSpec((None, d, block_f),
                               lambda tb, fb, l: (l[0], 0, fb))
        wo_spec = pl.BlockSpec((None, block_f, d),
                               lambda tb, fb, l: (l[0], fb, 0))

    # the working set plus a quarter for Mosaic's own temporaries, never
    # below the default limit and never above the core's VMEM
    need = vmem_bytes(block_t, block_f, d, x.dtype.itemsize,
                      wi.dtype.itemsize)
    limit = min(max(need + need // 4, VMEM_DEFAULT_LIMIT), VMEM_CAPACITY)
    specs = dict(grid=grid, in_specs=[x_spec, wi_spec, wi_spec, wo_spec],
                 out_specs=y_spec,
                 scratch_shapes=[pltpu.VMEM((block_t, d), jnp.float32)])
    kernel, prefetch = _swiglu_kernel, ()
    if layer is not None:
        specs = dict(grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, **specs))
        kernel = _stacked_kernel
        prefetch = (jnp.reshape(layer, (1,)).astype(jnp.int32),)
    return pl.pallas_call(
        functools.partial(kernel, n_ff_blocks=grid[1]),
        out_shape=jax.ShapeDtypeStruct((t, d), x.dtype),
        interpret=interpret,
        name="swiglu_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=limit),
        **specs,
    )(*prefetch, x, wi, wg, wo)

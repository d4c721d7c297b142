"""Pallas TPU kernel: elementwise E2AFS approximate sqrt / rsqrt.

TPU mapping of the paper's FPGA datapath (docs/kernels.md): the whole
computation is VPU integer work — bitcast, shifts, masks, adds and two
branchless selects — with no transcendental-unit involvement and no fp
multiply on the sqrt path.  Tiles are (block_rows, 128): the last dim
matches the VPU lane width; block_rows is sized so a tile (in+out) stays
well under VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import numerics
from repro.core.e2afs import _e2afs_mantissa_exponent, _rsqrt_mantissa_exponent

__all__ = ["e2afs_sqrt_kernel_call"]

LANE = 128


def _kernel(x_ref, o_ref, *, rsqrt: bool):
    x = x_ref[...]
    fmt = numerics.format_of(x.dtype)
    sign, exp, man = numerics.decompose(x, fmt)
    if rsqrt:
        exp_out, man_out = _rsqrt_mantissa_exponent(exp, man, fmt)
    else:
        exp_out, man_out = _e2afs_mantissa_exponent(exp, man, fmt)
    res = numerics.compose(jnp.zeros_like(sign), exp_out, man_out, fmt)
    res = numerics.apply_specials(res, x, sign, exp, man, fmt)
    if rsqrt:
        # as core.e2afs.e2afs_rsqrt: a flushed positive subnormal is zero to
        # the datapath, so it takes rsqrt(0) = +inf too
        is_zero = (exp == 0) & ((man == 0) | (sign == 0))
        is_inf = (exp == fmt.exp_mask) & (man == 0) & (sign == 0)
        res = jnp.where(is_zero, jnp.array(jnp.inf, res.dtype), res)
        res = jnp.where(is_inf, jnp.zeros_like(res), res)
    o_ref[...] = res


def e2afs_sqrt_kernel_call(
    x2d: jax.Array, *, rsqrt: bool = False, block_rows: int = 256, interpret: bool = True
) -> jax.Array:
    """x2d: (rows, LANE·k).  Rows must divide by block_rows."""
    rows, cols = x2d.shape
    assert cols % LANE == 0 and rows % block_rows == 0, (rows, cols)
    grid = (rows // block_rows,)
    return pl.pallas_call(
        functools.partial(_kernel, rsqrt=rsqrt),
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(x2d.shape, x2d.dtype),
        interpret=interpret,
    )(x2d)

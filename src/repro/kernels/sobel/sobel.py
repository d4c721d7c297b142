"""Pallas TPU kernel: Sobel edge magnitude with in-kernel E2AFS sqrt.

The paper's §4.1 pipeline as one fused kernel: per output tile, the 3x3
stencil (shift-adds — Sobel taps are +-1/+-2, multiplier-free like the
sqrt), the squared magnitude, and the E2AFS integer-datapath sqrt all run
in VMEM.  The image is small enough to sit in VMEM whole; output is tiled
and each tile reads its (bh+2, bw+2) halo window by indexing the ref with
pl.ds windows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.e2afs import e2afs_sqrt_positive
from repro.kernels.dispatch import pad2d_to_multiple

__all__ = ["sobel_kernel_call"]


def _kernel(img_ref, o_ref, *, bh: int, bw: int):
    i = pl.program_id(0)
    j = pl.program_id(1)
    win = img_ref[pl.ds(i * bh, bh + 2), pl.ds(j * bw, bw + 2)]
    # 3x3 Sobel taps via shifted adds (weights are powers of two)
    c = lambda di, dj: win[di : di + bh, dj : dj + bw]
    gx = (c(0, 2) - c(0, 0)) + 2.0 * (c(1, 2) - c(1, 0)) + (c(2, 2) - c(2, 0))
    gy = (c(2, 0) - c(0, 0)) + 2.0 * (c(2, 1) - c(0, 1)) + (c(2, 2) - c(0, 2))
    mag2 = jnp.maximum(gx * gx + gy * gy, 1e-12)
    o_ref[...] = e2afs_sqrt_positive(mag2)


def sobel_kernel_call(img: jax.Array, *, bh: int = 64, bw: int = 128, interpret: bool = True):
    """img: (H, W) f32, any size >= 3x3.  Returns (H-2, W-2) magnitude.

    Arbitrary sizes go through the dispatch layer's shared stencil padding:
    the image is edge-padded so the output divides the tile (zero-copy when
    already aligned) and the padded lanes are cropped after the kernel —
    tile choice stays purely a performance knob."""
    oh, ow = img.shape[0] - 2, img.shape[1] - 2
    padded = pad2d_to_multiple(img, (bh, bw), halo=2, mode="edge")
    ph, pw = padded.shape[0] - 2, padded.shape[1] - 2
    out = pl.pallas_call(
        functools.partial(_kernel, bh=bh, bw=bw),
        grid=(ph // bh, pw // bw),
        in_specs=[pl.BlockSpec(padded.shape, lambda i, j: (0, 0))],  # whole image in VMEM
        out_specs=pl.BlockSpec((bh, bw), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((ph, pw), jnp.float32),
        interpret=interpret,
    )(padded)
    return out[:oh, :ow]

"""Public wrappers: arbitrary-shape elementwise E2AFS sqrt/rsqrt.

Backend and tiling resolution live in the dispatch layer; these wrappers
only register the kernel and expose differentiable entry points (the JVP
rules make the integer datapath trainable — without them grads are silently
zero through the bitcasts).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import dispatch, tuning
from repro.kernels.e2afs_sqrt.e2afs_sqrt import LANE, e2afs_sqrt_kernel_call
from repro.kernels.e2afs_sqrt.ref import ref_rsqrt, ref_sqrt

__all__ = ["sqrt", "rsqrt"]

_WIDTH = LANE * 8


def _geometry(args):
    """Tile-prior geometry for the wrapper's (rows, _WIDTH) blocking.  The
    integer datapath keeps several tile-sized temporaries live beside the
    in/out pipeline buffers: for a described v5e, Mosaic reports 18.0 MiB
    (sqrt) and 21.9 MiB (rsqrt) of VMEM at 512 f32 rows, i.e. 9-11 tiles."""
    return {
        **tuning.tile_geometry(args),
        "rows": -(-int(args[0].size) // _WIDTH),
        "row_elems": _WIDTH,
        "vmem_tiles": 12,
    }


_TILING = dispatch.TilingSpec(
    default=(256,), candidates=((64,), (128,), (256,), (512,)), geometry=_geometry
)


@functools.partial(jax.jit, static_argnames=("rsqrt_", "block", "interpret"))
def _pallas(x, *, block, interpret, rsqrt_=False):
    # clamp to the real row count so tiny inputs pad to one row, not a block;
    # pad with ones: elementwise, and 1.0 is finite through both datapaths
    br = min(block[0], -(-x.size // _WIDTH))
    x2d = dispatch.as_blocked_2d(x, width=_WIDTH, block_rows=br, pad_value=1.0)
    out = e2afs_sqrt_kernel_call(x2d, rsqrt=rsqrt_, block_rows=br, interpret=interpret)
    return dispatch.unblock(out, x.size, x.shape)


dispatch.register(
    dispatch.KernelSpec(
        name="e2afs_sqrt",
        reference=ref_sqrt,
        pallas=_pallas,
        tiling=_TILING,
    )
)
dispatch.register(
    dispatch.KernelSpec(
        name="e2afs_rsqrt",
        reference=ref_rsqrt,
        pallas=functools.partial(_pallas, rsqrt_=True),
        tiling=_TILING,
    )
)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _sqrt(x, interpret):
    return dispatch.dispatch("e2afs_sqrt", x, interpret=interpret)


@_sqrt.defjvp
def _sqrt_jvp(interpret, primals, tangents):
    (x,), (t,) = primals, tangents
    y = _sqrt(x, interpret)
    return y, (t * (0.5 / y)).astype(y.dtype)


@functools.partial(jax.custom_jvp, nondiff_argnums=(1,))
def _rsqrt(x, interpret):
    return dispatch.dispatch("e2afs_rsqrt", x, interpret=interpret)


@_rsqrt.defjvp
def _rsqrt_jvp(interpret, primals, tangents):
    (x,), (t,) = primals, tangents
    y = _rsqrt(x, interpret)
    return y, (t * (-0.5 * y / x)).astype(y.dtype)


def sqrt(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    return _sqrt(x, interpret)


def rsqrt(x: jax.Array, *, interpret: bool | None = None) -> jax.Array:
    return _rsqrt(x, interpret)

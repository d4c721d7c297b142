"""Public wrapper: fused AdamW-E2AFS update for arbitrary-shaped params.

lr / b1c / b2c are runtime scalars (they change every step under a schedule
and must stay traceable inside a jitted train step); b1/b2/eps/wd are true
hyperparameters and stay static.  Backend/tiling come from the dispatch
layer.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch, tuning
from repro.kernels.adam.adam import LANE, adam_kernel_call
from repro.kernels.adam.ref import ref_adam_update

__all__ = ["adam_update"]

_WIDTH = LANE * 8


def _pallas_impl(p, g, m, v, *, block, interpret, lr, b1c=1.0, b2c=1.0,
                 b1=0.9, b2=0.95, eps=1e-8, wd=0.1):
    shape = p.shape
    n = p.size
    # clamp to the tensor's real row count: a (5,)-element bias must pad to
    # one row, not block_rows * width elements (x7 kernel streams)
    br = min(block[0], -(-n // _WIDTH))

    def prep(a, dtype):
        return dispatch.as_blocked_2d(a.astype(dtype), width=_WIDTH, block_rows=br)

    sched = jnp.stack([
        jnp.asarray(lr, jnp.float32),
        jnp.asarray(b1c, jnp.float32),
        jnp.asarray(b2c, jnp.float32),
    ])
    po, mo, vo = adam_kernel_call(
        prep(p, p.dtype), prep(g, g.dtype), prep(m, jnp.float32), prep(v, jnp.float32),
        sched, b1=b1, b2=b2, eps=eps, wd=wd,
        block_rows=br, interpret=interpret,
    )
    unflat = lambda a, dt: dispatch.unblock(a, n, shape).astype(dt)
    return unflat(po, p.dtype), unflat(mo, jnp.float32), unflat(vo, jnp.float32)


_jit = functools.partial(jax.jit, static_argnames=("b1", "b2", "eps", "wd", "block", "interpret"))
_pallas_nodonate = _jit(_pallas_impl)
# donating variant: p/m/v buffers are consumed and reused for the outputs,
# so a fused optimizer step adds zero transient HBM on its 7 streams.  g is
# NOT donated (callers may reuse grads for logging/metrics).
_pallas_donate = _jit(_pallas_impl, donate_argnums=(0, 2, 3))


def _pallas(p, g, m, v, *, donate: bool = False, **kw):
    return (_pallas_donate if donate else _pallas_nodonate)(p, g, m, v, **kw)


def _geometry(args):
    """Tile-prior geometry for the wrapper's (rows, _WIDTH) blocking: a
    block of rows moves 7 streams (p, g, m, v in; p, m, v out), each with
    two pipeline buffers in VMEM, and the update body keeps about 8 f32
    tiles live besides (for a described v5e, Mosaic reports 21.96 MiB of
    VMEM at 256 rows on a 2560x151936 parameter)."""
    return {
        **tuning.tile_geometry(args),
        "rows": -(-int(args[0].size) // _WIDTH),
        "row_elems": _WIDTH,
        "streams": 7,
        "vmem_tiles": 2 * 7 + 8,
    }


dispatch.register(
    dispatch.KernelSpec(
        name="adam",
        reference=ref_adam_update,
        pallas=_pallas,
        tiling=dispatch.TilingSpec(
            default=(256,), candidates=((8,), (64,), (256,), (512,)),
            geometry=_geometry,
        ),
    )
)


def adam_update(p, g, m, v, *, lr, b1=0.9, b2=0.95, eps=1e-8, wd=0.1,
                b1c=1.0, b2c=1.0, donate: bool = False, interpret: bool | None = None):
    """One fused AdamW step.  ``donate=True`` hands the p/m/v buffers to the
    kernel for in-place reuse — only safe when the caller rebinds them to the
    returned values (the train loop does; benchmarks re-calling with the same
    arrays must keep the default)."""
    kw = dict(lr=lr, b1=b1, b2=b2, eps=eps, wd=wd, b1c=b1c, b2c=b2c)
    if donate:
        kw["donate"] = True  # reference path doesn't take (or need) it
    return dispatch.dispatch("adam", p, g, m, v, interpret=interpret, **kw)

"""Public wrapper: fused K-means assignment for arbitrary pixel counts.

Pixels are padded to the tile (zero rows, masked out of the accumulators
by the true-count SMEM scalar, cropped from the returned assignments), so
tile choice is purely a performance knob the dispatch layer autotunes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.kernels.kmeans.kmeans import kmeans_assign_kernel_call
from repro.kernels.kmeans.ref import ref_kmeans_assign

__all__ = ["kmeans_assign"]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _pallas(px, cent, *, block, interpret):
    n = px.shape[0]
    bn = min(block[0], n)  # a tiny image must pad to one tile, not block_n rows
    px_p = dispatch.pad_rows(px.astype(jnp.float32), bn)
    assign, sums, counts = kmeans_assign_kernel_call(
        px_p, cent.astype(jnp.float32), jnp.full((1,), n, jnp.int32),
        block_n=bn, interpret=interpret,
    )
    return assign[:n, 0], sums, counts[0]


def _geometry(args):
    """Tile-prior geometry: each pixel row is scored against every centroid,
    so per-element work scales with K (the default prior would undercount
    it by ~K and overfavor tiny tiles).  The tile cap holds the kernel's
    memory contract — a per-tile (block_n, K, 3) working set far below the
    broadcast path's N-proportional footprint — against a prior that would
    otherwise pick one whole-input tile for mid-size images and degenerate
    to exactly the (N, K, 3) materialization the kernel exists to avoid.

    In VMEM each pixel row pads to a 128-lane row, and the per-tile
    distance work grows with K: for a described v5e, Mosaic reports
    18.9 MiB at 2048 rows for K=20 and 23.8 MiB for K=64, which
    ``vmem_tiles`` covers with a little room."""
    px, cent = args[0], args[1]
    n = int(px.shape[0])
    k = int(cent.shape[0])
    return {
        "rows": n,
        "row_elems": max(int(px.size) // max(n, 1), 1),
        "ops_per_elem": 3.0 * k,  # per channel: diff/mul/add x K
        "streams": 2,
        "vmem_tiles": 17 + -(-k // 8),
        "max_block_rows": max(n // 4, 128),
    }


dispatch.register(
    dispatch.KernelSpec(
        name="kmeans_assign",
        reference=ref_kmeans_assign,
        pallas=_pallas,
        tiling=dispatch.TilingSpec(
            default=(512,),
            candidates=((128,), (256,), (512,), (1024,), (2048,)),
            geometry=_geometry,
        ),
    )
)


def kmeans_assign(px: jax.Array, cent: jax.Array, *, interpret: bool | None = None):
    """px: (N, C); cent: (K, C).  Returns (assign, sums, counts) for one
    Lloyd iteration, computed in VMEM tiles (no (N, K, C) HBM intermediate)."""
    return dispatch.dispatch("kmeans_assign", px, cent, interpret=interpret)

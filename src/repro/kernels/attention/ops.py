"""Public wrapper: blocked decode attention over the stacked KV cache.

The dispatch layer resolves the t-block (``TilingSpec`` candidates
{128, 256, 512, 1024} lines, default 512: a 1 MiB bf16 K block at
qwen3-4b's 8 × 128 kv width, K and V double-buffered in 4 MiB of VMEM)
by the roofline prior or autotune.  The prior's geometry is one row's KV
stream, so the block depends on the cache's shape and not on the batch:
a row reads its lines in the same blocks whatever pool it sits in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch, tuning
from repro.kernels.attention.attention import decode_attention_kernel_call
from repro.kernels.attention.ref import as_stacked, lines_per_block, ref_decode_attention

__all__ = ["blocks_fetched", "decode_attention", "ref_decode_attention", "resolved_lines"]


@functools.partial(jax.jit, static_argnames=("scale", "wrap", "block", "interpret"))
def _pallas(q, k, v, pos, k_scale=None, v_scale=None, layer=None, active=None, *,
            block, interpret, scale, wrap=False):
    k, v, k_scale, v_scale, layer = as_stacked(k, v, k_scale, v_scale, layer)
    return decode_attention_kernel_call(
        q, k, v, jnp.asarray(pos, jnp.int32),
        jnp.reshape(jnp.asarray(layer, jnp.int32), (1,)), active, k_scale, v_scale,
        scale=scale, wrap=wrap, lines=lines_per_block(k.shape[2], block[0]),
        interpret=interpret,
    )


def _geometry(args):
    """Tile-prior geometry: the grid's reduction runs over one row's cache
    lines, each carrying its K and V line (2 · kv · hd elements, read
    once); every query head meets its kv head's line once in the scores
    and once in the weighted sum."""
    q, k = args[0], args[1]
    t, kv, hd = (int(d) for d in k.shape[-3:])
    return {
        "rows": t,
        "row_elems": 2 * kv * hd,
        "ops_per_elem": 2.0 * (int(q.shape[1]) // kv),
        "streams": 1,  # the KV read dominates; q/out lines are negligible
    }


dispatch.register(
    dispatch.KernelSpec(
        name="decode_attention",
        reference=ref_decode_attention,
        pallas=_pallas,
        tiling=dispatch.TilingSpec(
            default=(512,),
            candidates=((128,), (256,), (512,), (1024,)),
            geometry=_geometry,
        ),
    )
)


def resolved_lines(n_heads, cache_len, kv, hd, dtype=jnp.bfloat16) -> int:
    """The t-block a traced call resolves for this cache on the current
    backend: under tracing the dispatch layer takes the roofline prior,
    from shapes alone.  The oracle reads in these blocks too, and a
    host-side count of the blocks fetched needs them."""
    tiling = dispatch.get("decode_attention").tiling
    shapes = (jax.ShapeDtypeStruct((1, n_heads, hd), dtype),
              jax.ShapeDtypeStruct((1, 1, cache_len, kv, hd), dtype))
    block, _ = tuning.roofline_plan(
        tiling.candidates, tiling.default, shapes,
        interpret=dispatch.resolve_backend() != "compiled",
        geometry=tiling.geometry,
    )
    return lines_per_block(cache_len, block[0])


def blocks_fetched(pos, live, cache_len: int, lines: int, wrap: bool):
    """Host-side (numpy) count of the K blocks the kernel fetches, against
    the blocks a read of every row's whole cache would, over calls stacked
    along the last axis.  pos, live: (b, calls) positions and liveness.
    A live row fetches the blocks it reads (``ref.row_blocks``); a call
    with no live row still fetches one block, the pipeline's first.
    Returns (fetched, whole); V doubles both."""
    n_all = cache_len // lines
    n = np.minimum(pos // lines + 1, n_all)
    if wrap:
        n = np.where(pos >= cache_len, n_all, n)
    n = np.where(live, n, 0)
    fetched = int(n.sum()) + int(np.sum(~np.any(live, axis=0)))
    return fetched, n_all * live.size


def decode_attention(q, k, v, pos, k_scale=None, v_scale=None, layer=None,
                     active=None, *, scale, wrap=False, interpret: bool | None = None):
    """One decode-attention step.  q: (b, h, hd), the single query token per
    row; k/v: the stacked (L, b, t, kv, hd) cache with ``layer`` (an int32
    scalar), or one layer's (b, t, kv, hd); pos: (b,) per-row positions;
    scales: int8 caches' fp32 (L, b, t, kv) / (b, t, kv) scales, or None;
    active: (b,) bool, rows that are not live read nothing and return
    zeros (None: every row is live); ``wrap=True`` for ring
    (sliding-window) caches.  Returns (b, h, hd)."""
    return dispatch.dispatch(
        "decode_attention", q, k, v, pos, k_scale, v_scale, layer, active,
        scale=scale, wrap=wrap, interpret=interpret,
    )

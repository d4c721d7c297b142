"""Pallas TPU kernel: blocked decode attention over the stacked KV cache.

One query token per batch row attends over that row's cache lines.  The
kernel takes the cache as it lies in the decode program's carry, the
stacked ``(L, b, t, kv, hd)`` K and V buffers, and finds its layer by
scalar prefetch, so no layer plane is sliced or copied out of the stack.
The grid is ``(b, t / lines)``: step ``(i, j)`` folds t-block ``j`` of row
``i`` into an online softmax held in VMEM scratch (``ref.block_update``).

Only the blocks a row has written are fetched.  Row ``i`` reads
``nblk[i]`` blocks (``ref.row_blocks``: up to its position, all of a
wrapped ring, none when the row is not live).  The K/V index map clamps
``j`` to the row's last block, so a step past it names the block already
in VMEM and the pipeline issues no DMA for it; ``pl.when`` skips its
compute.  A row that reads nothing names the block its predecessor
ended on (or the first live row's first block), so it costs no DMA either.

The validity mask is built in-kernel from the row's position, with the
inline path's rule: line ``t`` is live when ``t <= pos``, or always once a
ring has wrapped (``pos >= cache_len``, sliding-window layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.attention.ref import block_update, finalize, init_state, row_blocks

__all__ = ["block_index", "decode_attention_kernel_call", "fetch_plan"]


def fetch_plan(nblk, n_all: int):
    """Per row, the (row, last block) its K/V index map clamps to: its own
    last block when it reads any; otherwise the block the previous live row
    ended on, or block 0 of the first live row, which the pipeline fetches
    next anyway.  One masked max over (row, candidate) pairs, so the plan
    is a single small fusion: a live candidate at or before the row is
    keyed above every fallback, the latest such one winning; the fallback
    keys rank the first live row highest."""
    b = nblk.shape[0]
    rows = jnp.arange(b, dtype=jnp.int32)
    live = (nblk > 0)[None, :]
    own = rows[None, :] <= rows[:, None]  # (i, r): candidate r at or before i
    key = rows * n_all + nblk - 1 + b * n_all  # >= b * n_all
    cand = jnp.where(live & own, key[None, :], jnp.where(live, -rows[None, :] - 1, -b - 1))
    best = jnp.max(cand, axis=1)
    ended = best - b * n_all
    krow = jnp.where(best >= b * n_all, ended // n_all,
                     jnp.where(best >= -b, -best - 1, 0))  # no live row: row 0
    kend = jnp.where(best >= b * n_all, ended % n_all, 0)
    return krow.astype(jnp.int32), kend.astype(jnp.int32)


def block_index(i, j, nblk, krow, kend):
    """The (row, t-block) the K/V index maps name at grid step (i, j): a
    live row walks its blocks and stays on its last; a row that reads
    nothing names one block, from :func:`fetch_plan`, for all its steps."""
    return krow[i], jnp.where(nblk[i] > 0, jnp.minimum(j, kend[i]), kend[i])


def _kernel(layer_ref, pos_ref, nblk_ref, krow_ref, kend_ref, q_ref, k_ref, v_ref,
            *rest, scale, wrap, lines, cache_len, quantized):
    del layer_ref, krow_ref, kend_ref  # read by the index maps only
    if quantized:
        ks_ref, vs_ref, o_ref, m_sc, l_sc, acc_sc = rest
    else:
        (o_ref, m_sc, l_sc, acc_sc), ks_ref, vs_ref = rest, None, None
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m0, l0, acc0 = init_state(*acc_sc.shape)
        m_sc[...], l_sc[...], acc_sc[...] = m0, l0, acc0

    @pl.when(j < nblk_ref[i])
    def _():
        state = (m_sc[...], l_sc[...], acc_sc[...])
        m, l, acc = block_update(
            q_ref[...], k_ref, v_ref, ks_ref, vs_ref, pos_ref[i], j * lines,
            state, scale=scale, wrap=wrap, cache_len=cache_len,
        )
        m_sc[...], l_sc[...], acc_sc[...] = m, l, acc

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        o_ref[...] = finalize((m_sc[...], l_sc[...], acc_sc[...]), o_ref.dtype)


def decode_attention_kernel_call(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos: jax.Array,
    layer: jax.Array,
    active: jax.Array | None = None,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    *,
    scale: float,
    wrap: bool = False,
    lines: int,
    interpret: bool = True,
) -> jax.Array:
    """q: (b, h, hd); k/v: the stacked (L, b, t, kv, hd) cache; pos: (b,)
    int32; layer: (1,) int32; active: (b,) bool or None (every row live);
    scales: (L, b, t, kv) fp32 or None.  ``lines`` must divide t.  Returns
    (b, h, hd)."""
    b, h, hd = q.shape
    cache_len, kv = k.shape[2], k.shape[3]
    assert cache_len % lines == 0, (cache_len, lines)
    nblk = row_blocks(pos, active, cache_len, lines, wrap)
    krow, kend = fetch_plan(nblk, cache_len // lines)

    def kv_map(i, j, layer, pos, nblk, krow, kend):
        return (layer[0], *block_index(i, j, nblk, krow, kend), 0, 0)

    def scale_map(i, j, layer, pos, nblk, krow, kend):
        return (layer[0], *block_index(i, j, nblk, krow, kend), 0)

    row_map = lambda i, j, *_: (i, 0, 0)
    kv_spec = pl.BlockSpec((None, None, lines, kv, hd), kv_map)
    in_specs = [pl.BlockSpec((None, h, hd), row_map), kv_spec, kv_spec]
    operands = [q, k, v]
    quantized = k_scale is not None
    if quantized:
        scale_spec = pl.BlockSpec((None, None, lines, kv), scale_map)
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(b, cache_len // lines),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, h, hd), row_map),
        scratch_shapes=[pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, 1), jnp.float32),
                        pltpu.VMEM((h, hd), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, wrap=wrap, lines=lines,
                          cache_len=cache_len, quantized=quantized),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(layer, pos, nblk, krow, kend, *operands)

"""The blocked decode-attention maths, and its pure-jnp oracle.

One query token per batch row attends over that row's cache lines in
t-blocks of ``lines`` lines, with an online softmax: a running max, sum
and fp32 accumulator, rescaled as each block arrives.  A row reads only
the blocks it has written (:func:`row_blocks`); a row that is not live
reads none and returns zeros.

:func:`block_update` and :func:`finalize` are the whole per-block
arithmetic.  The Pallas kernel (``attention.py``) calls them on the blocks
it loads, and :func:`ref_decode_attention` calls them on slices of the
same cache in the same block order, so the two agree bit for bit in
interpret mode.  Against the inline path (``layers/attention.py``
``_fold_masked_attention``, one softmax over the whole row) they differ by
rounding only: the exponentials are rescaled per block, and the weights
meet V before the sum is divided out.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.layers.attention import NEG_INF

__all__ = [
    "as_stacked",
    "block_update",
    "finalize",
    "lines_per_block",
    "ref_decode_attention",
    "row_blocks",
]


def lines_per_block(cache_len: int, block: int) -> int:
    """The t-block a ``cache_len``-line cache is read in, for a requested
    ``block``: the largest divisor of ``cache_len`` not above it that is a
    multiple of 8 (the sublane tile of an int8 scale block), else the whole
    cache.  Blocks never run past the cache's end."""
    for n in range(min(block, cache_len), 0, -1):
        if cache_len % n == 0 and (n % 8 == 0 or n == cache_len):
            return n
    return cache_len


def as_stacked(k, v, k_scale, v_scale, layer):
    """One layer's (b, t, kv, hd) cache is the stacked form with L = 1."""
    if k.ndim == 5:
        return k, v, k_scale, v_scale, layer
    if k_scale is not None:
        k_scale, v_scale = k_scale[None], v_scale[None]
    return k[None], v[None], k_scale, v_scale, 0


def row_blocks(pos, active, cache_len: int, lines: int, wrap: bool):
    """(b,) int32 blocks each row reads: the blocks holding lines
    ``0..pos``, every block once a ring cache has wrapped
    (``pos >= cache_len``), none for a row that is not live (``active``
    False; None means every row is)."""
    n_all = cache_len // lines
    n = jnp.minimum(pos // lines + 1, n_all)
    if wrap:
        n = jnp.where(pos >= cache_len, n_all, n)
    if active is not None:
        n = jnp.where(active, n, 0)
    return n.astype(jnp.int32)


def init_state(h: int, hd: int):
    """Running max, sum and accumulator of the online softmax, in fp32."""
    return (jnp.full((h, 1), NEG_INF, jnp.float32),
            jnp.zeros((h, 1), jnp.float32),
            jnp.zeros((h, hd), jnp.float32))


def block_update(q, k, v, k_scale, v_scale, pos, first_line, state, *, scale,
                 wrap, cache_len):
    """Fold one t-block of one row into the online-softmax state.

    q: (h, hd); k/v: (lines, kv, hd) arrays or kernel refs (int8 values are
    cast to q's dtype here); k_scale/v_scale: (lines, kv) fp32 or None;
    pos: the row's position; first_line: the block's first line index.
    Each kv head ``n`` is contracted against its g query heads ``n·g ..
    n·g + g - 1`` on its own lines ``k[:, n, :]``: the cache is never
    expanded to h heads.  Line ``t`` is live when ``t <= pos``, or always
    once a ring has wrapped; a line that is not is selected away before it
    can touch a score or an output, so whatever it holds (NaN included)
    leaves the state unchanged."""
    m, l, acc = state
    h = q.shape[0]
    lines, kv, _ = k.shape
    g = h // kv
    t = first_line + jax.lax.broadcasted_iota(jnp.int32, (1, lines), 1)
    t_col = first_line + jax.lax.broadcasted_iota(jnp.int32, (lines, 1), 0)
    valid, valid_col = t <= pos, t_col <= pos
    if wrap:
        valid = valid | (pos >= cache_len)
        valid_col = valid_col | (pos >= cache_len)
    nt = (((1,), (1,)), ((), ()))  # q_n (g, hd) . k_n (lines, hd) -> (g, lines)
    scores = []
    for n in range(kv):
        s = jax.lax.dot_general(q[n * g:(n + 1) * g], k[:, n, :].astype(q.dtype),
                                nt, preferred_element_type=jnp.float32) * scale
        if k_scale is not None:
            s = s * k_scale[:, n][None, :]
        scores.append(s)
    s = jnp.where(valid, jnp.concatenate(scores, axis=0), NEG_INF)  # (h, lines)
    m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
    nn = (((1,), (0,)), ((), ()))  # p_n (g, lines) . v_n (lines, hd) -> (g, hd)
    outs = []
    for n in range(kv):
        pn = p[n * g:(n + 1) * g]
        if v_scale is not None:
            pn = pn * v_scale[:, n][None, :]
        vn = v[:, n, :].astype(q.dtype)
        vn = jnp.where(valid_col, vn, jnp.zeros_like(vn))
        outs.append(jax.lax.dot_general(pn.astype(q.dtype), vn, nn,
                                        preferred_element_type=jnp.float32))
    acc = alpha * acc + jnp.concatenate(outs, axis=0)
    return m_new, l, acc


def finalize(state, dtype):
    """(h, hd) output: the accumulator over the sum; zeros for a row that
    read no block."""
    _, l, acc = state
    return jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0).astype(dtype)


def ref_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    layer=None,
    active=None,
    *,
    scale: float,
    wrap: bool = False,
    lines: int | None = None,
) -> jax.Array:
    """The kernel's arithmetic in plain jnp, one row at a time.

    q: (b, h, hd); k/v: the stacked (L, b, t, kv, hd) cache with ``layer``,
    or one layer's (b, t, kv, hd); pos: (b,) int32; scales: (L, b, t, kv) /
    (b, t, kv) fp32 or None; active: (b,) bool or None (every row live);
    ``lines``: the t-block (default: the one the kernel resolves for this
    cache on the current backend, ``ops.resolved_lines``).  Returns
    (b, h, hd)."""
    k, v, k_scale, v_scale, layer = as_stacked(k, v, k_scale, v_scale, layer)
    b, h, hd = q.shape
    cache_len = k.shape[2]
    if lines is None:
        from repro.kernels.attention.ops import resolved_lines

        lines = resolved_lines(h, cache_len, k.shape[3], hd, k.dtype)
    lines = lines_per_block(cache_len, lines)
    nblk = row_blocks(pos, active, cache_len, lines, wrap)
    k, v = k[layer], v[layer]
    if k_scale is not None:
        k_scale, v_scale = k_scale[layer], v_scale[layer]

    def row(i):
        state = init_state(h, hd)
        for j in range(cache_len // lines):
            cut = slice(j * lines, (j + 1) * lines)
            new = block_update(
                q[i], k[i, cut], v[i, cut],
                None if k_scale is None else k_scale[i, cut],
                None if v_scale is None else v_scale[i, cut],
                pos[i], j * lines, state, scale=scale, wrap=wrap,
                cache_len=cache_len,
            )
            # a block past the row's end is neither read nor folded in
            state = jax.tree.map(lambda a, o: jnp.where(j < nblk[i], a, o), new, state)
        return finalize(state, q.dtype)

    return jax.lax.map(row, jnp.arange(b))

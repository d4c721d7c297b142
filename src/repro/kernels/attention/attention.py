"""Pallas TPU kernel: fused per-slot decode attention.

One query token per batch row attends over that row's KV cache: the fp32
scores, the folded int8 K/V scales, the ring-validity mask, the fp32
softmax and the V-accumulate all happen on a VMEM-resident
(block_b, cache_len) tile — the decode hot loop reads the cache once from
HBM and writes only the (b, h, hd) output, instead of materializing the
score/weight tensors through HBM between XLA ops.

Bit-exactness contract: the in-kernel op sequence mirrors
``layers/attention.py:_fold_masked_attention`` term for term — the same
grouped-query contractions (``_grouped_scores`` / ``_grouped_out``: each kv
head against its g query heads, the cache never repeated to h heads), the
same fp32 casts, the same additive -2e38 mask, the same scale folding — so
interpret-mode output is bit-identical to the inline XLA decode path and
the engine's staggered-vs-solo parity suites hold with the kernel enabled
(float32; bf16 tolerance documented in docs/kernels.md).  The training and
cross-attention paths repeat kv heads instead (``_expand_kv``), which keeps
the head axis whole for the training meshes' sharding.

The validity mask is built in-kernel from the per-row positions of the
slot-pool contract (a ``(block_b, 1)`` int32 operand): slot ``t`` is live
when ``t <= pos``, or unconditionally once a ring buffer has wrapped
(``pos >= cache_len``, sliding-window layers).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.layers.attention import (
    NEG_INF,
    _gqa_out,
    _gqa_scores,
    _group_queries,
    _grouped_out,
    _grouped_scores,
    _per_kv_line,
)

__all__ = ["decode_attention_kernel_call"]


def _attend(q, k, v, pos, k_scale, v_scale, *, scale, wrap, out_dtype):
    """One tile of fused decode attention; q (bb, 1, h, hd), k/v
    (bb, t, kv, hd), pos (bb,), scales (bb, t, kv) or None."""
    bb, t, kv, hd = k.shape
    grouped = q.shape[2] > kv  # no mesh inside a kernel: GQA always groups
    if grouped:
        scores = _grouped_scores(_group_queries(q, kv), k)
    else:
        scores = _gqa_scores(q, k)[:, :, None]
    scores = scores.astype(jnp.float32) * scale  # (bb, n, g, 1, t)
    if k_scale is not None:
        scores = scores * _per_kv_line(k_scale, scores.shape[1])
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (bb, t), 1)
    valid = t_idx <= pos[:, None]
    if wrap:
        valid = valid | (pos[:, None] >= t)
    mask = jnp.where(valid, 0.0, NEG_INF)  # (bb, t) additive, fp32
    scores = scores + mask[:, None, None, None, :]
    w = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    if v_scale is not None:
        w = w * _per_kv_line(v_scale, w.shape[1]).astype(w.dtype)
    return _grouped_out(w, v) if grouped else _gqa_out(w[:, :, 0], v)


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, scale, wrap):
    out = _attend(
        q_ref[...][:, None], k_ref[...], v_ref[...], pos_ref[...][:, 0],
        None, None, scale=scale, wrap=wrap, out_dtype=o_ref.dtype,
    )
    o_ref[...] = out[:, 0].astype(o_ref.dtype)


def _kernel_quant(pos_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, *, scale, wrap):
    out = _attend(
        q_ref[...][:, None], k_ref[...], v_ref[...], pos_ref[...][:, 0],
        ks_ref[...], vs_ref[...], scale=scale, wrap=wrap, out_dtype=o_ref.dtype,
    )
    o_ref[...] = out[:, 0].astype(o_ref.dtype)


def decode_attention_kernel_call(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    pos2d: jax.Array,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    *,
    scale: float,
    wrap: bool = False,
    block_b: int = 8,
    interpret: bool = True,
) -> jax.Array:
    """q: (b, h, hd); k/v: (b, t, kv, hd) already in q's dtype; pos2d:
    (b, 1) int32; scales: (b, t, kv) fp32 or None.  Returns (b, h, hd)."""
    b, h, hd = q.shape
    t, kv = k.shape[1], k.shape[2]
    assert b % block_b == 0, (b, block_b)
    kv_spec = pl.BlockSpec((block_b, t, kv, hd), lambda i: (i, 0, 0, 0))
    in_specs = [
        pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        pl.BlockSpec((block_b, h, hd), lambda i: (i, 0, 0)),
        kv_spec,
        kv_spec,
    ]
    operands = [pos2d, q, k, v]
    kernel = _kernel
    if k_scale is not None:
        scale_spec = pl.BlockSpec((block_b, t, kv), lambda i: (i, 0, 0))
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
        kernel = _kernel_quant
    return pl.pallas_call(
        functools.partial(kernel, scale=scale, wrap=wrap),
        grid=(b // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, h, hd), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, hd), q.dtype),
        interpret=interpret,
    )(*operands)

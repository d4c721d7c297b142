"""Grouped-query attention with causal / sliding-window / bidirectional /
cross modes, optional QK-norm (through the configured sqrt unit), RoPE, and a
decode path over a (optionally int8-quantized, optionally sequence-sharded)
KV cache.

Shapes follow the (batch, seq, heads, head_dim) convention; logical axes:
  activations: ("batch", "seq", "heads", None)
  weights:     q (embed, heads, head_dim) / kv (embed, kv_heads, head_dim)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from repro.distributed.constraints import constrain, current_rules, shard_count
from repro.layers.norms import rmsnorm, rmsnorm_select
from repro.layers.param import DenseInit, zeros
from repro.layers.rope import apply_rope

__all__ = [
    "attention_init",
    "attention_train",
    "attention_prefill",
    "attention_decode",
    "attention_verify",
    "decode_route",
    "verify_cache_commit",
    "init_kv_cache",
    "kv_cache_specs",
    "prefill_cache_write",
]

NEG_INF = -2.0e38


def attention_init(ini: DenseInit, cfg, *, cross: bool = False):
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    ini.add("wq", (d, h, hd), ("embed", "heads", None), scale=1.0)
    ini.add("wk", (d, kv, hd), ("embed", "kv_heads", None), scale=1.0)
    ini.add("wv", (d, kv, hd), ("embed", "kv_heads", None), scale=1.0)
    ini.add("wo", (h, hd, d), ("heads", None, "embed"), scale=1.0)
    if cfg.qk_norm:
        ini.add("q_norm", (hd,), (None,), init=zeros)
        ini.add("k_norm", (hd,), (None,), init=zeros)
    del cross


def _project_qkv(p, cfg, xq, xkv, q_positions, kv_positions, *, use_rope, norm_levels=None):
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].astype(xq.dtype))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(xkv.dtype))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(xkv.dtype))
    if cfg.qk_norm:
        if norm_levels is not None and cfg.sqrt_ladder is not None:
            # accuracy-SLO decode: each slot's qk-norm rsqrt follows the
            # slot's current ladder rung (docs/robustness.md §Accuracy SLO)
            q = rmsnorm_select(
                p["q_norm"], q, norm_levels, ladder=cfg.sqrt_ladder, faults=cfg.sqrt_faults
            )
            k = rmsnorm_select(
                p["k_norm"], k, norm_levels, ladder=cfg.sqrt_ladder, faults=cfg.sqrt_faults
            )
        else:
            q = rmsnorm(p["q_norm"], q, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults)
            k = rmsnorm(p["k_norm"], k, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults)
    if use_rope:
        q = apply_rope(q, q_positions, theta=cfg.rope_theta)
        k = apply_rope(k, kv_positions, theta=cfg.rope_theta)
    return q, k, v


def _mask(mode, q_pos, kv_pos, window):
    """(q, kv) additive mask from position vectors."""
    d = q_pos[:, None] - kv_pos[None, :]
    if mode == "causal":
        ok = d >= 0
    elif mode == "window":  # causal sliding window
        ok = (d >= 0) & (d < window)
    elif mode == "bidir" or mode == "cross":
        ok = jnp.ones((q_pos.shape[0], kv_pos.shape[0]), bool)
    else:
        raise ValueError(mode)
    return jnp.where(ok, 0.0, NEG_INF)


def _softmax_scores(sc, out_dtype):
    """Softmax over the last axis.  For bf16-materialized scores (inference
    prefill) the O(s^2) chain tensors stay bf16 with an fp32 *accumulation*
    only — max-subtraction bounds the exponent so bf16 exp is safe, and the
    normalizer sum is f32 (pairwise bf16 summation at 32k terms is not).
    fp32 scores use the stock fp32 softmax."""
    if sc.dtype == jnp.float32:
        return jax.nn.softmax(sc, axis=-1).astype(out_dtype)
    m = jnp.max(sc, axis=-1, keepdims=True)
    e = jnp.exp(sc - m)
    s = jnp.sum(e.astype(jnp.float32), axis=-1, keepdims=True)
    return (e / s.astype(e.dtype)).astype(out_dtype)


def _expand_kv(k, h):
    """Broadcast kv heads up to h query heads, for the training, chunked and
    cross-attention paths.  Deliberately NOT a reshape of q into (kv,
    group) there: on training and prefill meshes that splits the sharded
    head dim into factors the mesh can't divide (e.g. 48 -> (4,12) on a
    16-wide axis) and GSPMD then REPLICATES the O(s^2) score tensors —
    measured 16x memory blowup on starcoder2 prefill (§Perf prefill study).
    The repeat keeps 'h' intact.  The serving block
    (:func:`_fold_masked_attention`) takes the grouped contraction instead
    (:func:`_grouped_scores`): there the repeat would copy the whole KV
    cache g-fold in every layer of every decode step.  It keeps the repeat
    only for MHA and on such meshes (:func:`_serve_grouped`)."""
    g = h // k.shape[2]
    return k if g == 1 else jnp.repeat(k, g, axis=2)


def _gqa_scores(q, k):
    """q: (b,s,h,k)  k: (b,t,kv,k) -> scores (b, h, s, t)."""
    return jnp.einsum("bshk,bthk->bhst", q, _expand_kv(k, q.shape[2]))


def _gqa_out(weights, v):
    """weights: (b, h, s, t), v: (b,t,kv,k) -> (b,s,h,k)."""
    return jnp.einsum("bhst,bthk->bshk", weights, _expand_kv(v, weights.shape[1]))


# query head j = n * g + i belongs to kv head n, as jnp.repeat(k, g, axis=2)
# assigns it.  Under serve rules n takes the cache's kv-head sharding, so the
# cache is read where it lies; where kv heads are unsharded, i takes the
# query heads' sharding when it divides (else _serve_grouped keeps flat h)
_GROUPED_Q = ("batch", "seq", "kv_heads", "heads", None)  # (b, s, kv, g, hd)
_GROUPED_SCORES = ("batch", "kv_heads", "heads", "seq", None)  # (b, kv, g, s, t)


def _serve_grouped(h, kv):
    """Whether the serving block contracts grouped queries: for GQA, unless
    the ambient mesh rules would split the grouped head axes (kv, g) over
    fewer devices than the flat query-head axis.  That happens where
    neither factor divides the model axis while h does — starcoder2-15b's
    (4, 12) on a 16-wide axis — and GSPMD would then replicate the scores
    on every model shard; the flat layout against the repeated cache
    (:func:`_gqa_scores`) keeps them sharded there.  MHA (g == 1) has
    nothing to group and keeps the flat einsum."""
    g = h // kv
    return g > 1 and (shard_count(("kv_heads", "heads"), (kv, g))
                      >= shard_count(("heads",), (h,)))


def _group_queries(q, kv):
    """q: (b, s, h, hd) -> (b, s, kv, g, hd), query head j at (j // g, j % g)."""
    b, s, h, hd = q.shape
    return q.reshape(b, s, kv, h // kv, hd)


def _grouped_scores(qg, k):
    """qg: (b, s, kv, g, hd)  k: (b, t, kv, hd) -> scores (b, kv, g, s, t).
    Each kv head is contracted against its own query group, so K is read
    as it lies."""
    return jnp.einsum("bsngk,btnk->bngst", qg, k)


def _grouped_out(w, v):
    """w: (b, kv, g, s, t)  v: (b, t, kv, hd) -> (b, s, h, hd)."""
    b, kv, g, s, _ = w.shape
    out = jnp.einsum("bngst,btnk->bsngk", w, v)
    return out.reshape(b, s, kv * g, v.shape[-1])


def _per_kv_line(scale, n):
    """int8 cache scales (b, t, kv) -> (b, n, 1, 1, t) for scores laid out
    as n head groups: folded per kv head (n == kv) or repeated to each
    query head (n == h, the flat layout)."""
    s = jnp.moveaxis(scale, 1, 2)
    s = s if n == s.shape[1] else jnp.repeat(s, n // s.shape[1], axis=1)
    return s[:, :, None, None, :]


def attention_train(
    p,
    cfg,
    x,
    *,
    mode: str = "causal",
    window: Optional[int] = None,
    kv_x: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    kv_positions: Optional[jax.Array] = None,
    q_chunk: int = 1024,
):
    """Full-sequence attention (training / prefill).

    mode: "causal" | "window" | "bidir" | "cross".  For "cross", ``kv_x`` is
    the encoder output.

    For seq > q_chunk, queries are processed in chunks via lax.scan (the
    memory-efficient / flash-style schedule — on real TPU this layer is where
    a Pallas flash kernel slots in; the XLA formulation keeps the dry-run's
    peak memory honest).  "window" mode restricts each query chunk to a fixed
    kv band of width (window + q_chunk), keeping windowed attention
    sub-quadratic in both memory AND flops.
    """
    b, s, d = x.shape
    xkv = x if kv_x is None else kv_x
    t = xkv.shape[1]
    q_pos = positions if positions is not None else jnp.arange(s)
    kv_pos = kv_positions if kv_positions is not None else jnp.arange(t)
    use_rope = cfg.pos == "rope" and mode != "cross"
    q, k, v = _project_qkv(p, cfg, x, xkv, q_pos, kv_pos, use_rope=use_rope)
    scale = cfg.d_head**-0.5  # compile-time constant; kept exact (docs/numerics.md)

    sdt = jnp.dtype(getattr(cfg, "scores_dtype", "float32"))
    if s <= q_chunk or s % q_chunk != 0:
        scores = _gqa_scores(q, k).astype(sdt) * scale
        scores = scores + _mask(mode, q_pos, kv_pos, window)[None, None].astype(sdt)
        scores = checkpoint_name(scores, "attn_scores")
        w = _softmax_scores(scores, x.dtype)
        out = _gqa_out(w, v)
    else:
        out = _chunked_attention(q, k, v, mode, window, q_pos, kv_pos, scale, q_chunk, sdt)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))


def _chunked_attention(q, k, v, mode, window, q_pos, kv_pos, scale, q_chunk, sdt=None):
    """Scan over query chunks; per chunk the full (or banded) KV is visible."""
    sdt = sdt or jnp.float32
    b, s, h, hd = q.shape
    n_chunks = s // q_chunk
    qc = q.reshape(b, n_chunks, q_chunk, h, hd)
    pc = q_pos.reshape(n_chunks, q_chunk)

    banded = mode == "window" and window is not None
    if banded:
        # kv band: [chunk_start - band + q_chunk, chunk_start + q_chunk)
        band = window + q_chunk
        pad = band - q_chunk
        k_pad = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        v_pad = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
        kv_pos_pad = jnp.pad(kv_pos, (pad, 0), constant_values=-(10**9))

    def chunk_body(_, idx):
        qi = qc[:, idx]
        pi = pc[idx]
        if banded:
            start = idx * q_chunk  # in padded coords the band ends at start+band
            ki = jax.lax.dynamic_slice_in_dim(k_pad, start, band, 1)
            vi = jax.lax.dynamic_slice_in_dim(v_pad, start, band, 1)
            kp = jax.lax.dynamic_slice_in_dim(kv_pos_pad, start, band, 0)
        else:
            ki, vi, kp = k, v, kv_pos
        sc = _gqa_scores(qi, ki).astype(sdt) * scale
        sc = sc + _mask(mode, pi, kp, window)[None, None].astype(sdt)
        sc = checkpoint_name(sc, "attn_scores")
        w = _softmax_scores(sc, q.dtype)
        return None, _gqa_out(w, vi)

    _, out = jax.lax.scan(chunk_body, None, jnp.arange(n_chunks))
    # out: (n_chunks, b, q_chunk, h, hd) -> (b, s, h, hd)
    return jnp.moveaxis(out, 0, 1).reshape(b, s, h, hd)


# ---------------------------------------------------------------------------
# Decode path with KV cache
# ---------------------------------------------------------------------------


def init_kv_cache(cfg, batch, cache_len, dtype, *, quantized: bool = False):
    """One layer's cache. quantized=True stores int8 KV + per (b,t,h) scales
    (beyond-paper optimization in the approximate-computing spirit; halves
    the decode memory roofline term — see EXPERIMENTS.md §Perf)."""
    kv, hd = cfg.n_kv_heads, cfg.d_head
    shape = (batch, cache_len, kv, hd)
    if quantized:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:3], jnp.float32),
            "v_scale": jnp.zeros(shape[:3], jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def kv_cache_specs(quantized: bool = False):
    base = {
        "k": ("batch", "kv_seq", "kv_heads", "kv_dim"),
        "v": ("batch", "kv_seq", "kv_heads", "kv_dim"),
    }
    if quantized:
        base["k_scale"] = ("batch", "kv_seq", "kv_heads")
        base["v_scale"] = ("batch", "kv_seq", "kv_heads")
    return base


def _quantize_kv(x):
    scale = jnp.max(jnp.abs(x), axis=-1) / 127.0 + 1e-8
    q = jnp.round(x / scale[..., None]).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _dequantize(q, scale, dtype):
    return q.astype(dtype) * scale[..., None].astype(dtype)


def _cache_update(buf, new, slot, layer_idx):
    """Write one token line in place.  ``buf`` is (b, t, h, d) per-layer, or
    (L, b, t, h, d) stacked when ``layer_idx`` is given — the scan-friendly
    form: the carried cache is updated with a single small DUS, never
    re-materialized."""
    if layer_idx is None:
        return jax.lax.dynamic_update_index_in_dim(buf, new, slot, 1)
    upd = new[None, :, None] if new.ndim + 2 == buf.ndim else new[None]
    start = (layer_idx, 0, slot) + (0,) * (buf.ndim - 3)
    return jax.lax.dynamic_update_slice(buf, upd.astype(buf.dtype), start)


def _cache_update_slots(buf, new, slots, layer_idx):
    """Per-slot variant of :func:`_cache_update`: row ``b`` of ``new`` lands
    at its own time index ``slots[b]`` (a (b,) vector — each batch row is an
    independent request with its own position counter).  One scatter of b
    token lines; like the DUS it updates the carried cache in place."""
    rows = jnp.arange(new.shape[0])
    if layer_idx is None:
        return buf.at[rows, slots].set(new.astype(buf.dtype))
    return buf.at[layer_idx, rows, slots].set(new.astype(buf.dtype))


def _cache_read(buf, layer_idx):
    return buf if layer_idx is None else jax.lax.dynamic_index_in_dim(
        buf, layer_idx, 0, keepdims=False
    )


def _prefill_update(buf, new, layer_idx):
    """Write tokens [0, s) of one cache buffer in a single DUS.  ``new`` is
    (b, s, ...); with ``layer_idx`` the buffer carries a leading stacked
    (L, ...) axis and only this layer's plane is touched."""
    if layer_idx is None:
        return jax.lax.dynamic_update_slice(
            buf, new.astype(buf.dtype), (0,) * buf.ndim
        )
    start = (layer_idx,) + (0,) * (buf.ndim - 1)
    return jax.lax.dynamic_update_slice(buf, new[None].astype(buf.dtype), start)


def _prefill_write_entries(cache, entries, *, layer_idx, ring):
    """Land per-buffer (b, s, ...) prompt tensors in the cache, one DUS
    each.  Only ring buffers (sliding-window layers) may be shorter than
    the prompt — there the last ``cache_len`` tokens survive, rolled so
    token ``pos`` sits at its decode slot ``pos % cache_len``; quantized
    values and scales are per-token, so rolling them is exact."""
    t_axis = 1 if layer_idx is None else 2
    cache_len = cache["k"].shape[t_axis]
    s = entries["k"].shape[1]
    if s > cache_len:
        if not ring:
            raise ValueError(
                f"prompt ({s} tokens) does not fit a non-ring cache of "
                f"length {cache_len}; allocate >= prompt_len + gen_len slots"
            )
        shift = s % cache_len  # slot of the oldest surviving token
        entries = {
            name: jnp.roll(a[:, -cache_len:], shift, axis=1)
            for name, a in entries.items()
        }
    return dict(
        cache,
        **{
            name: _prefill_update(cache[name], a, layer_idx)
            for name, a in entries.items()
        },
    )


def _quantized_entries(k_new, v_new):
    """Quantize full-sequence K/V through the same :func:`_quantize_kv` path
    the decode write uses (the scale reduce vectorizes over the token axis,
    so per-token values and scales are bit-identical to the step-loop's)."""
    kq, ks = _quantize_kv(k_new)
    vq, vs = _quantize_kv(v_new)
    return {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}


def prefill_cache_write(cache, k_new, v_new, *, layer_idx=None, ring=False):
    """Batched analogue of the decode write: tokens [0, s) of ``k_new`` /
    ``v_new`` (b, s, kv, hd) land in the cache via one dynamic_update_slice
    per buffer, instead of s per-token line writes.  int8 caches quantize
    through the decode write's path; ``ring=True`` (sliding-window layers)
    allows a cache shorter than the prompt — see _prefill_write_entries."""
    if cache["k"].dtype == jnp.int8:
        entries = _quantized_entries(k_new, v_new)
    else:
        entries = {"k": k_new, "v": v_new}
    return _prefill_write_entries(cache, entries, layer_idx=layer_idx, ring=ring)


def _fold_masked_attention(q, k, v, mask, scale, k_scale, v_scale, out_dtype):
    """The decode-contract scored-attention block, shared by
    :func:`attention_decode` and :func:`attention_prefill` so the
    prefill-vs-decode bit-exactness contract lives in ONE place: fp32
    scores, int8 cache scales FOLDED into scores / weights (never a
    dequantized cache copy), additive fp32 mask, fp32 softmax.

    Scores and the weighted sum are grouped-query contractions
    (:func:`_grouped_scores`): each kv head meets its g query heads, and
    the cache is never expanded to h heads.  In decode the expansion
    (:func:`_expand_kv`, which the training paths keep) would be a g-fold
    copy of the whole cache in every layer of every step.  MHA, and a mesh
    whose model axis divides h but neither kv nor g
    (:func:`_serve_grouped`), take the flat layout: scores (b, h, 1, sq, t)
    against the repeated cache.

    q: (b, sq, h, hd); k/v: (b, t, kv, hd), int8 values pre-cast to
    ``out_dtype``; mask: (sq, t) additive, or (b, sq, t) when validity is
    per batch row (slot-scheduled decode); scales: (b, t, kv) or None.
    Returns (b, sq, h, hd) — the wo projection stays with the caller.
    """
    grouped = _serve_grouped(q.shape[2], k.shape[2])
    if grouped:
        qg = constrain(_group_queries(q, k.shape[2]), _GROUPED_Q)
        scores = constrain(_grouped_scores(qg, k), _GROUPED_SCORES)
    else:
        scores = _gqa_scores(q, k)[:, :, None]
    scores = scores.astype(jnp.float32) * scale  # (b, n, g, sq, t)
    if k_scale is not None:
        scores = scores * _per_kv_line(k_scale, scores.shape[1])
    scores = scores + (mask if mask.ndim == 2 else mask[:, None, None])
    w = jax.nn.softmax(scores, axis=-1).astype(out_dtype)
    if v_scale is not None:
        w = w * _per_kv_line(v_scale, w.shape[1]).astype(w.dtype)
    return _grouped_out(w, v) if grouped else _gqa_out(w[:, :, 0], v)


def attention_prefill(p, cfg, x, cache, positions, *, window: Optional[int] = None,
                      layer_idx=None, q_chunk: int = 1024):
    """Full-sequence causal (or sliding-window) attention over the prompt
    that also writes tokens [0, s) of the KV cache in one shot.

    x: (b, s, d); ``cache`` must be empty (prefill owns positions [0, s)).
    Attention runs over the in-flight K/V — not a cache readback — through
    the same scored-attention block as :func:`attention_decode`
    (fp32 scores, folded int8 scales), so prefill is bit-exact against the
    step loop.  Prompts longer than ``q_chunk`` process queries in chunks
    (lax.scan) so the fp32 score tensor stays (b, h, q_chunk, s) instead of
    O(s^2) — softmax is per query row, so chunking preserves the contract.
    Returns (out, new_cache).
    """
    b, s, d = x.shape
    use_rope = cfg.pos == "rope"
    q, k, v = _project_qkv(p, cfg, x, x, positions, positions, use_rope=use_rope)
    # mesh serving (no-ops single-device): heads over 'model', batch over DP —
    # the cache write below then scatters shard-local rows, no collectives
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    ring = window is not None
    k_scale = v_scale = None
    if cache["k"].dtype == jnp.int8:
        # quantize ONCE: the written entries and the in-flight scoring K/V
        # share the same quantization
        entries = _quantized_entries(k, v)
        cache = _prefill_write_entries(cache, entries, layer_idx=layer_idx, ring=ring)
        k = entries["k"].astype(x.dtype)
        v = entries["v"].astype(x.dtype)
        k_scale, v_scale = entries["k_scale"], entries["v_scale"]
    else:
        cache = _prefill_write_entries(
            cache, {"k": k, "v": v}, layer_idx=layer_idx, ring=ring
        )

    scale = cfg.d_head**-0.5
    mode = "window" if window else "causal"
    if s <= q_chunk or s % q_chunk:
        mask = _mask(mode, positions, positions, window)
        out = _fold_masked_attention(q, k, v, mask, scale, k_scale, v_scale, x.dtype)
    else:
        nc = s // q_chunk
        qc = jnp.moveaxis(q.reshape(b, nc, q_chunk, *q.shape[2:]), 1, 0)
        pc = positions.reshape(nc, q_chunk)

        def chunk_body(_, inp):
            qi, pi = inp
            m = _mask(mode, pi, positions, window)
            return None, _fold_masked_attention(
                qi, k, v, m, scale, k_scale, v_scale, x.dtype
            )

        _, out = jax.lax.scan(chunk_body, None, (qc, pc))
        out = jnp.moveaxis(out, 0, 1).reshape(b, s, *q.shape[2:])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), cache


def decode_route(cfg, cache_dtype, kernel: Optional[str] = None) -> Optional[str]:
    """The route of cached decode attention: "fused" (the blocked Pallas
    kernel, ``kernels/attention``), "reference" (its jnp oracle) or None
    (the inline XLA block).  ``kernel``, else ``cfg.decode_kernel``, forces
    one.  Otherwise the kernel serves where it compiles and reads the cache
    as it lies: the dispatch layer resolves the compiled backend (a TPU),
    the cache holds floats (an int8 cache folds its scales inline), and no
    mesh rules scope is active (a sharded cache would need a shard_map)."""
    kernel = kernel if kernel is not None else getattr(cfg, "decode_kernel", None)
    if kernel is not None:
        if kernel not in ("fused", "reference"):
            raise ValueError(
                f"unknown decode kernel {kernel!r}; expected 'fused' or 'reference'"
            )
        return kernel
    from repro.kernels import dispatch

    if (dispatch.resolve_backend() == "compiled"
            and jnp.issubdtype(cache_dtype, jnp.floating)
            and current_rules() is None):
        return "fused"
    return None


def _decode_attend(cfg, x, q, k_new, v_new, cache, pos, *, window, layer_idx,
                   kernel, active):
    """The cached part of :func:`attention_decode`, between the q/k/v and
    output projections: write the new token's K/V line, read the cache back
    and attend over it.  Returns (per-head out (b, 1, h, hd), new cache)."""
    b = x.shape[0]
    t_axis = 1 if layer_idx is None else 2
    cache_len = cache["k"].shape[t_axis]
    quantized = cache["k"].dtype == jnp.int8
    per_slot = pos.ndim == 1
    # ring-buffer slot; for full caches cache_len covers all positions so
    # this is just ``pos``
    slot = jnp.asarray(pos % cache_len, jnp.int32)
    write = _cache_update_slots if per_slot else _cache_update
    if quantized:
        kq, ks = _quantize_kv(k_new[:, 0])
        vq, vs = _quantize_kv(v_new[:, 0])
        cache = {
            "k": write(cache["k"], kq, slot, layer_idx),
            "v": write(cache["v"], vq, slot, layer_idx),
            "k_scale": write(cache["k_scale"], ks, slot, layer_idx),
            "v_scale": write(cache["v_scale"], vs, slot, layer_idx),
        }
    else:
        cache = {
            "k": write(cache["k"], k_new[:, 0], slot, layer_idx),
            "v": write(cache["v"], v_new[:, 0], slot, layer_idx),
        }

    route = decode_route(cfg, cache["k"].dtype, kernel)
    if route is not None:
        # the kernel reads the written buffers as they lie, stacked or per
        # layer, and builds the validity mask from per-row positions; the
        # scalar lock-step case broadcasts to the per-slot form
        from repro.kernels.attention import ops as attn_kernel

        fn = (attn_kernel.ref_decode_attention if route == "reference"
              else attn_kernel.decode_attention)
        scales = ((cache["k_scale"], cache["v_scale"]) if quantized
                  else (None, None))
        pos_b = pos if per_slot else jnp.broadcast_to(pos, (b,))
        out = fn(
            q[:, 0], cache["k"], cache["v"], pos_b, *scales, layer_idx, active,
            scale=cfg.d_head**-0.5, wrap=bool(window),
        )[:, None]
        return out, cache

    k_scale = v_scale = None
    if quantized:
        # scales are FOLDED into the scores / attention weights rather than
        # materializing a dequantized cache copy (saves 2 full-cache HBM
        # passes per layer; on TPU the int8->bf16 convert fuses into the
        # matmul — §Perf decode study It2)
        k = _cache_read(cache["k"], layer_idx).astype(x.dtype)
        v = _cache_read(cache["v"], layer_idx).astype(x.dtype)
        k_scale = _cache_read(cache["k_scale"], layer_idx)  # (b, t, kv)
        v_scale = _cache_read(cache["v_scale"], layer_idx)
    else:
        k = _cache_read(cache["k"], layer_idx)
        v = _cache_read(cache["v"], layer_idx)

    # mask out unwritten slots: before the ring wraps only slots <= pos hold
    # tokens (treating unwritten zero-K slots as valid leaks exp(0) mass
    # into early softmaxes); once pos >= cache_len every slot is live.
    # Per-slot pos makes this mask per batch row, which is also what isolates
    # a reused slot from its previous occupant: a freshly admitted request
    # only ever attends to cache lines at positions it owns.
    t_idx = jnp.arange(cache_len)
    if per_slot:
        valid = t_idx[None, :] <= pos[:, None]  # (b, t)
        if window:
            valid = valid | (pos[:, None] >= cache_len)
        mask = jnp.where(valid, 0.0, NEG_INF)[:, None, :]  # (b, 1, t)
    else:
        valid = t_idx <= pos
        if window:
            valid = valid | (pos >= cache_len)
        mask = jnp.where(valid, 0.0, NEG_INF)[None, :]  # (1, t) additive
    out = _fold_masked_attention(
        q, k, v, mask, cfg.d_head**-0.5, k_scale, v_scale, x.dtype
    )
    return out, cache


def attention_decode(p, cfg, x, cache, pos, *, window: Optional[int] = None,
                     layer_idx=None, kernel: Optional[str] = None,
                     norm_levels=None, active=None):
    """Single-token decode. x: (b, 1, d); cache holds ``cache_len`` slots.

    ``pos`` is either a scalar (lock-step batch: every row at the same
    position) or a (b,) vector (slot-scheduled serving: each batch row is an
    independent request with its own position counter — RoPE, the ring-buffer
    write index and the validity mask all follow per row).

    For sliding-window layers the cache is a ring buffer of size ``window``.
    With ``layer_idx``, cache tensors carry a leading stacked-layers axis and
    are updated in place (see _cache_update).  Returns (out, new_cache).

    The scored attention takes the route :func:`decode_route` picks:
    ``kernel`` (defaults to ``cfg.decode_kernel``) forces "fused", the
    blocked Pallas kernel via the dispatch layer, or "reference", its
    pure-jnp oracle (same blocks, same arithmetic); left unset, the kernel
    serves on a TPU for a float cache outside any mesh rules scope, and
    the inline XLA block everywhere else.  The projections, cache write
    and wo projection are identical on every route.

    ``active`` ((b,) bool, optional): rows that are not live.  On the
    kernel route they read no cache block and attend to zeros (their
    logits are discarded by the slot scheduler); None means every row is
    live.  The inline path reads every row as before.

    ``norm_levels`` (accuracy-SLO serving, (b,) int32): per-slot ladder rung
    for the qk-norm rsqrt when ``cfg.sqrt_ladder`` is set; None keeps the
    single-datapath path bit-for-bit.

    The cache write, the cache read and the scored attention run under
    ``jax.named_scope("decode_attention")`` (metadata only); the q/k/v and
    output projections are weight matmuls and stay outside it.
    """
    assert x.shape[1] == 1
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1

    # rope position of the new token: (1,) broadcasts over the batch in the
    # scalar case; (b, 1) rotates each row at its own position
    kv_pos_q = pos[:, None] if per_slot else jnp.asarray([0], jnp.int32) + pos
    use_rope = cfg.pos == "rope"
    q, k_new, v_new = _project_qkv(
        p, cfg, x, x, kv_pos_q, kv_pos_q, use_rope=use_rope, norm_levels=norm_levels
    )
    # mesh serving (no-ops single-device): per serve_rules the token line each
    # row writes is kv-head-sharded like the cache itself, so the per-slot
    # ring write stays a shard-local scatter
    q = constrain(q, ("batch", "seq", "heads", None))
    k_new = constrain(k_new, ("batch", "seq", "kv_heads", None))
    v_new = constrain(v_new, ("batch", "seq", "kv_heads", None))

    with jax.named_scope("decode_attention"):
        out, cache = _decode_attend(
            cfg, x, q, k_new, v_new, cache, pos, window=window,
            layer_idx=layer_idx, kernel=kernel, active=active,
        )
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, cache


# ---------------------------------------------------------------------------
# Speculative decode: multi-token verify reads + rollback-safe commits
# ---------------------------------------------------------------------------


def attention_verify(p, cfg, x, cache, pos, *, window: Optional[int] = None,
                     layer_idx=None, norm_levels=None):
    """Draft-verify attention: score ``sq`` candidate rows per slot against
    the cache in ONE forward, WITHOUT committing any cache write.

    x: (b, sq, d) — row ``j`` is the token the slot would feed at position
    ``pos[b] + j`` (row 0 the committed next token, rows 1.. the drafts);
    pos: (b,) per-slot position of row 0.  Returns ``(out, entries)``:
    ``out`` (b, sq, d) the attention output per row, ``entries`` the per-row
    cache lines (quantized for int8 caches, exactly what the sequential step
    write would have landed) for :func:`verify_cache_commit` to commit once
    the accepted prefix is known.  The cache operand is left untouched —
    rollback is "never wrote", not "un-write".

    Bit-exactness contract (the headline spec-decode guarantee): row ``j``'s
    output equals the sequential :func:`attention_decode` step at position
    ``pos + j`` after feeding rows ``0..j-1``, bit-for-bit.  Each row scores
    against a per-row effective K/V — the old cache with rows ``j' <= j``
    substituted at their ring slots ``(pos + j') % cache_len`` — built by
    an exact one-hot gather, so the score vector has the same slot order,
    the same fp32 values and the same softmax summation order the
    sequential step sees.  Requires ``sq <= cache_len`` (distinct slots
    within the block; for sliding-window layers that means k+1 <= window).
    """
    b, sq, d = x.shape
    t_axis = 1 if layer_idx is None else 2
    cache_len = cache["k"].shape[t_axis]
    if sq > cache_len:
        raise ValueError(
            f"verify block of {sq} rows exceeds cache_len {cache_len}; "
            "speculation needs k+1 <= window for sliding-window layers"
        )
    quantized = cache["k"].dtype == jnp.int8
    pos = jnp.asarray(pos, jnp.int32)
    offs = jnp.arange(sq, dtype=jnp.int32)
    posr = pos[:, None] + offs[None, :]  # (b, sq) absolute row positions
    use_rope = cfg.pos == "rope"
    q, k_new, v_new = _project_qkv(
        p, cfg, x, x, posr, posr, use_rope=use_rope, norm_levels=norm_levels
    )
    q = constrain(q, ("batch", "seq", "heads", None))
    k_new = constrain(k_new, ("batch", "seq", "kv_heads", None))
    v_new = constrain(v_new, ("batch", "seq", "kv_heads", None))

    # slot occupancy of the in-flight rows: match[b, j, t] == row j's ring
    # slot is t; written[b, j, t] == some row j' <= j lands at slot t (rows
    # are distinct mod cache_len since sq <= cache_len)
    t_idx = jnp.arange(cache_len)
    slots = posr % cache_len
    match = slots[:, :, None] == t_idx[None, None, :]  # (b, sq, t)
    written = jnp.cumsum(match.astype(jnp.int32), axis=1) > 0

    if quantized:
        # quantize through the sequential write's path: the scale reduce is
        # per line, so values and scales are bit-identical to stepping
        kq, ks = _quantize_kv(k_new)
        vq, vs = _quantize_kv(v_new)
        entries = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        k_lines, v_lines = kq.astype(x.dtype), vq.astype(x.dtype)
        k_old = _cache_read(cache["k"], layer_idx).astype(x.dtype)
        v_old = _cache_read(cache["v"], layer_idx).astype(x.dtype)
        onehot_s = match.astype(jnp.float32)
        ks_at = jnp.einsum("bjt,bjn->btn", onehot_s, ks)  # (b, t, kv)
        vs_at = jnp.einsum("bjt,bjn->btn", onehot_s, vs)
        sel_s = written[..., None]  # (b, sq, t, 1)
        ks_old = _cache_read(cache["k_scale"], layer_idx)
        vs_old = _cache_read(cache["v_scale"], layer_idx)
        k_scale_eff = jnp.where(sel_s, ks_at[:, None], ks_old[:, None])
        v_scale_eff = jnp.where(sel_s, vs_at[:, None], vs_old[:, None])
    else:
        entries = {"k": k_new, "v": v_new}
        k_lines, v_lines = k_new, v_new
        k_old = _cache_read(cache["k"], layer_idx)
        v_old = _cache_read(cache["v"], layer_idx)
        k_scale_eff = v_scale_eff = None

    # per-row effective K/V: the one-hot matmul copies each in-flight line to
    # its slot exactly (one 1.0 coefficient, rest exact zeros), then rows
    # select in-flight vs old per slot — slot ORDER (softmax summation order)
    # is identical to the sequential step's cache layout
    onehot = match.astype(x.dtype)
    k_at = jnp.einsum("bjt,bjnh->btnh", onehot, k_lines)  # (b, t, kv, hd)
    v_at = jnp.einsum("bjt,bjnh->btnh", onehot, v_lines)
    sel = written[..., None, None]  # (b, sq, t, 1, 1)
    k_eff = jnp.where(sel, k_at[:, None], k_old[:, None])  # (b, sq, t, kv, hd)
    v_eff = jnp.where(sel, v_at[:, None], v_old[:, None])

    h = q.shape[2]
    g = h // k_eff.shape[3]
    k_exp = k_eff if g == 1 else jnp.repeat(k_eff, g, axis=3)
    v_exp = v_eff if g == 1 else jnp.repeat(v_eff, g, axis=3)
    scale = cfg.d_head**-0.5
    scores = jnp.einsum("bjhk,bjthk->bhjt", q, k_exp).astype(jnp.float32) * scale
    if k_scale_eff is not None:
        ks_h = jnp.moveaxis(k_scale_eff, 3, 1)  # (b, kv, sq, t)
        ks_h = ks_h if g == 1 else jnp.repeat(ks_h, g, axis=1)
        scores = scores * ks_h
    # per-row validity: row j sees exactly what the sequential step at
    # pos + j sees (its own line included — the write-then-attend order)
    valid = t_idx[None, None, :] <= posr[:, :, None]  # (b, sq, t)
    if window:
        valid = valid | (posr[:, :, None] >= cache_len)
    scores = scores + jnp.where(valid, 0.0, NEG_INF)[:, None]
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    if v_scale_eff is not None:
        vs_h = jnp.moveaxis(v_scale_eff, 3, 1)
        vs_h = vs_h if g == 1 else jnp.repeat(vs_h, g, axis=1)
        w = w * vs_h.astype(w.dtype)
    out = jnp.einsum("bhjt,bjthk->bjhk", w, v_exp)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype)), entries


def verify_cache_commit(cache, entries, pos, n_commit, *, stacked: bool = False):
    """Commit the accepted prefix of a verify block: rows ``j < n_commit[b]``
    of ``entries`` land at their ring slots; rejected rows write back the
    slot's prior content bit-for-bit (gather-then-select), so rollback is a
    no-op write — the cache after commit equals the sequential step loop's
    after feeding exactly the accepted tokens.

    entries: per-buffer (b, sq, ...) from :func:`attention_verify`, or
    (L, b, sq, ...) with ``stacked=True`` (uniform layer stacks — one
    scatter per buffer covers every layer plane); pos / n_commit: (b,).
    Rows whose slot wraps past a dense cache's capacity are only ever
    rejected rows (the scheduler truncates ``n_commit`` by the slot
    budget), and their write-back-old is harmless by construction.
    """
    t_axis = 2 if stacked else 1
    cache_len = cache["k"].shape[t_axis]
    lead = 1 if stacked else 0
    b, sq = entries["k"].shape[lead], entries["k"].shape[lead + 1]
    pos = jnp.asarray(pos, jnp.int32)
    n_commit = jnp.asarray(n_commit, jnp.int32)
    offs = jnp.arange(sq, dtype=jnp.int32)
    slots = (pos[:, None] + offs[None, :]) % cache_len  # (b, sq)
    keep = offs[None, :] < n_commit[:, None]  # (b, sq)
    rows = jnp.arange(b)[:, None]
    out = dict(cache)
    for name, new in entries.items():
        buf = cache[name]
        if stacked:
            old = buf[:, rows, slots]  # (L, b, sq, ...)
            kb = keep.reshape((1, b, sq) + (1,) * (new.ndim - 3))
            sel = jnp.where(kb, new.astype(buf.dtype), old)
            out[name] = buf.at[:, rows, slots].set(sel)
        else:
            old = buf[rows, slots]  # (b, sq, ...)
            kb = keep.reshape((b, sq) + (1,) * (new.ndim - 2))
            sel = jnp.where(kb, new.astype(buf.dtype), old)
            out[name] = buf.at[rows, slots].set(sel)
    return out


# ---------------------------------------------------------------------------
# Cross-attention decode (enc-dec): encoder K/V are computed once.
# ---------------------------------------------------------------------------


def precompute_cross_kv(p, cfg, enc_out):
    k = jnp.einsum("btd,dhk->bthk", enc_out, p["wk"].astype(enc_out.dtype))
    v = jnp.einsum("btd,dhk->bthk", enc_out, p["wv"].astype(enc_out.dtype))
    if cfg.qk_norm:
        k = rmsnorm(p["k_norm"], k, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults)
    return {"ck": k, "cv": v}


def cross_attention_decode(p, cfg, x, cross_kv):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if cfg.qk_norm:
        q = rmsnorm(p["q_norm"], q, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults)
    scale = cfg.d_head**-0.5
    scores = _gqa_scores(q, cross_kv["ck"]).astype(jnp.float32) * scale
    w = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
    out = _gqa_out(w, cross_kv["cv"])
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))

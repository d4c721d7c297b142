"""Config-driven model zoo: decoder LMs (dense / MoE / SSM / hybrid / VLM)
and encoder-decoder (Whisper-style), with scanned layer stacks for uniform
architectures and unrolled stacks for mixed block patterns.

Entry points:
    init(cfg, key)                     -> (params, specs)
    forward(params, cfg, batch)        -> (logits, aux)
    init_cache(cfg, batch, cache_len)  -> (cache, specs)
    decode_step(params, cfg, cache, tokens, pos) -> (logits, cache)
    prefill(params, cfg, cache, tokens)          -> (logits, cache)
    generate_scan(params, cfg, cache, tok, start_pos, gen_len)
                                       -> (tokens, next_tok, cache)
    prefill_into_slots(params, cfg, cache, tokens, slots)
                                       -> (last logits, cache)
    decode_slots_scan(params, cfg, cache, tok, pos, active, remaining, n)
                                       -> (toks, emitted, tok, pos,
                                           active, remaining, cache)

Batch dict keys:
    tokens  (b, s) int32            — text tokens (decoder side)
    vision  (b, n_vis, d) optional  — VLM stub frontend embeddings
    audio   (b, n_ctx, d) optional  — whisper stub frontend embeddings
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.constraints import constrain, maybe_axis_rules
from repro.layers import attention as attn
from repro.layers import moe as moe_lib
from repro.layers import rglru as rglru_lib
from repro.layers import ssd as ssd_lib
from repro.layers.mlp import mlp_apply, mlp_init
from repro.layers.norms import (
    layernorm,
    layernorm_init,
    layernorm_select,
    rmsnorm,
    rmsnorm_init,
    rmsnorm_select,
)
from repro.layers.param import DenseInit
from repro.models.config import ModelConfig

__all__ = [
    "init",
    "forward",
    "init_cache",
    "decode_step",
    "prefill",
    "generate_scan",
    "slot_rows_like",
    "insert_cache_slots",
    "init_pool_state",
    "prefill_into_slots",
    "decode_slots_scan",
    "decode_verify_step",
    "commit_verify_cache",
    "draft_ngram",
    "decode_slots_spec_scan",
    "sample_tokens",
    "param_count",
]


def _act_dtype(cfg):
    return jnp.dtype(cfg.act_dtype)


# ---------------------------------------------------------------------------
# Norm helpers (rmsnorm vs layernorm have different param layouts)
# ---------------------------------------------------------------------------


def _norm_init(ini, name, cfg):
    if cfg.norm == "rmsnorm":
        rmsnorm_init(ini, name, cfg.d_model)
    else:
        layernorm_init(ini, name, cfg.d_model)


def _norm(p, name, x, cfg, levels=None):
    if levels is not None and cfg.sqrt_ladder is not None:
        # accuracy-SLO decode: each batch row's rsqrt routes through the
        # row's current ladder rung (docs/robustness.md §Accuracy SLO)
        if cfg.norm == "rmsnorm":
            return rmsnorm_select(
                p[name], x, levels, ladder=cfg.sqrt_ladder, faults=cfg.sqrt_faults
            )
        return layernorm_select(
            p[f"{name}_scale"], p[f"{name}_bias"], x, levels,
            ladder=cfg.sqrt_ladder, faults=cfg.sqrt_faults,
        )
    if cfg.norm == "rmsnorm":
        return rmsnorm(p[name], x, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults)
    return layernorm(
        p[f"{name}_scale"], p[f"{name}_bias"], x, sqrt_unit=cfg.sqrt_unit, faults=cfg.sqrt_faults
    )


def exact_twin(cfg: ModelConfig) -> ModelConfig:
    """The exact-datapath, fault-free twin of a config — the bottom rung of
    the engine's approximate→exact degradation ladder (docs/robustness.md)."""
    if cfg.sqrt_unit == "exact" and cfg.sqrt_faults is None and cfg.sqrt_ladder is None:
        return cfg
    return cfg.replace(sqrt_unit="exact", sqrt_faults=None, sqrt_ladder=None)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------


def _layer_init(cfg: ModelConfig, block: str, key, *, cross: bool = False, abstract=False):
    ini = DenseInit(key, _act_dtype(cfg), abstract=abstract)
    _norm_init(ini, "ln1", cfg)
    sub_init = ini.child
    if block in ("global", "window"):
        a = sub_init()
        attn.attention_init(a, cfg)
        ini.sub("attn", *a.build())
        _norm_init(ini, "ln2", cfg)
        if cfg.moe is not None:
            m = sub_init()
            moe_lib.moe_init(m, cfg)
            ini.sub("moe", *m.build())
        else:
            m = sub_init()
            mlp_init(m, cfg)
            ini.sub("mlp", *m.build())
        if cross:
            c = sub_init()
            attn.attention_init(c, cfg)
            ini.sub("xattn", *c.build())
            _norm_init(ini, "lnx", cfg)
    elif block == "ssd":
        m = sub_init()
        ssd_lib.ssd_init(m, cfg)
        ini.sub("mixer", *m.build())
    elif block == "rglru":
        m = sub_init()
        rglru_lib.rglru_init(m, cfg)
        ini.sub("mixer", *m.build())
        _norm_init(ini, "ln2", cfg)
        m2 = sub_init()
        mlp_init(m2, cfg)
        ini.sub("mlp", *m2.build())
    else:
        raise ValueError(block)
    return ini.build()


# ---------------------------------------------------------------------------
# Per-layer apply (train / prefill)
# ---------------------------------------------------------------------------


def _layer_train(p, cfg, block, x, positions, *, enc_out=None):
    x = constrain(x, ("batch", "seq", "embed"))
    aux = jnp.zeros((), jnp.float32)
    if block in ("global", "window"):
        h = _norm(p, "ln1", x, cfg)
        mode = "causal" if block == "global" else "window"
        h = attn.attention_train(
            p["attn"], cfg, h, mode=mode, window=cfg.window, positions=positions
        )
        x = x + h
        if enc_out is not None:
            h = _norm(p, "lnx", x, cfg)
            h = attn.attention_train(p["xattn"], cfg, h, mode="cross", kv_x=enc_out)
            x = x + h
        h = _norm(p, "ln2", x, cfg)
        if cfg.moe is not None:
            h, aux = moe_lib.moe_apply(p["moe"], cfg, h, capacity_factor=cfg.moe.capacity_factor)
        else:
            h = mlp_apply(p["mlp"], cfg, h)
        x = x + h
    elif block == "ssd":
        x = x + ssd_lib.ssd_train(p["mixer"], cfg, _norm(p, "ln1", x, cfg))
    elif block == "rglru":
        x = x + rglru_lib.rglru_train(p["mixer"], cfg, _norm(p, "ln1", x, cfg))
        x = x + mlp_apply(p["mlp"], cfg, _norm(p, "ln2", x, cfg))
    return constrain(x, ("batch", "seq", "embed")), aux


def _remat_wrapper(cfg):
    """Remat policy for the layer stack:
      "none"      store everything (needs microbatching at scale)
      "block"     full per-layer rematerialization (max recompute)
      "minimal"   store everything EXCEPT attention scores (flash-style
                  selective remat: bwd recomputes only the O(s^2) tensors)
    """
    if cfg.remat == "none":
        return lambda f: f
    if cfg.remat == "minimal":
        policy = jax.checkpoint_policies.save_anything_except_these_names("attn_scores")
        return lambda f: jax.checkpoint(f, policy=policy)
    return lambda f: jax.checkpoint(f)


# ---------------------------------------------------------------------------
# Encoder (whisper-style, bidirectional)
# ---------------------------------------------------------------------------


def _enc_layer_init(cfg, key, *, abstract=False):
    ini = DenseInit(key, _act_dtype(cfg), abstract=abstract)
    _norm_init(ini, "ln1", cfg)
    a = ini.child()
    attn.attention_init(a, cfg)
    ini.sub("attn", *a.build())
    _norm_init(ini, "ln2", cfg)
    m = ini.child()
    mlp_init(m, cfg)
    ini.sub("mlp", *m.build())
    return ini.build()


def _enc_layer(p, cfg, x):
    h = _norm(p, "ln1", x, cfg)
    x = x + attn.attention_train(p["attn"], cfg, h, mode="bidir")
    x = x + mlp_apply(p["mlp"], cfg, _norm(p, "ln2", x, cfg))
    return x


def _sinusoidal(n, d):
    pos = np.arange(n)[:, None]
    i = np.arange(d // 2)[None, :]
    angle = pos / np.power(10000.0, 2 * i / d)
    return jnp.asarray(
        np.concatenate([np.sin(angle), np.cos(angle)], -1), jnp.float32
    )


# ---------------------------------------------------------------------------
# Full-model init
# ---------------------------------------------------------------------------


def _stacked_init(init_fn, key, n, *, abstract=False):
    """Stack a per-layer init over n layers (leading 'layers' axis).
    Abstract: (shapes, logical specs).  Concrete: (vmapped params, None) —
    the specs come from the abstract pass."""
    if abstract:
        layer, specs = init_fn(key)
        params = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((n, *s.shape), s.dtype), layer
        )
        specs = jax.tree.map(
            lambda s: ("layers", *s),
            specs,
            is_leaf=lambda s: isinstance(s, tuple)
            and all(isinstance(e, (str, type(None))) for e in s),
        )
        return params, specs
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: init_fn(k)[0])(keys), None


def init(cfg: ModelConfig, key, *, abstract: bool = False):
    """Initialize a model from its config.  Returns ``(params, specs)``:
    ``params`` the parameter pytree in the activation dtype (uniform stacks
    carry a leading 'layers' axis for the scanned forward), ``specs`` the
    matching tree of logical-axis tuples that ``distributed.shardings_for``
    maps onto a mesh.  ``abstract=True`` returns ShapeDtypeStructs instead
    of arrays — free, for deriving shardings or dry-run lowering.

    Concrete params are drawn and cast inside one jitted program, so the
    fp32 draws of a full layer stack never outlive it on the device.
    Training casts the result to fp32 master weights itself."""
    cfg.validate()
    shapes, specs = _init_tree(cfg, key, abstract=True)
    if abstract:
        return shapes, specs
    return _init_params(cfg, key), specs


@functools.partial(jax.jit, static_argnums=0)
def _init_params(cfg: ModelConfig, key):
    return _init_tree(cfg, key, abstract=False)[0]


def _init_tree(cfg: ModelConfig, key, *, abstract: bool):
    ini = DenseInit(key, _act_dtype(cfg), abstract=abstract)
    vp = cfg.padded_vocab
    ini.add("embed", (vp, cfg.d_model), ("vocab", "embed"), scale=float(np.sqrt(cfg.d_model)))
    if not cfg.tie_embeddings:
        ini.add("unembed", (cfg.d_model, vp), ("embed", "vocab"))
    _norm_init(ini, "ln_f", cfg)
    if cfg.vision_tokens:
        # VLM stub frontend: a projection from precomputed patch embeddings
        ini.add("vision_proj", (cfg.d_model, cfg.d_model), ("embed", None))

    cross = cfg.kind == "encdec"
    blocks = cfg.blocks
    if cfg.uniform:
        layer_fn = lambda k: _layer_init(cfg, blocks[0], k, cross=cross, abstract=abstract)
        params, specs = _stacked_init(layer_fn, ini._next(), cfg.n_layers, abstract=abstract)
        ini.sub("layers", params, specs)
    else:
        layers_p, layers_s = [], []
        for b in blocks:
            p, s = _layer_init(cfg, b, ini._next(), cross=cross, abstract=abstract)
            layers_p.append(p)
            layers_s.append(s)
        ini.sub("layers", layers_p, layers_s)

    if cross:
        enc_fn = lambda k: _enc_layer_init(cfg, k, abstract=abstract)
        pe, se = _stacked_init(enc_fn, ini._next(), cfg.encoder.n_layers, abstract=abstract)
        ini.sub("encoder", pe, se)
        e2 = ini.child()
        _norm_init(e2, "enc_ln_f", cfg)
        pp, ss = e2.build()
        ini.sub("enc_extra", pp, ss)
    return ini.build()


# ---------------------------------------------------------------------------
# Forward (train / prefill)
# ---------------------------------------------------------------------------


def _run_encoder(params, cfg, audio):
    x = audio.astype(_act_dtype(cfg))
    x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(x.dtype)[None]

    def body(x, p):
        return _enc_layer(p, cfg, x), None

    if cfg.remat == "block":
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(body, x, params["encoder"])
    return _norm(params["enc_extra"], "enc_ln_f", x, cfg)


def _embed_inputs(params, cfg, batch):
    dt = _act_dtype(cfg)
    tokens = batch["tokens"]
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    if cfg.vision_tokens:
        v = batch["vision"].astype(dt)
        v = jnp.einsum("bnd,de->bne", v, params["vision_proj"].astype(dt))
        x = jnp.concatenate([v, x], axis=1)
    if cfg.pos == "sinusoidal":
        x = x + _sinusoidal(x.shape[1], cfg.d_model).astype(dt)[None]
    return x


def forward(params, cfg: ModelConfig, batch, *, return_hidden: bool = False):
    """Returns (logits over the token positions, aux dict).  With
    ``return_hidden`` the unembed matmul is left to the caller (the train
    loss computes it in sequence chunks so the fp32 logits buffer is never
    materialized whole — see steps.loss_fn)."""
    x = _embed_inputs(params, cfg, batch)
    positions = jnp.arange(x.shape[1])
    enc_out = None
    if cfg.kind == "encdec":
        enc_out = _run_encoder(params, cfg, batch["audio"])

    aux_total = jnp.zeros((), jnp.float32)
    blocks = cfg.blocks
    remat_wrap = _remat_wrapper(cfg)
    if cfg.uniform:

        def body(carry, p):
            x, aux = carry
            x, a = _layer_train(p, cfg, blocks[0], x, positions, enc_out=enc_out)
            return (x, aux + a), None

        body = remat_wrap(body)
        (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), params["layers"])
    else:
        for p, b in zip(params["layers"], blocks):
            fn = functools.partial(_layer_train, cfg=cfg, block=b, positions=positions, enc_out=enc_out)
            wrapped = remat_wrap(lambda p, x, fn=fn: fn(p, x=x))
            x, a = wrapped(p, x)
            aux_total = aux_total + a

    x = _norm(params, "ln_f", x, cfg)
    if cfg.vision_tokens:
        x = x[:, cfg.vision_tokens :]  # logits over text positions only
    aux = {"moe_aux": aux_total / max(1, len(blocks))}
    unembed = (
        params["embed"].T if cfg.tie_embeddings else params["unembed"]
    ).astype(x.dtype)
    if return_hidden:
        return (x, unembed), aux
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits, aux


# ---------------------------------------------------------------------------
# KV-cache / state init
# ---------------------------------------------------------------------------


def _layer_cache(cfg, block, batch, cache_len, dtype, quantized):
    if block == "global":
        c = attn.init_kv_cache(cfg, batch, cache_len, dtype, quantized=quantized)
        s = attn.kv_cache_specs(quantized)
    elif block == "window":
        c = attn.init_kv_cache(
            cfg, batch, min(cache_len, cfg.window), dtype, quantized=quantized
        )
        s = attn.kv_cache_specs(quantized)
    elif block == "ssd":
        c = ssd_lib.init_ssd_state(cfg, batch, dtype)
        s = ssd_lib.ssd_state_specs()
    elif block == "rglru":
        c = rglru_lib.init_rglru_state(cfg, batch, dtype)
        s = rglru_lib.rglru_state_specs()
    else:
        raise ValueError(block)
    return c, s


def init_cache(
    cfg: ModelConfig, batch: int, cache_len: int, *, quantized=False, abstract=False
):
    """Returns (cache, specs).  Uniform stacks get a leading 'layers' axis."""
    dtype = _act_dtype(cfg)
    mk = (
        (lambda shape, a: jax.ShapeDtypeStruct(shape, a.dtype))
        if abstract
        else (lambda shape, a: jnp.zeros(shape, a.dtype))
    )
    if cfg.uniform:
        c, s = _layer_cache(cfg, cfg.blocks[0], batch, cache_len, dtype, quantized)
        c = jax.tree.map(lambda a: mk((cfg.n_layers, *a.shape), a), c)
        s = jax.tree.map(
            lambda sp: ("layers", *sp),
            s,
            is_leaf=lambda sp: isinstance(sp, tuple)
            and all(isinstance(e, (str, type(None))) for e in sp),
        )
        return c, s
    caches, specs = [], []
    for b in cfg.blocks:
        c, s = _layer_cache(cfg, b, batch, cache_len, dtype, quantized)
        if abstract:
            c = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), c)
        caches.append(c)
        specs.append(s)
    return caches, specs


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def _layer_decode(p, cfg, block, x, cache, pos, *, cross_kv=None, layer_idx=None, levels=None):
    """One decoder layer step.  With ``layer_idx`` the cache tree is the full
    stacked (L, ...) carry and only this layer's line is touched (in-place
    DUS — the production decode pattern: per-step HBM traffic is one layer
    read + one token write, not a cache re-materialization).

    ``levels`` (accuracy-SLO serving): per-row ladder rung for every norm
    rsqrt in the layer, including the qk-norm inside attention_decode."""
    if block in ("global", "window"):
        h = _norm(p, "ln1", x, cfg, levels)
        h, cache = attn.attention_decode(
            p["attn"], cfg, h, cache, pos,
            window=cfg.window if block == "window" else None,
            layer_idx=layer_idx,
            norm_levels=levels,
        )
        x = x + h
        if cross_kv is not None:
            x = x + attn.cross_attention_decode(
                p["xattn"], cfg, _norm(p, "lnx", x, cfg, levels), cross_kv
            )
        h = _norm(p, "ln2", x, cfg, levels)
        if cfg.moe is not None:
            h, _ = moe_lib.moe_apply(p["moe"], cfg, h, capacity_factor=cfg.moe.capacity_factor)
        else:
            h = mlp_apply(p["mlp"], cfg, h)
        x = x + h
    elif block == "ssd":
        st = ssd_lib.read_state(cache, layer_idx)
        h, new_st = ssd_lib.ssd_decode(p["mixer"], cfg, _norm(p, "ln1", x, cfg, levels), st)
        cache = ssd_lib.write_state(cache, new_st, layer_idx)
        x = x + h
    elif block == "rglru":
        st = ssd_lib.read_state(cache, layer_idx)
        h, new_st = rglru_lib.rglru_decode(p["mixer"], cfg, _norm(p, "ln1", x, cfg, levels), st)
        cache = ssd_lib.write_state(cache, new_st, layer_idx)
        x = x + h
        x = x + mlp_apply(p["mlp"], cfg, _norm(p, "ln2", x, cfg, levels))
    return x, cache


def decode_step(params, cfg: ModelConfig, cache, tokens, pos, *, cross_kv=None, unit_levels=None):
    """One decode forward (a single token per batch row) over the cache.

    tokens: (b, 1) int32; pos: int32 position of this token — a scalar
    (lock-step batch) or a (b,) vector (slot-scheduled serving, one position
    counter per batch row; threaded through RoPE / sinusoidal PE, the cache
    write index and the validity mask — see attention_decode).

    Mesh-aware: inside an ``axis_rules(mesh, serve_rules(...))`` scope (the
    Engine's ``mesh=`` mode, ``lm.prefill(mesh=...)``) the activation /
    logits constraints below pin the batch axis to the data axes and the
    vocab axis to 'model'; outside any scope they are no-ops.

    ``unit_levels`` ((b,) int32, requires ``cfg.sqrt_ladder``): accuracy-SLO
    serving — every norm rsqrt (layer norms, qk-norm, final norm) routes each
    row through its ladder rung; None keeps the single-datapath trace.

    Returns (logits (b, 1, vocab), new_cache).
    """
    dt = _act_dtype(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    x = constrain(x, ("batch", "seq", "embed"))
    if cfg.pos == "sinusoidal":
        # absolute sinusoid at ``pos``: (d,) for scalar pos, (b, d) per slot
        d = cfg.d_model
        i = jnp.arange(d // 2, dtype=jnp.float32)
        ang = pos.astype(jnp.float32)[..., None] / jnp.power(10000.0, 2 * i / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + (pe[:, None] if pe.ndim == 2 else pe).astype(dt)

    blocks = cfg.blocks
    if cfg.uniform:
        # stacked cache rides in the CARRY and is updated in place per layer
        idxs = jnp.arange(cfg.n_layers)
        if cross_kv is not None:

            def body(carry, layer):
                x, c = carry
                p, ckv, i = layer
                x, c = _layer_decode(
                    p, cfg, blocks[0], x, c, pos, cross_kv=ckv, layer_idx=i,
                    levels=unit_levels,
                )
                return (x, c), None

            (x, new_cache), _ = jax.lax.scan(
                body, (x, cache), (params["layers"], cross_kv, idxs)
            )
        else:

            def body(carry, layer):
                x, c = carry
                p, i = layer
                x, c = _layer_decode(
                    p, cfg, blocks[0], x, c, pos, layer_idx=i, levels=unit_levels
                )
                return (x, c), None

            (x, new_cache), _ = jax.lax.scan(body, (x, cache), (params["layers"], idxs))
    else:
        new_cache = []
        for p, b, c in zip(params["layers"], blocks, cache):
            x, c = _layer_decode(p, cfg, b, x, c, pos, cross_kv=cross_kv, levels=unit_levels)
            new_cache.append(c)

    x = _norm(params, "ln_f", x, cfg, unit_levels)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"]).astype(dt)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits[..., : cfg.vocab], new_cache


# ---------------------------------------------------------------------------
# Serving fast path: one-shot prefill + scan-based greedy decode
# ---------------------------------------------------------------------------


def _layer_prefill(p, cfg, block, x, cache, positions, *, cross_kv=None, layer_idx=None):
    """One decoder layer over the whole prompt, writing its cache slice in a
    single batched update: attention layers DUS tokens [0, s) of their KV
    buffers (quantizing through the decode write's path for int8 caches);
    SSM / RG-LRU layers write the recurrent state after the last token."""
    if block in ("global", "window"):
        h = _norm(p, "ln1", x, cfg)
        h, cache = attn.attention_prefill(
            p["attn"], cfg, h, cache, positions,
            window=cfg.window if block == "window" else None,
            layer_idx=layer_idx,
        )
        x = x + h
        if cross_kv is not None:
            x = x + attn.cross_attention_decode(p["xattn"], cfg, _norm(p, "lnx", x, cfg), cross_kv)
        h = _norm(p, "ln2", x, cfg)
        if cfg.moe is not None:
            h, _ = moe_lib.moe_apply(p["moe"], cfg, h, capacity_factor=cfg.moe.capacity_factor)
        else:
            h = mlp_apply(p["mlp"], cfg, h)
        x = x + h
    elif block == "ssd":
        h, st = ssd_lib.ssd_train(p["mixer"], cfg, _norm(p, "ln1", x, cfg), return_state=True)
        cache = ssd_lib.write_state(cache, st, layer_idx)
        x = x + h
    elif block == "rglru":
        h, st = rglru_lib.rglru_train(
            p["mixer"], cfg, _norm(p, "ln1", x, cfg), return_state=True
        )
        cache = ssd_lib.write_state(cache, st, layer_idx)
        x = x + h
        x = x + mlp_apply(p["mlp"], cfg, _norm(p, "ln2", x, cfg))
    return x, cache


def prefill(params, cfg: ModelConfig, cache, tokens, *, cross_kv=None,
            last_logit_only: bool = False, mesh=None, rules=None):
    """One-shot batched prefill: a single full-sequence forward over the
    prompt that writes positions [0, s) of every layer's cache, replacing
    the token-at-a-time teacher-forcing loop (s decode_step dispatches and
    s masked full-cache attention passes collapse into one causal forward).

    tokens: (b, s) int32 with s >= 1; ``cache`` must be freshly initialized
    (prefill owns positions [0, s)).  Returns (logits (b, s, vocab), cache);
    logits at position i condition on tokens [0, i], so
    ``argmax(logits[:, -1])`` is the first generated token.  Serving wants
    only that last column — ``last_logit_only`` skips the other s-1 unembed
    rows (s x fewer unembed FLOPs, no (b, s, vocab) buffer) and returns
    (b, 1, vocab).

    Matches stepping :func:`decode_step` over the prompt for attention /
    SSM / RG-LRU stacks (float caches reproduce the step-loop's cache
    contents; int8 caches quantize through the same path).  MoE layers
    route with a sequence-level expert capacity during prefill, so
    dropped-token behavior may differ from per-token stepping.

    ``mesh=`` (with an optional ``rules=`` table, default
    ``serve_rules(cfg, mesh)``) traces the forward inside an ``axis_rules``
    scope so the activation constraints resolve against the mesh — params
    TP-sharded over 'model', batch and the KV cache's slot axis over the
    data axes, per docs/serving.md.  Single-device callers omit it and every
    constraint is a no-op.
    """
    if mesh is not None:
        if rules is None:
            from repro.distributed.sharding import serve_rules

            rules = serve_rules(cfg, mesh)
        with maybe_axis_rules(mesh, rules):
            return prefill(params, cfg, cache, tokens, cross_kv=cross_kv,
                           last_logit_only=last_logit_only)
    b, s = tokens.shape
    if s < 1:
        raise ValueError(
            f"prefill needs at least one prompt token, got tokens shape {tokens.shape}"
        )
    dt = _act_dtype(cfg)
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    x = constrain(x, ("batch", "seq", "embed"))
    positions = jnp.arange(s)
    if cfg.pos == "sinusoidal":
        x = x + _sinusoidal(s, cfg.d_model).astype(dt)[None]

    blocks = cfg.blocks
    if cfg.uniform:
        # stacked cache rides in the CARRY, one layer plane written per step
        idxs = jnp.arange(cfg.n_layers)
        if cross_kv is not None:

            def body(carry, layer):
                x, c = carry
                p, ckv, i = layer
                x, c = _layer_prefill(
                    p, cfg, blocks[0], x, c, positions, cross_kv=ckv, layer_idx=i
                )
                return (x, c), None

            (x, cache), _ = jax.lax.scan(
                body, (x, cache), (params["layers"], cross_kv, idxs)
            )
        else:

            def body(carry, layer):
                x, c = carry
                p, i = layer
                x, c = _layer_prefill(p, cfg, blocks[0], x, c, positions, layer_idx=i)
                return (x, c), None

            (x, cache), _ = jax.lax.scan(body, (x, cache), (params["layers"], idxs))
    else:
        new_cache = []
        for p, bk, c in zip(params["layers"], blocks, cache):
            x, c = _layer_prefill(p, cfg, bk, x, c, positions, cross_kv=cross_kv)
            new_cache.append(c)
        cache = new_cache

    if last_logit_only:
        x = x[:, -1:]
    x = _norm(params, "ln_f", x, cfg)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"]).astype(dt)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits[..., : cfg.vocab], cache


def generate_scan(params, cfg: ModelConfig, cache, tok, start_pos, gen_len: int,
                  *, cross_kv=None, mesh=None, rules=None):
    """Greedy decode as ONE device call: a ``lax.scan`` over ``gen_len``
    decode_steps, replacing the per-token Python dispatch loop.

    tok: (b, 1) int32, the first token to feed (usually the prefill argmax);
    start_pos: scalar int32 position of that token; gen_len must be static.
    Returns (tokens (b, gen_len), next_tok (b, 1), cache); tokens[:, 0] ==
    tok — the same convention as the loop baseline (each emitted token is
    the one *fed* at that step) — and ``next_tok`` is the argmax after the
    last step, so a follow-up call continues generation seamlessly.  Jit
    with ``donate_argnums`` on the cache and token operands: both reappear
    in the output (cache carry, next_tok), so donation aliases their buffers
    instead of holding a second full-size cache alive across the call.

    ``mesh=`` / ``rules=`` as in :func:`prefill`: trace the scan inside an
    ``axis_rules`` scope so each decode step's constraints bind to the mesh.
    """
    if mesh is not None:
        if rules is None:
            from repro.distributed.sharding import serve_rules

            rules = serve_rules(cfg, mesh)
        with maybe_axis_rules(mesh, rules):
            return generate_scan(params, cfg, cache, tok, start_pos, gen_len,
                                 cross_kv=cross_kv)
    start_pos = jnp.asarray(start_pos, jnp.int32)

    def step(carry, i):
        c, t = carry
        logits, c = decode_step(params, cfg, c, t, start_pos + i, cross_kv=cross_kv)
        nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(t.dtype)
        return (c, nxt), t[:, 0]

    (cache, next_tok), toks = jax.lax.scan(
        step, (cache, tok), jnp.arange(gen_len, dtype=jnp.int32)
    )
    return jnp.moveaxis(toks, 0, 1), next_tok, cache


# ---------------------------------------------------------------------------
# Slot-scheduled serving: continuous batching over a KV-cache slot pool
# ---------------------------------------------------------------------------


def _slot_batch_axis(cfg) -> int:
    """Axis of the batch dim in cache leaves: uniform stacks carry a leading
    stacked-layers axis, so batch is axis 1; per-layer lists put it at 0."""
    return 1 if cfg.uniform else 0


def init_pool_state(cfg: ModelConfig, num_slots: int, cache_len: int, *,
                    quantized: bool = False, key=None, abstract: bool = False):
    """The engine's complete device-side slot-pool state as ONE pytree::

        {"cache":     lm.init_cache tree (all cache families, float/int8),
         "tok":       (b, 1) int32   next token each slot feeds,
         "pos":       (b,)   int32   per-slot position counters,
         "active":    (b,)   bool    slot liveness,
         "remaining": (b,)   int32   per-slot generation budgets,
         "keys":      (b, 2) uint32  per-slot PRNG key pool}

    This single tree is the serialization unit for crash-consistent serving:
    ``Engine.reset`` builds the live pool from it, ``Engine.snapshot`` writes
    exactly this tree through ``checkpoint.save``, and ``Engine.resume``
    passes the ``abstract=True`` form as the restore target (elastic
    resharding included).  ``key``: split into the per-slot PRNG pool;
    without it (or in abstract mode) the keys leaf is zeros / a
    ShapeDtypeStruct of the same (b, 2) uint32 layout.
    """
    cache, _ = init_cache(cfg, num_slots, cache_len, quantized=quantized,
                          abstract=abstract)
    b = num_slots
    mk = (
        (lambda shape, dt: jax.ShapeDtypeStruct(shape, jnp.dtype(dt)))
        if abstract
        else (lambda shape, dt: jnp.zeros(shape, dt))
    )
    if key is not None and not abstract:
        keys = jax.random.split(key, b)
    else:
        keys = mk((b, 2), jnp.uint32)
    return {
        "cache": cache,
        "tok": mk((b, 1), jnp.int32),
        "pos": mk((b,), jnp.int32),
        "active": mk((b,), jnp.bool_),
        "remaining": mk((b,), jnp.int32),
        "keys": keys,
    }


def slot_rows_like(cfg: ModelConfig, cache, k: int):
    """A fresh zeroed cache for ``k`` requests, shaped like ``cache`` with the
    batch axis resized — the staging area a new request prefills into before
    its rows are landed in the live pool."""
    ax = _slot_batch_axis(cfg)
    return jax.tree.map(
        lambda a: jnp.zeros(a.shape[:ax] + (k,) + a.shape[ax + 1 :], a.dtype), cache
    )


def insert_cache_slots(cfg: ModelConfig, cache, rows, slots):
    """Land per-request cache rows in the live pool: row ``i`` of every leaf
    of ``rows`` overwrites batch row ``slots[i]`` of ``cache``.  Whole-row
    writes, so any stale KV / recurrent state from the slot's previous
    occupant is cleared wholesale; jit with the live cache donated and the
    scatter updates it in place without disturbing active slots."""
    slots = jnp.asarray(slots, jnp.int32)
    if cfg.uniform:
        return jax.tree.map(
            lambda buf, r: buf.at[:, slots].set(r.astype(buf.dtype)), cache, rows
        )
    return jax.tree.map(
        lambda buf, r: buf.at[slots].set(r.astype(buf.dtype)), cache, rows
    )


def prefill_into_slots(params, cfg: ModelConfig, cache, tokens, slots, *,
                       cross_kv=None, mesh=None, rules=None):
    """Admit new requests into a *live* slot pool mid-decode: a batch-k
    :func:`prefill` into fresh staging rows (identical math and cache layout
    to a solo prefill — the parity anchor), then one whole-row scatter per
    cache buffer into ``slots`` of the donated live cache.  Rows the prompt
    does not reach stay zero and are masked by the per-slot validity mask in
    ``attention_decode`` until the new occupant writes them.

    tokens: (k, s) int32 prompts (one length bucket per call — group ragged
    admissions by length so each bucket compiles once); slots: (k,) int32.
    Returns (last-token logits (k, 1, vocab), new_cache).

    ``mesh=`` / ``rules=`` as in :func:`prefill`: the staging prefill and the
    whole-row scatter into the (batch-over-data sharded) live pool trace
    inside an ``axis_rules`` scope, so admission stays one dispatch on a
    mesh too.
    """
    if mesh is not None:
        if rules is None:
            from repro.distributed.sharding import serve_rules

            rules = serve_rules(cfg, mesh)
        with maybe_axis_rules(mesh, rules):
            return prefill_into_slots(params, cfg, cache, tokens, slots,
                                      cross_kv=cross_kv)
    k = tokens.shape[0]
    rows = slot_rows_like(cfg, cache, k)
    logits, rows = prefill(
        params, cfg, rows, tokens, cross_kv=cross_kv, last_logit_only=True
    )
    return logits, insert_cache_slots(cfg, cache, rows, slots)


def sample_tokens(logits, pos, keys, temperature, top_k):
    """Per-slot next-token choice from (b, v) fp32 logits.  Greedy when
    ``temperature`` is 0; otherwise each row draws from its own PRNG stream,
    folded on the row's position so a request's samples depend only on its
    key and its token index — independent of which slot it landed in or who
    else shares the batch."""
    if not temperature:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / temperature
    if top_k:
        kth = jnp.sort(lg, axis=-1)[:, -top_k, None]
        lg = jnp.where(lg >= kth, lg, -jnp.inf)

    def one(lg_row, p, key):
        return jax.random.categorical(jax.random.fold_in(key, p), lg_row)

    return jax.vmap(one)(lg, pos, keys).astype(jnp.int32)


def decode_slots_scan(params, cfg: ModelConfig, cache, tok, pos, active,
                      remaining, n_steps: int, *, eos_id=None,
                      temperature: float = 0.0, top_k: int = 0, keys=None,
                      cross_kv=None, mesh=None, rules=None,
                      with_health: bool = False, logits_hook=None,
                      unit_levels=None, canary_stride: int = 0,
                      canary_offset=None):
    """Slot-scheduled decode: ``n_steps`` decode_steps under one ``lax.scan``
    where every batch row is an independent request.

    tok (b, 1) next token each slot will feed; pos (b,) its position; active
    (b,) bool whether the slot holds a live request; remaining (b,) int32
    tokens the slot may still emit; keys (b,) PRNG keys, REQUIRED when
    ``temperature`` > 0 and expected to be request-derived (slot-index keys
    would tie a request's samples to its slot placement — the Engine passes
    uid-keyed streams).  Inactive slots re-feed their last token at a
    frozen position — their logits are discarded, their emissions masked, and
    row-wise math keeps them from perturbing live slots, so a staggered slot
    decodes bit-identically to a solo :func:`generate_scan` of the same
    request (greedy, non-MoE).

    Per step each active slot emits the token it FEEDS (the
    :func:`generate_scan` convention), advances ``pos``, decrements
    ``remaining``, and goes inactive once its budget is spent or the token it
    just emitted is ``eos_id`` (the EOS itself is emitted).  Returns
    (toks (b, n_steps), emitted (b, n_steps) bool, tok, pos, active,
    remaining, cache) — every donated operand reappears, so jit with
    ``donate_argnums`` on (cache, tok, pos, active, remaining) aliases the
    pool buffers across chunks.

    ``mesh=`` / ``rules=`` as in :func:`prefill`: the whole chunk traces
    inside an ``axis_rules`` scope so each step's constraints bind batch to
    the data axes and heads/vocab to 'model' — the chunk stays ONE dispatch
    on the mesh (the scan carries the sharded pool, no per-step host trips).

    ``with_health=True`` appends two per-slot health signals to the return
    tuple — ``bad`` (b,) bool: some decode step of this chunk produced a
    non-finite logit while the slot was active; ``mx`` (b,) f32: the max
    |logit| seen while active (the engine's magnitude sentinel) — computed
    as two cheap row reductions inside the same scan, riding the chunk's
    existing single host sync (docs/robustness.md).  ``logits_hook``
    (fp32 logits -> fp32 logits) is applied to each step's last-position
    logits before health/sampling — the fault model's activation-injection
    point; detectors see exactly what sampling sees.

    Accuracy-SLO extensions (docs/robustness.md §Accuracy SLO):

    * ``unit_levels`` ((b,) int32, requires ``cfg.sqrt_ladder``) — per-slot
      datapath ladder rung for every norm rsqrt; rows at level 0 compute
      bit-identically to the plain path, so an all-zero vector is a no-op.
    * ``canary_stride=N`` (static; 0 disables) — every step whose *global*
      index ``canary_offset + i`` is ≡ 0 (mod N), recompute that step's
      logits through :func:`exact_twin`'s datapath from the same pre-step
      cache read (the shadow's cache write is discarded — no second cache
      write survives, no second dispatch happens) and reduce four per-slot
      stats onto the chunk's single sync: ``canary_checks`` (i32 canaries
      run while active), ``canary_divergences`` (i32 argmax disagreements),
      ``canary_max_rel`` (f32 max over canaries of max|served−exact| /
      max|exact|), ``canary_red_sum`` (f32 sum of per-canary mean relative
      logit deviation — an online MRED in the spirit of
      ``core/metrics.py``; divide by checks for the running mean).  The
      served logits compared are post-``logits_hook`` (what sampling sees);
      the shadow never applies the hook — it is the trusted reference.
      ``canary_offset`` is a traced scalar so the cadence continues across
      chunks without retracing.  The canary lane is read-only: it must not
      perturb tokens (asserted by the SLO suite).
    """
    if mesh is not None:
        if rules is None:
            from repro.distributed.sharding import serve_rules

            rules = serve_rules(cfg, mesh)
        with maybe_axis_rules(mesh, rules):
            return decode_slots_scan(
                params, cfg, cache, tok, pos, active, remaining, n_steps,
                eos_id=eos_id, temperature=temperature, top_k=top_k,
                keys=keys, cross_kv=cross_kv,
                with_health=with_health, logits_hook=logits_hook,
                unit_levels=unit_levels, canary_stride=canary_stride,
                canary_offset=canary_offset,
            )
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, bool)
    remaining = jnp.asarray(remaining, jnp.int32)
    if temperature and keys is None:
        raise ValueError(
            "temperature sampling needs per-request PRNG keys (a (b,) keys "
            "array); slot-index defaults would break replay reproducibility"
        )
    canary = bool(canary_stride)
    if canary:
        ecfg = exact_twin(cfg)
        offset = jnp.asarray(0 if canary_offset is None else canary_offset, jnp.int32)
    if unit_levels is not None:
        if cfg.sqrt_ladder is None:
            raise ValueError("unit_levels requires cfg.sqrt_ladder to be set")
        unit_levels = jnp.asarray(unit_levels, jnp.int32)

    def step(carry, i):
        cache, tok, pos, active, remaining = carry[:5]
        tail = 5
        if with_health:
            bad, mx = carry[tail], carry[tail + 1]
            tail += 2
        if canary:
            cc, cd, cmr, crs = carry[tail:tail + 4]
        logits, new_cache = decode_step(
            params, cfg, cache, tok, pos, cross_kv=cross_kv, unit_levels=unit_levels
        )
        lg = logits[:, -1].astype(jnp.float32)
        if logits_hook is not None:
            lg = logits_hook(lg)
        if canary:
            fire = ((offset + i) % canary_stride) == 0

            def shadow(op):
                # reads the PRE-step cache (the same read the served step
                # saw); the shadow's own cache write is dropped on the floor
                c, t, p, served = op
                el, _ = decode_step(params, ecfg, c, t, p, cross_kv=cross_kv)
                el = el[:, -1].astype(jnp.float32)
                agree = jnp.argmax(served, axis=-1) == jnp.argmax(el, axis=-1)
                ed = jnp.abs(served - el)
                ref = jnp.abs(el)
                rel = (jnp.max(ed, axis=-1)
                       / jnp.maximum(jnp.max(ref, axis=-1), 1e-20))
                red = jnp.mean(ed / jnp.maximum(ref, 1e-20), axis=-1)
                return agree, rel, red

            def no_shadow(op):
                b_ = op[3].shape[0]
                return (jnp.ones((b_,), bool), jnp.zeros((b_,), jnp.float32),
                        jnp.zeros((b_,), jnp.float32))

            # the whole vocab-wide reduction lives INSIDE the cond: a
            # non-canary step pays only the scalar predicate, not O(vocab)
            agree, rel, red = jax.lax.cond(
                fire, shadow, no_shadow, (cache, tok, pos, lg)
            )
            upd = fire & active
            cc = cc + upd.astype(jnp.int32)
            cd = cd + (upd & ~agree).astype(jnp.int32)
            # NaN-corrupted served logits make rel NaN; the health latch is
            # the authoritative signal there, exactly as for ``mx``
            cmr = jnp.maximum(cmr, jnp.where(upd, rel, 0.0))
            crs = crs + jnp.where(upd, red, 0.0)
        cache = new_cache
        if with_health:
            finite = jnp.all(jnp.isfinite(lg), axis=-1)
            bad = bad | (active & ~finite)
            # a NaN row makes mx NaN from here on; harmless — `bad` has
            # already latched for that slot and is the authoritative signal
            step_mx = jnp.max(jnp.abs(lg), axis=-1)
            mx = jnp.maximum(mx, jnp.where(active, step_mx, 0.0))
        nxt = sample_tokens(lg, pos, keys, temperature, top_k)
        fed = tok[:, 0]
        remaining = remaining - active.astype(jnp.int32)
        still = active & (remaining > 0)
        if eos_id is not None:
            still = still & (fed != eos_id)
        new_pos = pos + active.astype(jnp.int32)
        new_tok = jnp.where(active[:, None], nxt[:, None], tok)
        out = [cache, new_tok, new_pos, still, remaining]
        if with_health:
            out += [bad, mx]
        if canary:
            out += [cc, cd, cmr, crs]
        return tuple(out), (fed, active)

    b = tok.shape[0]
    carry0 = [cache, tok, pos, active, remaining]
    if with_health:
        carry0 += [jnp.zeros(b, bool), jnp.zeros(b, jnp.float32)]
    if canary:
        carry0 += [
            jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.float32),
        ]
    xs = jnp.arange(n_steps, dtype=jnp.int32) if canary else None
    fin, (toks, emitted) = jax.lax.scan(step, tuple(carry0), xs, length=n_steps)
    cache, tok, pos, active, remaining = fin[:5]
    return (
        jnp.moveaxis(toks, 0, 1),
        jnp.moveaxis(emitted, 0, 1),
        tok,
        pos,
        active,
        remaining,
        cache,
    ) + tuple(fin[5:])


# ---------------------------------------------------------------------------
# Speculative decoding: draft-and-verify over the slot pool
# ---------------------------------------------------------------------------


def _validate_spec_cfg(cfg: ModelConfig, *, what: str = "speculative decode"):
    """Speculation covers the cache families the exactness contract names
    (dense / ring / int8 KV): attention-only decoder stacks, greedy, no MoE
    routing (sequence-level capacity breaks per-row independence) and no
    recurrent state (SSM/RG-LRU steps cannot be verified position-parallel
    without replaying the recurrence)."""
    bad = [b for b in cfg.blocks if b not in ("global", "window")]
    if bad or cfg.moe is not None or cfg.kind != "decoder":
        raise ValueError(
            f"{what} supports attention-only decoder LMs "
            f"(dense/ring/int8 KV caches); got kind={cfg.kind!r}, "
            f"blocks={tuple(cfg.blocks)!r}, moe={cfg.moe is not None}"
        )


def _layer_verify(p, cfg, block, x, cache, pos, *, layer_idx=None, levels=None):
    """One decoder layer over a (b, sq) verify block — the multi-row twin of
    :func:`_layer_decode`'s attention branch.  Reads the cache, never writes
    it; returns (x, entries) with the layer's in-flight cache lines for
    :func:`commit_verify_cache`."""
    if block not in ("global", "window"):
        raise ValueError(f"verify step reached non-attention block {block!r}")
    h = _norm(p, "ln1", x, cfg, levels)
    h, entries = attn.attention_verify(
        p["attn"], cfg, h, cache, pos,
        window=cfg.window if block == "window" else None,
        layer_idx=layer_idx, norm_levels=levels,
    )
    x = x + h
    h = _norm(p, "ln2", x, cfg, levels)
    h = mlp_apply(p["mlp"], cfg, h)
    return x + h, entries


def decode_verify_step(params, cfg: ModelConfig, cache, tokens, pos, *,
                       unit_levels=None):
    """One draft-verify forward: score all ``sq = k+1`` candidate rows per
    slot against the cache in a single dispatch, committing NOTHING.

    tokens: (b, sq) int32 — column 0 the committed next token each slot
    would feed, columns 1.. its drafts; pos: (b,) the position of column 0.
    Returns (logits (b, sq, vocab), entries): row ``j``'s logits are
    bit-identical to sequential :func:`decode_step` at position ``pos + j``
    after feeding rows ``0..j-1`` (see ``attention_verify``), and
    ``entries`` carries every layer's in-flight cache lines (a stacked tree
    for uniform layer stacks, a per-layer list otherwise) for
    :func:`commit_verify_cache` once the accepted prefix is known.

    ``unit_levels`` as in :func:`decode_step`: per-slot ladder rungs apply
    to every row of the slot — a demoted slot's row 0 is bit-identical to
    its sequential demoted step, which is what keeps "speculation disabled"
    equal to "acceptance clamped to zero".
    """
    _validate_spec_cfg(cfg, what="decode_verify_step")
    dt = _act_dtype(cfg)
    pos = jnp.asarray(pos, jnp.int32)
    b, sq = tokens.shape
    x = jnp.take(params["embed"].astype(dt), tokens, axis=0)
    x = constrain(x, ("batch", "seq", "embed"))
    if cfg.pos == "sinusoidal":
        d = cfg.d_model
        i = jnp.arange(d // 2, dtype=jnp.float32)
        posr = pos[:, None] + jnp.arange(sq, dtype=jnp.int32)[None, :]
        ang = posr.astype(jnp.float32)[..., None] / jnp.power(10000.0, 2 * i / d)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
        x = x + pe.astype(dt)

    blocks = cfg.blocks
    if cfg.uniform:
        idxs = jnp.arange(cfg.n_layers)

        def body(x, layer):
            p, i = layer
            x, entries = _layer_verify(
                p, cfg, blocks[0], x, cache, pos, layer_idx=i, levels=unit_levels
            )
            return x, entries

        x, entries = jax.lax.scan(body, x, (params["layers"], idxs))
    else:
        entries = []
        for p, bk, c in zip(params["layers"], blocks, cache):
            x, e = _layer_verify(p, cfg, bk, x, c, pos, levels=unit_levels)
            entries.append(e)

    x = _norm(params, "ln_f", x, cfg, unit_levels)
    unembed = (params["embed"].T if cfg.tie_embeddings else params["unembed"]).astype(dt)
    logits = jnp.einsum("bsd,dv->bsv", x, unembed)
    logits = constrain(logits, ("batch", "seq", "vocab"))
    return logits[..., : cfg.vocab], entries


def commit_verify_cache(cfg: ModelConfig, cache, entries, pos, n_commit):
    """Commit the accepted prefix of a verify block into every layer's cache:
    rows ``j < n_commit[b]`` land at their ring slots, rejected rows write
    the slot's prior content back bit-for-bit (rollback = the write never
    happened).  ``entries`` is :func:`decode_verify_step`'s second output."""
    if cfg.uniform:
        return attn.verify_cache_commit(cache, entries, pos, n_commit, stacked=True)
    return [
        attn.verify_cache_commit(c, e, pos, n_commit)
        for c, e in zip(cache, entries)
    ]


def draft_ngram(hist, tok, pos, k: int):
    """Self-drafting n-gram / prompt lookup: propose the ``k`` tokens that
    followed the most recent prior occurrence of ``tok`` in the slot's fed
    history.  hist: (b, H) int32 — position ``p`` holds the token fed at
    step ``p`` for every ``p < pos[b]``; tok: (b,) the committed token about
    to be fed at ``pos``.  Draft positions past the written history (and
    slots with no match at all) fall back to repeating ``tok`` — greedy
    decode of small models loves short cycles, so the repeat is a decent
    period-1 guess.  Draft quality only moves the acceptance rate; row 0 of
    the verify block is always the committed token, so a bad draft can never
    cost correctness, only speed."""
    b, H = hist.shape
    idx = jnp.arange(H)
    cand = (hist == tok[:, None]) & (idx[None, :] < pos[:, None])
    p_star = jnp.max(jnp.where(cand, idx[None, :], -1), axis=1)  # (b,), -1 = none
    didx = p_star[:, None] + jnp.arange(1, k + 1, dtype=jnp.int32)[None, :]
    drafts = jnp.take_along_axis(hist, jnp.clip(didx, 0, H - 1), axis=1)
    usable = (p_star[:, None] >= 0) & (didx < pos[:, None])
    return jnp.where(usable, drafts, tok[:, None]).astype(jnp.int32)


def decode_slots_spec_scan(params, cfg: ModelConfig, cache, tok, pos, active,
                           remaining, hist, n_steps: int, *, k: int,
                           eos_id=None, with_health: bool = False,
                           logits_hook=None, unit_levels=None,
                           spec_disable=None, canary_stride: int = 0,
                           canary_offset=None, draft_params=None,
                           draft_cfg=None, draft_cache=None):
    """Draft-and-verify slot decode: ``n_steps`` speculative steps under one
    ``lax.scan``, each committing 1..k+1 tokens per active slot.

    Per step each active slot (i) drafts ``k`` candidates — self-drafting
    n-gram lookup over ``hist`` by default, or greedy continuation of a
    small draft model when ``draft_params``/``draft_cfg``/``draft_cache``
    are given — (ii) verifies the block ``[tok, drafts]`` in one
    :func:`decode_verify_step` forward, (iii) accepts the longest prefix of
    drafts agreeing with the verify argmaxes (truncated by the slot's
    budget and the first EOS among committed rows), and (iv) commits
    exactly the accepted rows' cache lines — rejected rows roll back to the
    pre-step cache content bit-for-bit.  Greedy only by construction: the
    acceptance rule compares argmaxes, so the emitted stream equals
    :func:`decode_slots_scan`'s token-for-token (the headline contract,
    enforced by tests/models/test_spec_decode.py).

    hist: (b, H) int32 fed-token history (prompt + emissions at positions
    [0, pos)) — the n-gram draft source, maintained in-scan; writes past H
    are dropped (drafting then degrades gracefully for ring stacks that
    outlive the buffer).  ``spec_disable`` (b,) bool clamps acceptance to 0
    for flagged slots (demoted rungs): they advance exactly one row — row 0
    IS the sequential step — per spec step.  ``with_health`` latches
    ``bad``/``mx`` over committed rows only (the sequential logit set).
    ``canary_stride`` fires the shadow-exact canary on row 0 of the block —
    always an accepted position, never a rejected draft — against the
    pre-step cache, on the spec-step clock (``canary_offset`` continues it
    across chunks).

    Returns (toks (b, n_steps*(k+1)), emitted (b, n_steps*(k+1)) bool, tok,
    pos, active, remaining, cache, hist, accepted (b,) i32 drafts accepted,
    spec_steps (b,) i32 active steps) — then ``draft_cache`` when drafting
    with a model, then health / canary extras as in
    :func:`decode_slots_scan`.  Emitted tokens are the tokens FED, exactly
    the sequential convention, so ``toks[emitted]`` concatenates across
    chunks of either scan.
    """
    _validate_spec_cfg(cfg)
    if k < 1:
        raise ValueError(f"speculation needs k >= 1 draft tokens, got k={k}")
    if "window" in cfg.blocks and k + 1 > cfg.window:
        raise ValueError(
            f"verify block k+1={k + 1} exceeds the sliding window "
            f"({cfg.window}); pick k <= window - 1"
        )
    use_draft = draft_params is not None
    if use_draft:
        if draft_cfg is None or draft_cache is None:
            raise ValueError("draft-model speculation needs draft_params, "
                             "draft_cfg and draft_cache together")
        _validate_spec_cfg(draft_cfg, what="draft model")
        if draft_cfg.vocab != cfg.vocab:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab} != target vocab {cfg.vocab}"
            )
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, bool)
    remaining = jnp.asarray(remaining, jnp.int32)
    hist = jnp.asarray(hist, jnp.int32)
    sq = k + 1
    canary = bool(canary_stride)
    if canary:
        ecfg = exact_twin(cfg)
        offset = jnp.asarray(0 if canary_offset is None else canary_offset, jnp.int32)
    if unit_levels is not None:
        if cfg.sqrt_ladder is None:
            raise ValueError("unit_levels requires cfg.sqrt_ladder to be set")
        unit_levels = jnp.asarray(unit_levels, jnp.int32)
    if spec_disable is not None:
        spec_disable = jnp.asarray(spec_disable, bool)
    b = tok.shape[0]
    offs = jnp.arange(sq, dtype=jnp.int32)
    rows_b = jnp.arange(b)[:, None]

    def step(carry, i):
        cache, tok, pos, active, remaining, hist, acc_cnt, step_cnt = carry[:8]
        tail = 8
        if use_draft:
            dcache = carry[tail]
            tail += 1
        if with_health:
            bad, mx = carry[tail], carry[tail + 1]
            tail += 2
        if canary:
            cc, cd, cmr, crs = carry[tail:tail + 4]

        # --- draft k candidates
        if use_draft:
            def dstep(c2, j):
                dc2, t2 = c2
                dlg, dc2 = decode_step(draft_params, draft_cfg, dc2, t2, pos + j)
                nx2 = jnp.argmax(dlg[:, -1], axis=-1).astype(jnp.int32)[:, None]
                return (dc2, nx2), nx2[:, 0]

            # the drafting pass runs on a throwaway copy of the draft cache;
            # the committed prefix re-lands below through the same
            # verify/commit path the target uses, so the draft cache tracks
            # committed tokens only
            _, drafts_t = jax.lax.scan(
                dstep, (dcache, tok), jnp.arange(k, dtype=jnp.int32)
            )
            drafts = jnp.moveaxis(drafts_t, 0, 1)  # (b, k)
        else:
            drafts = draft_ngram(hist, tok[:, 0], pos, k)

        # --- one batched verify forward over [tok, drafts]
        block = jnp.concatenate([tok, drafts], axis=1)  # (b, sq)
        logits, entries = decode_verify_step(
            params, cfg, cache, block, pos, unit_levels=unit_levels
        )
        lg = logits.astype(jnp.float32)  # (b, sq, vocab)
        if logits_hook is not None:
            # the fault model's injection point, applied per verify row —
            # committed rows see exactly what their sequential step would
            lg = jax.vmap(logits_hook, in_axes=1, out_axes=1)(lg)
        out_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)  # (b, sq) greedy

        # --- longest agreeing prefix, then budget / EOS truncation
        agree = drafts == out_tok[:, :-1]  # (b, k)
        acc = jnp.sum(jnp.cumprod(agree.astype(jnp.int32), axis=1), axis=1)
        if spec_disable is not None:
            acc = jnp.where(spec_disable, 0, acc)
        n_flow = jnp.minimum(acc + 1, jnp.maximum(remaining, 1))
        if eos_id is not None:
            is_eos = block == eos_id
            n_flow = jnp.where(
                jnp.any(is_eos, axis=1),
                jnp.minimum(n_flow, jnp.argmax(is_eos, axis=1) + 1),
                n_flow,
            )
        n_commit = jnp.where(active, n_flow, 0)  # (b,)
        commit_mask = offs[None, :] < n_commit[:, None]  # (b, sq)

        if canary:
            fire = ((offset + i) % canary_stride) == 0

            def shadow(op):
                # row 0 is ALWAYS an accepted position: the shadow verifies
                # a token the stream commits, never a rejected draft, from
                # the same pre-commit cache the verify forward read
                c, t, p, served = op
                el, _ = decode_step(params, ecfg, c, t, p)
                el = el[:, -1].astype(jnp.float32)
                agree_c = jnp.argmax(served, axis=-1) == jnp.argmax(el, axis=-1)
                ed = jnp.abs(served - el)
                ref = jnp.abs(el)
                rel = (jnp.max(ed, axis=-1)
                       / jnp.maximum(jnp.max(ref, axis=-1), 1e-20))
                red = jnp.mean(ed / jnp.maximum(ref, 1e-20), axis=-1)
                return agree_c, rel, red

            def no_shadow(op):
                b_ = op[3].shape[0]
                return (jnp.ones((b_,), bool), jnp.zeros((b_,), jnp.float32),
                        jnp.zeros((b_,), jnp.float32))

            agree_c, rel, red = jax.lax.cond(
                fire, shadow, no_shadow, (cache, tok, pos, lg[:, 0])
            )
            upd = fire & active
            cc = cc + upd.astype(jnp.int32)
            cd = cd + (upd & ~agree_c).astype(jnp.int32)
            cmr = jnp.maximum(cmr, jnp.where(upd, rel, 0.0))
            crs = crs + jnp.where(upd, red, 0.0)

        if with_health:
            # committed rows ARE the sequential logit set; rejected-draft
            # rows never existed in the sequential stream, so they must not
            # latch the detectors
            finite = jnp.all(jnp.isfinite(lg), axis=-1)  # (b, sq)
            bad = bad | jnp.any(commit_mask & ~finite, axis=1)
            row_mx = jnp.max(jnp.abs(lg), axis=-1)
            mx = jnp.maximum(mx, jnp.max(jnp.where(commit_mask, row_mx, 0.0), axis=1))

        # --- commit accepted rows; roll back the rest
        cache = commit_verify_cache(cfg, cache, entries, pos, n_commit)
        if use_draft:
            _, d_entries = decode_verify_step(draft_params, draft_cfg, dcache, block, pos)
            dcache = commit_verify_cache(draft_cfg, dcache, d_entries, pos, n_commit)
        hidx = pos[:, None] + offs[None, :]
        hist = hist.at[rows_b, jnp.where(commit_mask, hidx, hist.shape[1])].set(
            block, mode="drop"
        )

        # --- scheduler bookkeeping, row n_commit-1 is the last token fed
        last = jnp.clip(n_commit - 1, 0, k)
        nxt = jnp.take_along_axis(out_tok, last[:, None], axis=1)  # (b, 1)
        fed_last = jnp.take_along_axis(block, last[:, None], axis=1)[:, 0]
        remaining = remaining - n_commit
        still = active & (remaining > 0)
        if eos_id is not None:
            still = still & (fed_last != eos_id)
        new_pos = pos + n_commit
        new_tok = jnp.where(active[:, None], nxt, tok)
        acc_cnt = acc_cnt + jnp.maximum(n_commit - 1, 0)
        step_cnt = step_cnt + active.astype(jnp.int32)
        out = [cache, new_tok, new_pos, still, remaining, hist, acc_cnt, step_cnt]
        if use_draft:
            out += [dcache]
        if with_health:
            out += [bad, mx]
        if canary:
            out += [cc, cd, cmr, crs]
        return tuple(out), (block, commit_mask)

    carry0 = [cache, tok, pos, active, remaining, hist,
              jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32)]
    if use_draft:
        carry0 += [draft_cache]
    if with_health:
        carry0 += [jnp.zeros(b, bool), jnp.zeros(b, jnp.float32)]
    if canary:
        carry0 += [
            jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
            jnp.zeros(b, jnp.float32), jnp.zeros(b, jnp.float32),
        ]
    fin, (blocks_t, emits_t) = jax.lax.scan(
        step, tuple(carry0), jnp.arange(n_steps, dtype=jnp.int32)
    )
    toks = jnp.moveaxis(blocks_t, 0, 1).reshape(b, n_steps * sq)
    emitted = jnp.moveaxis(emits_t, 0, 1).reshape(b, n_steps * sq)
    cache, tok, pos, active, remaining, hist = fin[:6]
    return (toks, emitted, tok, pos, active, remaining, cache, hist,
            fin[6], fin[7]) + tuple(fin[8:])


def precompute_cross(params, cfg: ModelConfig, audio):
    """Enc-dec serving: run the encoder once and build the stacked per-layer
    cross-attention K/V (consumed by decode_step's ``cross_kv``)."""
    enc_out = _run_encoder(params, cfg, audio)

    def per_layer(p):
        return attn.precompute_cross_kv(p["xattn"], cfg, enc_out)

    return jax.vmap(per_layer, in_axes=0)(params["layers"]), enc_out


def cross_kv_specs():
    """Logical-axis specs for :func:`precompute_cross`'s stacked cross-KV
    tree (feed to ``shardings_for`` alongside the model/cache specs)."""
    return {
        "ck": ("layers", "batch", "kv_seq", "kv_heads", None),
        "cv": ("layers", "batch", "kv_seq", "kv_heads", None),
    }


def param_count(params) -> int:
    """Total parameter count of a params pytree (leaf shapes, host-side)."""
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(params))

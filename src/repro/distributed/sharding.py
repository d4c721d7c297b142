"""Logical-axis -> physical-mesh rule tables (docs/serving.md).

Production mesh axes: ("pod", "data", "model") multi-pod / ("data", "model")
single-pod.  Parameters and optimizer state are FSDP-sharded over the
data-parallel axes (ZeRO-3) *and* tensor-parallel over 'model'; activations
shard batch over DP and heads/mlp over 'model'.  Serving replicates params
across DP (no per-step all-gather latency) unless the arch is too big
(qwen3-moe: experts shard over 'data' at decode).

A physical axis is claimed at most once per tensor (`logical_to_spec`), so
e.g. ("embed", "heads", None) -> P(("pod","data"), "model", None).
"""
from __future__ import annotations


import jax
from jax.sharding import Mesh, NamedSharding

from repro.distributed.constraints import Rules, logical_to_spec
from repro.models.config import ModelConfig

__all__ = [
    "train_rules",
    "serve_rules",
    "shardings_for",
    "is_spec_leaf",
    "serve_pool_shardings",
]


def _fsdp_axes(mesh: Mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)


def train_rules(cfg: ModelConfig, mesh: Mesh, *, seq_parallel: bool = False) -> Rules:
    """``seq_parallel`` shards the residual stream's sequence axis over
    'model' between blocks (Megatron-SP): the scan-carried activations and
    norm compute shard 16x at the cost of boundary all-gathers."""
    fsdp = _fsdp_axes(mesh)
    model_size = mesh.shape["model"]
    rules: Rules = {
        # activations
        "batch": fsdp,
        "seq": "model" if seq_parallel else None,
        # params (FSDP x TP)
        "embed": fsdp,
        "heads": "model",
        "kv_heads": "model" if cfg.n_kv_heads % model_size == 0 else None,
        "heads_mix": "model",
        "mlp": "model",
        "vocab": "model",
        "layers": None,
        "expert": None,
        # caches (train unused)
        "kv_seq": None,
    }
    if cfg.moe is not None:
        if cfg.moe.n_experts % model_size == 0:
            # EP: experts over 'model'; expert-ffn dim falls back to replicated
            rules["expert"] = "model"
            rules["mlp"] = "model"  # claimed second -> replicated on expert w
        # else: experts replicated, ffn dim TP (mixtral path)
    return rules


def serve_rules(cfg: ModelConfig, mesh: Mesh, *, seq_shard_kv: bool = False,
                replicate_params: bool = False) -> Rules:
    """Serving rule table.

    Default: tensor-parallel — params sharded over 'model' (replicated
    across DP for latency), KV cache batch-over-data and kv-heads-over-model.

    ``replicate_params=True`` is the *exact* serving mode: params replicate
    everywhere and the batch (slot) axis claims EVERY mesh axis, so each
    device owns a contiguous block of slots end-to-end.  No contraction ever
    crosses a shard boundary, which makes mesh decode bit-exact against a
    single device on the host CPU backend (TP's partitioned wo/mlp
    reductions reassociate the bf16 sums — ~1 ulp logit wobble, enough to
    flip a greedy argmax; see docs/serving.md).  On a TPU the compiler's
    layouts still differ between the two programs, so tokens agree up to
    near-ties.  Use it when the model fits one chip and the pool is what
    needs scaling — the slot-parity acceptance tests run in this mode.
    """
    if replicate_params:
        rules: Rules = {
            "batch": tuple(mesh.axis_names),
            "seq": None,
            "embed": None,
            "heads": None,
            "kv_heads": None,
            "heads_mix": None,
            "mlp": None,
            "vocab": None,
            "layers": None,
            "expert": None,
            "kv_seq": None,
            "kv_dim": None,
        }
        return rules
    if "kv" in mesh.axis_names:
        return _serve_rules_kv_mesh(cfg, mesh, seq_shard_kv=seq_shard_kv)
    fsdp = _fsdp_axes(mesh)
    model_size = mesh.shape["model"]
    rules: Rules = {
        "batch": fsdp,
        "seq": None,
        # params: TP only; replicated across DP for serving latency
        "embed": None,
        "heads": "model",
        # kv_heads shard over 'model' when divisible; otherwise the KV cache
        # replicates across 'model' and decode fits HBM via the int8 cache
        # (see kv note below + dryrun's quantization policy)
        "kv_heads": "model" if cfg.n_kv_heads % model_size == 0 else None,
        "heads_mix": "model",
        "mlp": "model",
        "vocab": "model",
        "layers": None,
        "expert": None,
        # Decode cache sharding: never shard the sequence axis — GSPMD
        # rewrites the per-token cache update (DUS at a dynamic index of a
        # sharded dim) into a full-cache select, turning an O(token) write
        # into an O(cache) rewrite per layer per step (measured: 1.2 TB/step
        # on deepseek-67b decode_32k).  Sharding head_dim instead triggers
        # "involuntary full rematerialization" (a full KV all-gather per
        # layer).  kv_heads over 'model' — unevenly padded when kv_heads <
        # model — is the clean choice: updates stay local, attention is
        # collective-free, and the padding cost is bounded by 2x on the KV
        # (none when divisible).  Full study: EXPERIMENTS.md §Perf.
        "kv_seq": None,
        "kv_dim": None,
    }
    if seq_shard_kv:
        # long-context decode (batch=1): batch can't shard; KV stays model-
        # sharded via heads/dim and replicates over DP.  (A seq-sharded
        # variant was evaluated and rejected — see rationale above.)
        rules["batch"] = None
    if cfg.moe is not None:
        per_chip_gb = _param_gib(cfg) / model_size
        if per_chip_gb > 12.0 and cfg.moe.n_experts % (mesh.shape.get("data", 1)) == 0:
            rules["expert"] = "data"  # qwen3-moe: too big for pure TP
    return rules


def _serve_rules_kv_mesh(cfg: ModelConfig, mesh: Mesh, *, seq_shard_kv: bool = False) -> Rules:
    """Decode mesh reshaped to (pod?, data, kv, qg): the 'model' dimension is
    split into kv_heads x query-groups so the KV cache is *persistently*
    kv-head-sharded.  Motivation (§Perf deepseek decode): with the cache
    merely replicated over 'model', GSPMD re-shards it inside the step and
    all-gathers 49 GiB/device/step to restore the replicated out_sharding.
    Here every tensor's steady-state sharding equals its in-step sharding —
    zero cache collectives."""
    fsdp = _fsdp_axes(mesh)
    rules: Rules = {
        "batch": fsdp,
        "seq": None,
        "embed": None,
        "heads": ("kv", "qg"),
        "kv_heads": "kv",
        "heads_mix": ("kv", "qg"),
        "mlp": ("kv", "qg"),
        "vocab": ("kv", "qg"),
        "layers": None,
        "expert": None,
        "kv_seq": None,
        "kv_dim": None,
    }
    if seq_shard_kv:
        rules["batch"] = None
    if cfg.moe is not None:
        per_chip_gb = _param_gib(cfg) / (mesh.shape["kv"] * mesh.shape["qg"])
        if per_chip_gb > 12.0 and cfg.moe.n_experts % (mesh.shape.get("data", 1)) == 0:
            rules["expert"] = "data"
    return rules


def _param_gib(cfg: ModelConfig) -> float:
    """Rough bf16 parameter GiB (for serve-sharding policy)."""
    d, f, L, v = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    attn = d * (cfg.n_heads + 2 * cfg.n_kv_heads) * cfg.d_head + cfg.n_heads * cfg.d_head * d
    if cfg.moe is not None:
        ffn = 3 * d * cfg.moe.d_ff_expert * cfg.moe.n_experts
    else:
        ffn = (3 if cfg.mlp_act == "swiglu" else 2) * d * f
    total = L * (attn + ffn) + 2 * v * d
    return total * 2 / 2**30


def is_spec_leaf(s):
    return isinstance(s, tuple) and all(isinstance(e, (str, type(None))) for e in s)


def divisible_spec(spec, shape, mesh: Mesh):
    """Drop mesh axes a dim's size can't divide (replicate instead) — e.g.
    gemma3's 4 heads on a 16-wide 'model' axis, or odd vocabs."""
    parts = []
    for i, p in enumerate(spec):
        if p is None:
            parts.append(None)
            continue
        axes = (p,) if isinstance(p, str) else tuple(p)
        kept = []
        size = shape[i]
        for a in axes:
            n = mesh.shape[a]
            if size % n == 0:
                kept.append(a)
                size //= n
        parts.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    from jax.sharding import PartitionSpec as P

    return P(*parts)


def serve_pool_shardings(cfg: ModelConfig, mesh: Mesh, rules: Rules, *,
                         num_slots: int, cache_len: int,
                         quantized: bool = False):
    """NamedShardings for the continuous-batching engine's slot-pool state on
    a serving mesh.

    The KV slot pool follows the :func:`serve_rules` table — batch (the slot
    axis) sharded over the data-parallel axes, ``kv_heads`` over ``model``
    where divisible — and the per-slot scheduler vectors ride the same batch
    sharding so the decode scan needs no resharding collectives at the jit
    boundary.  Returns a dict::

        {"cache": <tree matching lm.init_cache>,
         "tok":   (num_slots, 1),
         "vec":   (num_slots,),          # pos / active / remaining
         "keys":  (num_slots, 2),        # per-slot PRNG key pool
         "replicated": scalarlike operands (prompts, slot indices)}

    Indivisible dims (e.g. ``num_slots`` not a multiple of the data axis, or
    1-row admission staging) degrade to replication per-dim, matching
    :func:`shardings_for`.
    """
    from repro.models import lm

    cache_abs, cache_specs = lm.init_cache(
        cfg, num_slots, cache_len, quantized=quantized, abstract=True
    )
    cache_sh = shardings_for(cache_specs, mesh, rules, cache_abs)

    def vec_sharding(shape, axes):
        spec = divisible_spec(logical_to_spec(axes, rules), shape, mesh)
        return NamedSharding(mesh, spec)

    from jax.sharding import PartitionSpec as P

    return {
        "cache": cache_sh,
        "tok": vec_sharding((num_slots, 1), ("batch", None)),
        "vec": vec_sharding((num_slots,), ("batch",)),
        "keys": vec_sharding((num_slots, 2), ("batch", None)),
        "replicated": NamedSharding(mesh, P()),
    }


def serve_pool_tree(pool_sh: dict) -> dict:
    """Reshape a :func:`serve_pool_shardings` bundle into a sharding tree
    matching ``lm.init_pool_state``'s single-pytree pool layout — the restore
    target for ``Engine.resume``'s elastic path: a snapshot taken on one mesh
    shape lands on another by passing this tree to ``checkpoint.restore``."""
    return {
        "cache": pool_sh["cache"],
        "tok": pool_sh["tok"],
        "pos": pool_sh["vec"],
        "active": pool_sh["vec"],
        "remaining": pool_sh["vec"],
        "keys": pool_sh["keys"],
    }


def shardings_for(spec_tree, mesh: Mesh, rules: Rules, shapes=None):
    """Map a logical-spec tree to a NamedSharding tree.  With ``shapes`` (a
    matching tree of arrays/structs), indivisible assignments degrade to
    replication per-dim."""
    if shapes is None:
        return jax.tree.map(
            lambda s: NamedSharding(mesh, logical_to_spec(s, rules)),
            spec_tree,
            is_leaf=is_spec_leaf,
        )
    return jax.tree.map(
        lambda s, arr: NamedSharding(
            mesh, divisible_spec(logical_to_spec(s, rules), arr.shape, mesh)
        ),
        spec_tree,
        shapes,
        is_leaf=is_spec_leaf,
    )

"""The dense default (bench/archs/__init__.py): the GQA/MHA block with a
SwiGLU or GELU MLP of bench/reference.py, one sliding window (or none) for
every layer, and the counts of bench/flops.py."""
from __future__ import annotations

from bench import flops
from bench.reference import Reference

__all__ = ["Reference", "RULES", "work", "check_widths", "check_program"]

# no rules beyond bench/weights.py's; the reference draws with the same
RULES = Reference.RULES

# how the model block maps onto the program's config
_CHECKS = (
    ("hidden_size", "d_model"), ("num_hidden_layers", "n_layers"),
    ("num_attention_heads", "n_heads"), ("num_key_value_heads", "n_kv_heads"),
    ("head_dim", "d_head"), ("intermediate_size", "d_ff"),
    ("vocab_size", "vocab"), ("rope_theta", "rope_theta"),
    ("tie_word_embeddings", "tie_embeddings"), ("sliding_window", "window"),
)

# a model block's layer_types -> the program's block types
BLOCKS = {"sliding_attention": "window", "full_attention": "global"}


def work(model: dict) -> flops.Dims:
    return flops.dims(model)


def check_widths(model: dict, cfg) -> None:
    """The widths, norms, MLP and attention biases of the block: a value
    that differs is an error, and so is a ``use_bias`` that the program's
    tree disagrees with (it holds ``layers/attn/bq`` exactly when the model
    has attention biases)."""
    import jax

    from repro.models import lm

    for mk, ck in _CHECKS:
        if model.get(mk) != getattr(cfg, ck):
            raise ValueError(f"model {mk}={model.get(mk)!r} but the "
                             f"program runs {ck}={getattr(cfg, ck)!r}")
    want = {"norm": cfg.norm, "qk_norm": cfg.qk_norm,
            "hidden_act": {"swiglu": "silu", "gelu": "gelu_pytorch_tanh"}[cfg.mlp_act]}
    for k, v in want.items():
        if model.get(k, False) != v:
            raise ValueError(f"model {k}={model.get(k)!r}, program {v!r}")
    abstract, _ = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    layers = abstract.get("layers", {})
    has_bias = all("bq" in layer.get("attn", {})
                   for layer in (layers if isinstance(layers, list) else [layers]))
    if bool(model.get("use_bias", False)) != has_bias:
        raise ValueError(f"model use_bias={model.get('use_bias')!r} but the "
                         f"program's tree {'has' if has_bias else 'has no'} layers/attn/bq")


def check_program(model: dict, cfg) -> None:
    """:func:`check_widths`, and the blocks: a model block's ``layer_types``
    must be the program's blocks, and since this reference and these counts
    apply one window to every layer, a sliding window needs every block to
    be ``window`` (mixed layers bring an architecture module)."""
    check_widths(model, cfg)
    if "layer_types" in model:
        listed = tuple(BLOCKS.get(t, t) for t in model["layer_types"])
        if listed != cfg.blocks:
            raise ValueError(f"model layer_types {listed} but the program runs "
                             f"blocks {cfg.blocks}")
    if model.get("sliding_window") and set(cfg.blocks) != {"window"}:
        raise ValueError(f"sliding window set, blocks {set(cfg.blocks)}: the dense "
                         f"default windows every layer")

"""Seeded weights, made by the benchmark and not by the program.

Every weight is a function of the seed, its name and its layer alone::

    leaf(seed, "layers/attn/wq", shape, layer) = uniform(key, std), as bf16

so the program's tree comes out of one jitted call on the device, and the
plain reference (bench/reference.py) draws any single layer again, bit for
bit, without reading anything the program holds.  The draw is integer
arithmetic on random bits, and the value is a bf16 number at every step,
so no rounding is left for a compiler choice (fusion, a dropped or moved
conversion, an approximated transcendental) to change between the two
calls: on the TPU, a normal draw through ``erf_inv`` and a draw of 16-bit
levels rounded to bf16 both did.  Names follow the layout the program's
parameter tree uses; the std of each follows from its name (bf16 levels
give it within a factor of sqrt(2)):

* matrices: 1/sqrt(fan-in), the fan-in being the contracted dims;
* the token embedding: 0.02 (tied: also the unembedding);
* an untied unembedding: 1/sqrt(d_model), so logits have unit scale;
* RMSNorm gains (stored as ``scale`` with weight ``1 + scale``), LayerNorm
  ``bias``, MLP and attention biases: 0.1, 0.02, 0.02 around 0; LayerNorm
  ``scale``: 0.1 around 1.

Attention biases (a model block with ``use_bias``, as StarCoder2's) are the
leaves ``layers/attn/bq`` (heads, head_dim), ``layers/attn/bk`` and
``layers/attn/bv`` (kv_heads, head_dim), added to the q, k and v
projections before RoPE, and ``layers/attn/bo`` (d_model,), added to the
output projection: the names and shapes a program's tree uses for them.

On a mesh the one jitted call takes the program's serving shardings as its
``out_shardings``: each device draws only its shard, and the values are
those of the one-device draw bit for bit (the random bits do not depend on
how the draw is partitioned).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

__all__ = ["leaf", "levels", "leaf_spec", "layer_leaves", "program_params", "key_of", "seed_words"]

DTYPE = jnp.bfloat16

# last path component -> (mean, std); a std of "first" is 1/sqrt(shape[0])
# (wq is (d, h, hd) and contracts d), "leading" 1/sqrt of the product of all
# dims but the last (attention's wo is (h, hd, d) and contracts h*hd)
_RULES = {
    "embed": (0.0, 0.02),
    "unembed": (0.0, "first"),
    "wq": (0.0, "first"),
    "wk": (0.0, "first"),
    "wv": (0.0, "first"),
    "wo": (0.0, "leading"),
    "wi_gate": (0.0, "first"),
    "wi_up": (0.0, "first"),
    "bi": (0.0, 0.02),
    "bo": (0.0, 0.02),
    "bq": (0.0, 0.02),
    "bk": (0.0, 0.02),
    "bv": (0.0, 0.02),
    "ln1": (0.0, 0.1),
    "ln2": (0.0, 0.1),
    "ln_f": (0.0, 0.1),
    "q_norm": (0.0, 0.1),
    "k_norm": (0.0, 0.1),
    "ln1_scale": (1.0, 0.1),
    "ln2_scale": (1.0, 0.1),
    "ln_f_scale": (1.0, 0.1),
    "ln1_bias": (0.0, 0.02),
    "ln2_bias": (0.0, 0.02),
    "ln_f_bias": (0.0, 0.02),
}


def leaf_spec(path: str, shape) -> tuple[float, float]:
    """(mean, std) of the leaf at ``path`` with per-layer ``shape``."""
    name = path.rsplit("/", 1)[-1]
    if name not in _RULES:
        raise KeyError(f"no weight rule for {path!r}")
    mean, rule = _RULES[name]
    if rule == "first":
        return mean, 1.0 / math.sqrt(shape[0])
    if rule == "leading":
        return mean, 1.0 / math.sqrt(math.prod(shape[:-1]))
    return mean, float(rule)


def seed_words(seed: int):
    """The seed as two uint32 words, passed to jitted code as operands so
    that one compiled program serves every seed."""
    seed = int(seed) % (1 << 64)
    return (jnp.uint32(seed & 0xFFFFFFFF), jnp.uint32(seed >> 32))


def key_of(words, path: str, layer: int):
    """The PRNG key of one leaf of one layer (layer -1: not stacked)."""
    k = jax.random.PRNGKey(words[0])
    k = jax.random.fold_in(k, words[1])
    k = jax.random.fold_in(k, zlib.crc32(path.encode()))
    return jax.random.fold_in(k, layer + 1)


def levels(mean: float, std: float) -> tuple[int, float]:
    """(m, step) of a leaf: odd levels ``k`` in ``[-(2^m - 1), 2^m - 1]``
    give the values ``mean + k * step``, every one of them a bf16 number.

    Around 0, ``m`` is 8 (bf16 holds 8 significant bits) and ``step`` the
    power of two that brings the std nearest ``std``.  Around a nonzero
    ``mean``, ``step`` is at least bf16's spacing at ``mean`` and ``m`` as
    large as that allows, so the std is as near ``std`` as bf16 lets it be."""
    step = 2.0 ** round(math.log2(std * 3.0 ** 0.5 / 256.0))
    m = 8
    if mean:
        step = max(step, 2.0 ** (math.floor(math.log2(abs(mean))) - 7))
        m = max(1, min(round(math.log2(std * 3.0 ** 0.5 / step)),
                       math.floor(math.log2(abs(mean) / 2 / step))))
    return m, step


def leaf(key, shape, mean: float, std: float):
    """Uniform around ``mean`` over the odd levels of :func:`levels`, drawn
    from the top bits of a random word.  Each value is a bf16 number from
    the integer on, so the cast to bf16 is exact and no compiler choice
    can change a bit."""
    m, step = levels(mean, std)
    bits = jax.random.bits(key, shape, jnp.uint32)
    level = ((bits >> (32 - m)).astype(jnp.int32) * 2 - (2**m - 1)).astype(jnp.float32)
    return (level * step + mean).astype(DTYPE)


def layer_leaves(words, paths_shapes: dict, layer: int) -> dict:
    """{path: array} for one layer's leaves, each drawn on its own key
    (traceable: ``words`` and ``layer`` may be jit operands)."""
    return {
        p: leaf(key_of(words, p, layer), s, *leaf_spec(p, s))
        for p, s in paths_shapes.items()
    }


def _flatten(tree, prefix=""):
    if isinstance(tree, dict):
        out = {}
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
        return out
    return {prefix[:-1]: tree}


def _unflatten(flat: dict) -> dict:
    root: dict = {}
    for path, v in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v
    return root


def program_params(seed: int, abstract_tree: dict, n_layers: int, shardings=None):
    """The program's parameter tree, drawn on the device in one jitted call.

    ``abstract_tree`` is the tree of ShapeDtypeStructs the program expects
    (leaves under ``layers/`` carry a leading stacked-layers axis); the
    values come from :func:`leaf` alone.  ``shardings``, a matching tree,
    places each leaf where the call leaves it; without it every leaf lands
    on the default device."""
    flat = _flatten(abstract_tree)
    for p, s in flat.items():
        if s.dtype != DTYPE:
            raise TypeError(f"{p}: the program wants {s.dtype}, weights are {DTYPE}")
        if p.startswith("layers/") and s.shape[0] != n_layers:
            raise ValueError(f"{p}: stacked axis {s.shape[0]} != {n_layers} layers")

    def make(words):
        out = {}
        for p, s in flat.items():
            if p.startswith("layers/"):
                shape = tuple(s.shape[1:])
                mean, std = leaf_spec(p, shape)
                out[p] = jax.vmap(
                    lambda i, p=p, sh=shape, m=mean, sd=std:
                        leaf(key_of(words, p, i), sh, m, sd)
                )(jnp.arange(n_layers))
            else:
                shape = tuple(s.shape)
                out[p] = leaf(key_of(words, p, -1), shape, *leaf_spec(p, shape))
        return out

    draw = (jax.jit(make) if shardings is None
            else jax.jit(make, out_shardings=_flatten(shardings)))
    return _unflatten(draw(seed_words(seed)))

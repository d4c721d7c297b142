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

A configuration's architecture module (bench/archs/) may bring rules of
its own, ``RULES``: a leaf path, or its end, -> (mean, std), consulted
before those below.  A std of ``"axis:<i>"`` is 1/sqrt(shape[i]), so a
stack of experts ``(e, d, f)`` draws at 1/sqrt(d) under ``"axis:1"``.

The program's ``layers`` are either one stacked tree (every leaf with a
leading axis of ``n_layers``) or a list of per-layer trees, where its
blocks differ.  A leaf's key is the same in both: (seed, the path without
the layer index, the layer), so ``layers/attn/wq`` of layer 3 has the same
bits either way, and a reference draws any one layer again.

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

__all__ = ["leaf", "levels", "leaf_spec", "layer_leaves", "path_and_layer", "program_params",
           "key_of", "seed_words"]

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


def leaf_spec(path: str, shape, rules=None) -> tuple[float, float]:
    """(mean, std) of the leaf at ``path`` with per-layer ``shape``: from
    ``rules`` by the whole path or its end (``layers/moe/wo``, ``moe/wo``,
    ``wo``), else from the default rules by the last component."""
    rules = rules or {}
    parts = path.split("/")
    ends = ("/".join(parts[i:]) for i in range(len(parts)))
    end = next((e for e in ends if e in rules), None)
    if end is not None:
        mean, rule = rules[end]
    elif parts[-1] in _RULES:
        mean, rule = _RULES[parts[-1]]
    else:
        raise KeyError(f"no weight rule for {path!r}")
    if rule == "first":
        return mean, 1.0 / math.sqrt(shape[0])
    if rule == "leading":
        return mean, 1.0 / math.sqrt(math.prod(shape[:-1]))
    if isinstance(rule, str) and rule.startswith("axis:"):
        return mean, 1.0 / math.sqrt(shape[int(rule[5:])])
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


def layer_leaves(words, paths_shapes: dict, layer: int, rules=None) -> dict:
    """{path: array} for one layer's leaves, each drawn on its own key
    (traceable: ``words`` and ``layer`` may be jit operands)."""
    return {
        p: leaf(key_of(words, p, layer), s, *leaf_spec(p, s, rules))
        for p, s in paths_shapes.items()
    }


def path_and_layer(key_path) -> tuple[str, int]:
    """A leaf's path without the layer index, and that index (-1 where the
    path holds none: a top-level or a stacked leaf)."""
    parts, layer = [], -1
    for k in key_path:
        if isinstance(k, jax.tree_util.SequenceKey):
            layer = k.idx
        else:
            parts.append(str(k.key))
    return "/".join(parts), layer


def program_params(seed: int, abstract_tree: dict, n_layers: int, shardings=None,
                   rules=None):
    """The program's parameter tree, drawn on the device in one jitted call.

    ``abstract_tree`` is the tree of ShapeDtypeStructs the program expects:
    its ``layers`` a stacked tree (a leading axis of ``n_layers``) or a
    list of ``n_layers`` per-layer trees.  The values come from
    :func:`leaf` alone, with std by ``rules`` and the defaults
    (:func:`leaf_spec`).  ``shardings``, a matching tree, places each leaf
    where the call leaves it; without it every leaf lands on the default
    device."""
    layers = abstract_tree.get("layers")
    if isinstance(layers, list) and len(layers) != n_layers:
        raise ValueError(f"layers: {len(layers)} listed != {n_layers} layers")
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract_tree)
    plan = []
    for key_path, s in flat:
        p, layer = path_and_layer(key_path)
        if s.dtype != DTYPE:
            raise TypeError(f"{p}: the program wants {s.dtype}, weights are {DTYPE}")
        stacked = p.startswith("layers/") and layer < 0
        if stacked and s.shape[0] != n_layers:
            raise ValueError(f"{p}: stacked axis {s.shape[0]} != {n_layers} layers")
        shape = tuple(s.shape[1:] if stacked else s.shape)
        plan.append((p, layer, stacked, shape, leaf_spec(p, shape, rules)))

    def make(words):
        out = []
        for p, layer, stacked, shape, (mean, std) in plan:
            if stacked:
                out.append(jax.vmap(
                    lambda i, p=p, sh=shape, m=mean, sd=std:
                        leaf(key_of(words, p, i), sh, m, sd)
                )(jnp.arange(n_layers)))
            else:
                out.append(leaf(key_of(words, p, layer), shape, mean, std))
        return out

    draw = (jax.jit(make) if shardings is None
            else jax.jit(make, out_shardings=treedef.flatten_up_to(shardings)))
    return jax.tree_util.tree_unflatten(treedef, draw(seed_words(seed)))

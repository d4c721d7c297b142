"""Parameter trees with logical-axis annotations.

Params are plain pytrees (nested dicts of arrays).  Every init function also
returns a parallel tree of *logical axis specs* (tuples of axis names or
None), which `repro.distributed.sharding` maps onto the physical mesh.  This
is the MaxText/T5X "logical axes" pattern without a framework dependency.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["DenseInit", "tree_size", "truncated_normal", "zeros", "ones"]


def truncated_normal(key, shape, dtype, scale):
    # fan-in scaled truncated normal, the LM default
    stddev = scale / np.sqrt(max(1, shape[0] if len(shape) > 1 else 1))
    x = jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * stddev
    return x.astype(dtype)


def zeros(_key, shape, dtype, _scale=None):
    return jnp.zeros(shape, dtype)


def ones(_key, shape, dtype, _scale=None):
    return jnp.ones(shape, dtype)


class DenseInit:
    """Accumulates (params, specs) pairs with a split PRNG stream.

    ``abstract=True`` produces ShapeDtypeStructs instead of arrays (used by
    the dry-run: full-size configs are never materialized).  Random inits
    draw in fp32 and cast to ``dtype``."""

    def __init__(self, key, dtype=jnp.float32, abstract=False):
        self._key = key
        self.dtype = dtype
        self.abstract = abstract
        self.params = {}
        self.specs = {}

    def _next(self):
        if self.abstract:
            return self._key
        self._key, sub = jax.random.split(self._key)
        return sub

    def child(self) -> "DenseInit":
        """A sub-initializer on the next key, with the same dtype and mode."""
        return DenseInit(self._next(), self.dtype, self.abstract)

    def add(self, name, shape, axes, init=truncated_normal, scale=1.0, dtype=None):
        assert len(shape) == len(axes), (name, shape, axes)
        if self.abstract:
            self.params[name] = jax.ShapeDtypeStruct(shape, dtype or self.dtype)
        else:
            self.params[name] = init(self._next(), shape, dtype or self.dtype, scale)
        self.specs[name] = tuple(axes)

    def sub(self, name, params, specs):
        self.params[name] = params
        self.specs[name] = specs

    def build(self):
        return self.params, self.specs


def tree_size(tree) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))

"""Logical-axis sharding context.

Layers annotate activations with *logical* axes (``constrain(x, ("batch",
"seq", "embed"))``).  Inside a ``with axis_rules(mesh, rules):`` scope these
become ``with_sharding_constraint`` on the physical mesh; outside any scope
(unit tests, single-device smoke runs) they are no-ops, keeping the model
code mesh-agnostic.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "axis_rules",
    "maybe_axis_rules",
    "constrain",
    "logical_to_spec",
    "current_rules",
    "shard_count",
]

_state = threading.local()

Rules = Dict[str, Union[None, str, Tuple[str, ...]]]


def current_rules() -> Optional[Tuple[Mesh, Rules]]:
    return getattr(_state, "ctx", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Rules):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, rules)
    try:
        yield
    finally:
        _state.ctx = prev


def maybe_axis_rules(mesh: Optional[Mesh], rules: Optional[Rules]):
    """``axis_rules(mesh, rules)`` when a mesh is given, else a no-op context.

    The mesh-optional entry points (``lm.prefill(..., mesh=...)``, the
    Engine's sharded mode) wrap their traced bodies in this so the same model
    code serves single-device and mesh-sharded callers: ``constrain`` calls
    resolve against the ambient rules inside the scope and vanish outside it.
    """
    if mesh is None:
        return contextlib.nullcontext()
    if rules is None:
        raise ValueError("maybe_axis_rules: a mesh needs a rule table (rules=None)")
    return axis_rules(mesh, rules)


def logical_to_spec(axes: Sequence[Optional[str]], rules: Rules) -> P:
    """Map logical axis names to a PartitionSpec via the rule table.

    A physical mesh axis may be claimed only once per spec; later logical
    axes that map to an already-used physical axis fall back to replication
    (standard logical-axis-rules semantics).
    """
    used = set()
    parts = []
    for ax in axes:
        phys = rules.get(ax) if ax is not None else None
        if phys is None:
            parts.append(None)
            continue
        phys_t = (phys,) if isinstance(phys, str) else tuple(phys)
        free = tuple(a for a in phys_t if a not in used)
        if not free:
            parts.append(None)
            continue
        used.update(free)
        parts.append(free if len(free) > 1 else free[0])
    return P(*parts)


def constrain(x: jax.Array, axes: Sequence[Optional[str]]):
    ctx = current_rules()
    if ctx is None:
        return x
    mesh, rules = ctx
    from repro.distributed.sharding import divisible_spec  # avoid cycle at import

    spec = divisible_spec(logical_to_spec(axes, rules), x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_count(axes: Sequence[Optional[str]], shape: Sequence[int]) -> int:
    """How many devices :func:`constrain` would split a tensor of ``shape``
    over along the logical ``axes``; 1 outside any rules scope."""
    ctx = current_rules()
    if ctx is None:
        return 1
    mesh, rules = ctx
    from repro.distributed.sharding import divisible_spec  # avoid cycle at import

    n = 1
    for p in divisible_spec(logical_to_spec(axes, rules), shape, mesh):
        for a in () if p is None else (p,) if isinstance(p, str) else p:
            n *= mesh.shape[a]
    return n

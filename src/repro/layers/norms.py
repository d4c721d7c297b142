"""Normalization layers with a pluggable sqrt unit — the paper's technique
integrated at its highest-traffic site (every layer of every architecture).

``x * rsqrt(ms + eps)`` is computed through the configured SqrtUnit: "e2afs"
routes through the E2AFS-R integer datapath (multiplier-free rsqrt), "exact"
through ``jax.lax.rsqrt``.  The reduction is fp32 regardless of activation
dtype; the rsqrt itself runs in the reduction dtype's bit format.

Every norm runs under ``jax.named_scope("norm")``: metadata only, so the
compiled code is unchanged and a device trace can tell the norm datapath's
time apart (docs/serving.md, "Taking a profile").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import get_unit, resolve_ladder
from repro.layers.param import DenseInit, ones, zeros

__all__ = [
    "rmsnorm_init",
    "rmsnorm",
    "rmsnorm_select",
    "layernorm_init",
    "layernorm",
    "layernorm_select",
]


def _select_inv(ms, levels, ladder, faults, ndim):
    """rsqrt of ``ms`` through every ladder rung, per-row selected by ``levels``.

    ``ms`` has shape ``x.shape[:-1] + (1,)``; ``levels`` is ``(b,)`` over the
    leading (slot) axis.  Rows at level 0 select exactly the rung-0 rsqrt
    output — bit-identical to the single-unit path, which is the accuracy-SLO
    parity anchor (docs/robustness.md §Accuracy SLO).  Faults ride rung 0 only.
    """
    units = resolve_ladder(ladder, faults=faults)
    invs = [u.rsqrt(ms) for u in units]
    lv = levels.reshape((levels.shape[0],) + (1,) * (ndim - 1))
    inv = invs[-1]
    for j in range(len(units) - 2, -1, -1):
        inv = jnp.where(lv == j, invs[j], inv)
    return inv


def rmsnorm_init(ini: DenseInit, name: str, d: int):
    # zero-init with (1 + scale) application (gemma convention)
    ini.add(name, (d,), ("embed",), init=zeros)


def rmsnorm(
    scale, x, *, sqrt_unit: str = "exact", eps: float = 1e-6, fused: bool = False, faults=None
):
    """``fused=True`` routes the whole norm through the Pallas RMSNorm kernel
    (one HBM read/write, rsqrt in-register) via the kernel dispatch layer;
    only the "e2afs" unit has a fused datapath.  ``faults`` threads a seeded
    sqrt-site :class:`~repro.core.faults.FaultConfig` into the unit (the
    fused kernel has no in-register injection hook, so the two are exclusive).
    """
    if fused:
        if sqrt_unit != "e2afs":
            raise ValueError(f"fused rmsnorm requires sqrt_unit='e2afs', got {sqrt_unit!r}")
        if faults is not None and faults.targets_sqrt and faults.rate > 0.0:
            raise ValueError("fused rmsnorm has no fault-injection hook; use fused=False")
        from repro.kernels.rmsnorm.ops import rmsnorm as rmsnorm_kernel

        with jax.named_scope("norm"):
            return rmsnorm_kernel(x, scale.astype(jnp.float32), eps=eps)
    unit = get_unit(sqrt_unit, faults=faults)
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        inv = unit.rsqrt(ms + eps)
        return (xf * inv).astype(dt) * (1.0 + scale.astype(dt))


def rmsnorm_select(scale, x, levels, *, ladder, eps: float = 1e-6, faults=None):
    """Per-row ladder variant of :func:`rmsnorm` for accuracy-SLO decode:
    row ``i`` routes its rsqrt through ``ladder[levels[i]]``.  The mean-square
    reduction is computed once; only the (tiny) rsqrt runs per rung."""
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
        inv = _select_inv(ms + eps, levels, ladder, faults, x.ndim)
        return (xf * inv).astype(dt) * (1.0 + scale.astype(dt))


def layernorm_init(ini: DenseInit, name: str, d: int):
    ini.add(f"{name}_scale", (d,), ("embed",), init=ones)
    ini.add(f"{name}_bias", (d,), ("embed",), init=zeros)


def layernorm(scale, bias, x, *, sqrt_unit: str = "exact", eps: float = 1e-5, faults=None):
    unit = get_unit(sqrt_unit, faults=faults)
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        inv = unit.rsqrt(var + eps)
        return ((xf - mu) * inv).astype(dt) * scale.astype(dt) + bias.astype(dt)


def layernorm_select(scale, bias, x, levels, *, ladder, eps: float = 1e-5, faults=None):
    """Per-row ladder variant of :func:`layernorm` (see :func:`rmsnorm_select`)."""
    with jax.named_scope("norm"):
        dt = x.dtype
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        inv = _select_inv(var + eps, levels, ladder, faults, x.ndim)
        return ((xf - mu) * inv).astype(dt) * scale.astype(dt) + bias.astype(dt)

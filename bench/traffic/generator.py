"""The one traffic generator: reads a mix file and draws an open-loop trace.

A mix file (``bench/traffic/<mix>.json``) holds only parameters::

    {"arrival": {"process": "poisson" | "gamma", "rate_rps": 3.1, "cv": 3.0},
     "prompt":  {"dist": "lognormal", "median": 256, "sigma": 0.9,
                 "buckets": [128, 256, 512, 1024]}
              | {"dist": "choice", "values": [1024, 1536], "weights": [1, 1]},
     "output":  {"dist": "lognormal", "median": 128, "sigma": 0.9,
                 "min": 16, "max": 768},
     "strata": 10, "drain_s": 20.0, "check_tokens": 384}

Every seed gets the same multiset of prompt lengths, output lengths and
interarrival gaps: each is the distribution's quantile at ``(i + 0.5) / n``
for ``i < n``, where ``n`` is the rate times the window, rounded to a whole
request.  The seed only orders them.  The gaps take a free seeded order,
so short gaps fall together as they do in a Poisson (or gamma) process and
bursts come where the seed puts them; they are scaled to sum to the window
exactly, and the first request is due at 0.  What this keeps from a
Poisson process is the gaps' distribution; what it drops is the spread of
the count, which is ``n`` in every run.  The lengths are stratified: their
sorted values are cut into ``strata`` quantile bands, and each band's
members are spread evenly through the trace in a seeded order, so every
stretch of ``strata`` arrivals holds about one from each band.  Two seeds
then offer the same work at the same rate, with the same mix in every few
seconds of the window, and differ in which request comes when.

Lengths are drawn independently of each other and of the gaps.  A prompt
length is rounded up to the smallest bucket that holds it (the largest
bucket caps it); its tokens are uniform over the vocabulary.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np
from scipy import special

__all__ = ["TraceRequest", "make_trace", "quantiles", "stratified_order"]


@dataclasses.dataclass(frozen=True)
class TraceRequest:
    uid: int
    arrival_s: float
    prompt: np.ndarray
    max_new_tokens: int


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length or gap distribution, sorted."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "lognormal":
        z = math.sqrt(2.0) * special.erfinv(2.0 * u - 1.0)
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "choice":
        w = np.asarray(spec["weights"], np.float64)
        edges = np.cumsum(w) / w.sum()
        idx = np.searchsorted(edges, u, side="right")
        x = np.asarray(spec["values"], np.float64)[np.minimum(idx, len(w) - 1)]
    elif dist == "exponential":
        x = -np.log1p(-u)
    elif dist == "gamma":
        k = 1.0 / spec["cv"] ** 2
        x = special.gammaincinv(k, u) / k
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.sort(x)


def stratified_order(n: int, strata: int, rng: np.random.Generator) -> np.ndarray:
    """A permutation of ``range(n)`` (indices into sorted values) that
    spreads each of the ``strata`` quantile bands evenly through it: the
    ``j``-th of a band's ``m`` members, in a seeded order, sits at
    ``(j + u) / m`` of the way through, ``u`` drawn once per band."""
    band = np.arange(n) * strata // n
    key = np.empty(n)
    for b in range(strata):
        idx = np.flatnonzero(band == b)
        key[rng.permutation(idx)] = (np.arange(len(idx)) + rng.random()) / max(len(idx), 1)
    return np.lexsort((rng.random(n), key))


def _lengths(spec: dict, n: int, strata: int, rng) -> np.ndarray:
    vals = quantiles(spec, n)
    if "buckets" in spec:
        buckets = np.asarray(sorted(spec["buckets"]))
        idx = np.minimum(np.searchsorted(buckets, vals, side="left"), len(buckets) - 1)
        vals = buckets[idx]
    lo, hi = spec.get("min", 1), spec.get("max", None)
    vals = np.clip(np.ceil(vals), lo, hi if hi is not None else np.inf)
    return vals.astype(np.int64)[stratified_order(n, strata, rng)]


def request_count(mix: dict, seconds: float) -> int:
    """Requests due in a window: the rate times its length, to the nearest."""
    return max(1, round(mix["arrival"]["rate_rps"] * seconds))


def make_trace(mix: dict, seconds: float, seed: int, vocab: int) -> list[TraceRequest]:
    """The open-loop trace of one run: requests due over ``seconds``."""
    strata = int(mix["strata"])
    n = request_count(mix, seconds)
    arr = mix["arrival"]
    if arr["process"] == "poisson":
        gap_spec = {"dist": "exponential"}
    elif arr["process"] == "gamma":
        gap_spec = {"dist": "gamma", "cv": arr["cv"]}
    else:
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    gaps = quantiles(gap_spec, n)[_rng(seed, 0).permutation(n)]
    gaps = gaps * (seconds / gaps.sum())
    arrivals = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    prompts = _lengths(mix["prompt"], n, strata, _rng(seed, 1))
    outputs = _lengths(mix["output"], n, strata, _rng(seed, 2))
    tok_rng = _rng(seed, 3)
    return [
        TraceRequest(
            uid=i,
            arrival_s=float(arrivals[i]),
            prompt=tok_rng.integers(0, vocab, int(prompts[i]), dtype=np.int32),
            max_new_tokens=int(outputs[i]),
        )
        for i in range(n)
    ]

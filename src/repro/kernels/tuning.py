"""Block-size autotuning for the kernel dispatch layer.

A tiling choice is resolved in three steps (DESIGN.md "Autotune cache"):

1. cache hit — the JSON cache maps a problem key
   ``<kernel>/<backend>/<dtype>/n2^<bucket>`` to a previously-picked block;
2. timed sweep — when autotuning is enabled (``REPRO_AUTOTUNE=1`` or an
   explicit ``tune=True``), the roofline-admissible candidates from the
   kernel's TilingSpec are timed on the real inputs and the winner is
   persisted to the cache;
3. roofline prior — otherwise the analytical tile-time model picks the
   block: per candidate, predicted time = grid steps x (chip step overhead
   + tile work), with work the max of the compute and HBM roofline terms
   (chip constants from :mod:`repro.core.hw_model`, per-element op weight
   from the E2AFS unit-gate depth).  Candidates whose predicted occupancy
   (busy fraction, work / total) falls below :data:`OCC_FLOOR` are rejected
   — this is what retires the degenerate block-8 rmsnorm pick, whose 64
   grid steps were pure launch overhead.  The same plan narrows the sweep:
   step 2 only times the admissible candidates, not the blind grid.

The cache lives at ``.cache/kernel_tune.json`` in the checkout unless
``REPRO_TUNE_CACHE`` points elsewhere.  Sweeps never run under tracing
(arguments are abstract, so there is nothing to time); the prior, being
pure shape arithmetic, still resolves there.
"""
from __future__ import annotations

import json
import math
import os
import time
from pathlib import Path
from typing import Callable, Optional, Sequence

import jax

from repro.local_cache import CACHE_DIR

__all__ = [
    "OCC_FLOOR",
    "autotune_enabled",
    "cache_path",
    "choose_block",
    "predict_block_time",
    "problem_key",
    "roofline_plan",
    "sweep",
    "tile_geometry",
]

ENV_CACHE = "REPRO_TUNE_CACHE"
ENV_AUTOTUNE = "REPRO_AUTOTUNE"
DEFAULT_CACHE = CACHE_DIR / "kernel_tune.json"
CACHE_VERSION = 1

# minimum predicted busy fraction (tile work / total incl. launch overhead)
# for a candidate to stay in the tuning plan
OCC_FLOOR = 0.5
# when every candidate is overhead-bound (tiny problems), keep this many
# best-predicted candidates so a sweep still has something to time
_NARROW_TOP = 3

# in-memory mirror of the on-disk cache, keyed by resolved path so tests can
# repoint REPRO_TUNE_CACHE without stale state leaking across cache files
_mem: dict = {}


def cache_path() -> Path:
    return Path(os.environ.get(ENV_CACHE, DEFAULT_CACHE)).expanduser()


def autotune_enabled() -> bool:
    return os.environ.get(ENV_AUTOTUNE, "0").lower() not in ("0", "", "false", "off")


def _entries(path: Path) -> dict:
    key = str(path)
    if key not in _mem:
        try:
            _mem[key] = json.loads(path.read_text()).get("entries", {})
        except (FileNotFoundError, json.JSONDecodeError, OSError):
            _mem[key] = {}
    return _mem[key]


def _persist(path: Path, entries: dict) -> None:
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(
            json.dumps({"version": CACHE_VERSION, "entries": entries}, indent=2, sort_keys=True)
        )
    except OSError:
        pass  # read-only FS: keep the in-memory pick, skip persistence


def problem_key(name: str, args: Sequence, interpret: bool) -> str:
    """Cache key: kernel, backend, dtype, and a power-of-two size bucket."""
    arr = next(a for a in args if hasattr(a, "dtype") and hasattr(a, "size"))
    bucket = max(int(arr.size) - 1, 0).bit_length()  # ceil(log2(n))
    backend = "interpret" if interpret else "compiled"
    return f"{name}/{backend}/{arr.dtype}/n2^{bucket}"


def lookup(key: str, candidates: Sequence[tuple]) -> Optional[tuple]:
    entry = _entries(cache_path()).get(key)
    if entry is None:
        return None
    block = tuple(entry.get("block", ()))
    return block if block in tuple(candidates) else None


def record(key: str, block: tuple, timings_us: dict) -> None:
    path = cache_path()
    entries = _entries(path)
    entries[key] = {"block": list(block), "timings_us": timings_us}
    _persist(path, entries)


def sweep(run: Callable[[tuple], object], candidates: Sequence[tuple], reps: int = 3):
    """Time ``run(block)`` for each candidate; returns (best_block, timings_us)."""
    results = []
    timings = {}
    for cand in candidates:
        cand = tuple(cand)
        try:
            jax.block_until_ready(run(cand))  # warmup / compile
            t0 = time.perf_counter()
            out = None
            for _ in range(reps):
                out = run(cand)
            jax.block_until_ready(out)
        except Exception:
            continue  # candidate infeasible for this problem shape
        us = (time.perf_counter() - t0) / reps * 1e6
        results.append((cand, us))
        timings[str(list(cand))] = us
    if not results:
        return None, timings
    return min(results, key=lambda r: r[1])[0], timings


# ---------------------------------------------------------------------------
# roofline tile priors
# ---------------------------------------------------------------------------


def _hw_model():
    # function-level import: repro.core's package init imports the units
    # module, which imports dispatch -> tuning; by the time a block is
    # actually chosen the cycle has long resolved
    from repro.core import hw_model

    return hw_model


def tile_geometry(args: Sequence) -> dict:
    """Default problem geometry for the tile-time model: the first array
    argument is blocked along its leading axis, each of whose rows carries
    ``row_elems`` elements.  Kernels with a different blocking contract
    register their own geometry on the TilingSpec (e.g. decode attention,
    whose per-row work is the whole KV stream).  ``ops_per_elem`` defaults
    to the E2AFS critical-path depth — the one datapath whose gate-level
    cost this repo knows exactly — so the compute roofline term is tied to
    the same unit-gate model as the Table 3 proxies."""
    arr = next(a for a in args if getattr(a, "ndim", 0) >= 1 and hasattr(a, "size"))
    rows = int(arr.shape[0])
    return {
        "rows": rows,
        "row_elems": max(int(arr.size) // max(rows, 1), 1),
        "ops_per_elem": _hw_model().cost("e2afs")["depth"],
        "streams": 2,  # read x + write out
    }


# a VMEM tile is laid out in whole rows of this many 32-bit lanes
_LANES = 128


def predict_block_time(block: Sequence[int], geom: dict, chip):
    """Predicted (seconds, occupancy, vmem_feasible) for one block candidate.

    The model is the per-kernel analogue of the repo's roofline tables:
    tile work = max(compute term, HBM term) over the *padded* element count
    (a clamped block never pads past one tile), plus a fixed per-grid-step
    launch overhead.  Occupancy is the busy fraction work / total.

    VMEM feasibility counts what Mosaic allocates per grid step: the tile
    padded to whole 128-lane rows at 4 bytes an element, times
    ``vmem_tiles`` — by default two pipeline buffers for each of the
    ``streams``; kernels whose body holds tile-sized temporaries register a
    larger count.  The budget is strict: Mosaic adds a few KiB of its own."""
    rows, width = geom["rows"], geom["row_elems"]
    streams = geom.get("streams", 2)
    b0 = max(1, min(int(block[0]), rows))  # wrappers clamp oversize blocks
    steps = math.ceil(rows / b0)
    elems = steps * b0 * width  # padded: grid work includes the pad waste
    compute_s = elems * geom["ops_per_elem"] / chip.peak_flops
    memory_s = elems * 4.0 * streams / chip.hbm_bw
    work = max(compute_s, memory_s)
    total = work + steps * chip.step_overhead_s
    occupancy = work / total if total > 0.0 else 0.0
    tile_bytes = b0 * -(-width // _LANES) * _LANES * 4.0
    feasible = tile_bytes * geom.get("vmem_tiles", 2 * streams) < chip.vmem_bytes
    # a geometry may cap the tile below what VMEM admits — e.g. kmeans,
    # whose whole point is a working set that stays a fraction of the input
    feasible = feasible and int(block[0]) <= geom.get("max_block_rows", int(block[0]))
    return total, occupancy, feasible


def roofline_plan(
    candidates: Sequence[tuple],
    default: tuple,
    args: Sequence,
    *,
    interpret: bool,
    geometry: Optional[Callable[[Sequence], dict]] = None,
):
    """(prior_block, admissible_candidates) from the chip roofline model.

    The prior is the fastest-predicted candidate whose occupancy clears
    :data:`OCC_FLOOR`; when every candidate is overhead-bound (tiny
    problems) the floor is waived and ties break toward the smallest block,
    which keeps tiny-input picks at the TilingSpec default.  Any modeling
    failure (no array argument, exotic shapes) falls back to the blind
    grid.  A compiled backend on a device with no chip constants is an
    error, not a modeling failure."""
    cands = tuple(tuple(c) for c in candidates)
    chip = _hw_model().chip_for_backend(interpret)
    try:
        geom = (geometry or tile_geometry)(args)
        scored = []
        for cand in cands:
            t, occ, ok = predict_block_time(cand, geom, chip)
            if ok:
                scored.append((t, math.prod(cand), cand, occ))
        if not scored:
            return tuple(default), cands
        scored.sort()
        admissible = [c for _, _, c, occ in scored if occ >= OCC_FLOOR]
        if admissible:
            prior = admissible[0]
        else:
            admissible = [c for _, _, c, _ in scored[:_NARROW_TOP]]
            prior = admissible[0]
        return prior, tuple(admissible)
    except Exception:
        return tuple(default), cands


def _is_tracer(a) -> bool:
    try:
        return isinstance(a, jax.core.Tracer)
    except AttributeError:
        pass
    # jax versions without jax.core.Tracer: fail closed — treat any array-like
    # without concrete addressable shards as traced, so a sweep never times
    # (and persists a bogus winner from) abstract values inside a jit trace
    if hasattr(a, "dtype") and hasattr(a, "shape"):
        return not hasattr(a, "addressable_shards")
    return False


def choose_block(
    name: str,
    candidates: Sequence[tuple],
    default: tuple,
    run: Callable[[tuple], object],
    args: Sequence,
    *,
    interpret: bool,
    tune: Optional[bool] = None,
    geometry: Optional[Callable[[Sequence], dict]] = None,
) -> tuple:
    """Resolve a block size: cache hit > (optional) timed sweep over the
    roofline-admissible candidates > roofline prior."""
    prior, admissible = roofline_plan(
        candidates, default, args, interpret=interpret, geometry=geometry
    )
    if any(_is_tracer(a) for a in args):
        return prior  # shapes are concrete under tracing; timings are not
    key = problem_key(name, args, interpret)
    hit = lookup(key, candidates)
    if hit is not None:
        return hit
    if tune is None:
        tune = autotune_enabled()
    if not tune:
        return prior
    best, timings = sweep(run, admissible)
    if best is None:
        return prior
    record(key, best, timings)
    return best

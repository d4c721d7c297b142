#!/usr/bin/env python3
"""Bring-up smoke on the chip: the system's main path, once, at published widths.

    python chip_smoke.py             # one TPU: datapath, engine, paper apps
    python chip_smoke.py --chips 4   # four TPUs: 1-device engine vs the (2, 2) mesh

One process drives everything (a chip belongs to one process at a time).
The phases, each of which must pass:

* **device** — JAX must run on a TPU, and ``REPRO_KERNEL_BACKEND`` must not
  force the Pallas interpreter or the pure-jnp reference.
* **datapath** — E2AFS sqrt and E2AFS-R rsqrt over all 2^16 FP16 bit
  patterns and a seeded 2^20-value FP32 sample (plus the compiled Pallas
  kernel on the FP32 sample), on the TPU and on the host CPU backend of the
  same process.  Mismatches are counted by input class; any mismatch on a
  normal input fails.
* **engine** — qwen3-4b at its published widths with the E2AFS datapath in
  every norm, bf16 params from seed 0, served by ``Engine`` (8 slots,
  2048-token cache): 16 seeded requests with 128- or 512-token prompts and
  64 new tokens each.  Every completion must be ``ok``, and two requests
  are checked against ``solo_generate``: token-exact, or — where a slot and
  its solo run part — the two tokens must be a near-tie of the logits at
  that position (within ``TIE_TOL`` of the largest logit), which is what a
  different reduction order can flip and a bug cannot hide behind.
* **apps** — the paper's Sobel and K-means apps through their compiled
  kernels on a 512x512 test image, against their reference paths at the
  PSNR bound the tests use, with a ``tpu_custom_call`` in each kernel's HLO.

With ``--chips 4`` only the mesh phase runs: the 1-device engine serves the
16 requests, then the same engine on a (data=2, model=2) mesh in exact mode
(params replicated) must emit the same tokens or part from them only at a
near-tie, and tp mode (params sharded over 'model') must complete every
request; the KV pool and the tp params must span all 4 devices.  Token
agreement of both modes with the 1-device engine is reported.

Times printed are one bring-up run's wall clock, not a benchmark.  The last
line of output is ``{"ok": true, "device": {...}}`` only when every phase
passed; any failure exits non-zero.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-4b"
SEED = 0
NUM_SLOTS = 8
CACHE_LEN = 2048
PROMPT_LENS = (128, 512)
N_REQUESTS = 16
MAX_NEW_TOKENS = 64
N_SOLO = 2
IMAGE_SIZE = 512
FP32_SAMPLE = 1 << 20
# A run that parts from its reference (a slot from its solo run, a mesh from
# one device) passes only if the two tokens' logits, teacher-forced on the
# shared prefix, lie within this fraction of the largest |logit| at that
# position: bf16 activations round at 2^-8.
TIE_TOL = 2.0**-6
# A paper app's kernel must score within this many dB of its reference path
# on the paper's metric, PSNR against the exact-sqrt result (the bound of
# tests/kernels/test_kmeans_kernel.py).  Sobel's kernel and reference sum the
# stencil in different orders, and the E2AFS sqrt steps at its segment
# boundaries, so a pixel can land across one: at 512x512 one pixel of
# 'house' does (5.82 vs 6.00), on the host CPU as on the chip.  The
# elementwise bound of tests/apps/test_apps.py is reported beside it.
PSNR_DB = 0.1
SOBEL_RTOL, SOBEL_ATOL = 1e-5, 1e-3


class SmokeFailure(Exception):
    """A phase ran and its result was wrong."""


def log(msg: str) -> None:
    print(msg, flush=True)


def check_device(devices, env) -> dict:
    """The device record of the last line; fails unless JAX runs on a TPU
    with the compiled kernel backend."""
    backend = env.get("REPRO_KERNEL_BACKEND", "auto")
    if backend in ("interpret", "reference"):
        raise SmokeFailure(
            f"REPRO_KERNEL_BACKEND={backend} would keep the kernels off the chip"
        )
    dev = devices[0]
    if dev.platform != "tpu":
        raise SmokeFailure(f"JAX found no TPU (platform {dev.platform!r})")
    return {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}


# ---------------------------------------------------------------------------
# datapath
# ---------------------------------------------------------------------------


def input_classes(x: np.ndarray) -> dict:
    """Masks of normal / subnormal / special (zero, inf, NaN) inputs."""
    finfo = np.finfo(x.dtype)
    bits = x.view(np.uint16 if x.dtype == np.float16 else np.uint32).astype(np.int64)
    man_bits = finfo.nmant
    exp_mask = (1 << finfo.nexp) - 1
    exp = (bits >> man_bits) & exp_mask
    man = bits & ((1 << man_bits) - 1)
    sub = (exp == 0) & (man != 0)
    special = ((exp == 0) & (man == 0)) | (exp == exp_mask)
    return {"normal": ~(sub | special), "subnormal": sub, "special": special}


def mismatches(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Bitwise disagreement, with any NaN equal to any NaN."""
    width = np.uint16 if a.dtype == np.float16 else np.uint32
    same = (a.view(width) == b.view(width)) | (np.isnan(a) & np.isnan(b))
    return ~same


def datapath_phase(accel, host, *, fp32_sample: int = FP32_SAMPLE) -> dict:
    """E2AFS sqrt / rsqrt on ``accel`` against the same datapath on
    ``host``; returns {case: {class: mismatches}}."""
    import jax

    from repro.core.e2afs import e2afs_rsqrt, e2afs_sqrt
    from repro.kernels.e2afs_sqrt import ops as sqrt_kernel

    fp16 = np.arange(1 << 16, dtype=np.uint16).view(np.float16)
    rng = np.random.default_rng(SEED)
    fp32 = rng.integers(0, 1 << 32, fp32_sample, dtype=np.uint32).view(np.float32)
    cases = (
        ("fp16 sqrt", fp16, e2afs_sqrt, e2afs_sqrt),
        ("fp16 rsqrt", fp16, e2afs_rsqrt, e2afs_rsqrt),
        ("fp32 sqrt", fp32, e2afs_sqrt, e2afs_sqrt),
        ("fp32 rsqrt", fp32, e2afs_rsqrt, e2afs_rsqrt),
        ("fp32 sqrt, Pallas kernel", fp32, sqrt_kernel.sqrt, e2afs_sqrt),
        ("fp32 rsqrt, Pallas kernel", fp32, sqrt_kernel.rsqrt, e2afs_rsqrt),
    )
    report = {}
    for name, x, on_accel, on_host in cases:
        got = np.asarray(jax.jit(on_accel)(jax.device_put(x, accel)))
        want = np.asarray(jax.jit(on_host)(jax.device_put(x, host)))
        bad = mismatches(got, want)
        counts = {k: int((bad & m).sum()) for k, m in input_classes(x).items()}
        report[name] = counts
        log(f"[datapath] {name}: {x.size} inputs, mismatches " + ", ".join(
            f"{k}={v}" for k, v in counts.items()))
    wrong = [n for n, c in report.items() if c["normal"]]
    if wrong:
        raise SmokeFailure(f"datapath disagrees with the host on normal inputs: {wrong}")
    return report


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


def make_requests(vocab: int, *, n: int = N_REQUESTS, prompt_lens=PROMPT_LENS,
                  max_new_tokens: int = MAX_NEW_TOKENS):
    from repro.launch.engine import Request

    rng = np.random.default_rng(SEED)
    return [
        Request(
            uid=i,
            prompt=rng.integers(0, vocab, int(rng.choice(prompt_lens)), dtype=np.int32),
            max_new_tokens=max_new_tokens,
        )
        for i in range(n)
    ]


def init_params(cfg):
    import jax

    from repro.models import lm

    t0 = time.perf_counter()
    params, _ = lm.init(cfg, jax.random.key(SEED))
    jax.block_until_ready(params)
    n = lm.param_count(params)
    dtypes = sorted({str(p.dtype) for p in jax.tree.leaves(params)})
    log(f"[engine] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}, {n / 1e9:.3f} B params {dtypes}, sqrt_unit "
        f"{cfg.sqrt_unit}; init {time.perf_counter() - t0:.1f} s")
    return params


def serve(params, cfg, reqs, *, label: str, num_slots: int = NUM_SLOTS,
          cache_len: int = CACHE_LEN, mesh=None, rules=None):
    """Serve ``reqs`` through one Engine; returns (completions, engine).
    Fails unless every completion is ``ok``."""
    from repro.launch.engine import Engine

    t0 = time.perf_counter()
    eng = Engine(params, cfg, num_slots=num_slots, cache_len=cache_len,
                 mesh=mesh, rules=rules)
    eng.warmup(prompt_lens={len(r.prompt) for r in reqs})
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    done = eng.run(reqs)
    wall = time.perf_counter() - t0
    n_tok = sum(len(c.tokens) for c in done.values())
    statuses = sorted({c.status for c in done.values()})
    log(f"[{label}] {len(done)}/{len(reqs)} requests, statuses {statuses}, "
        f"{n_tok} tokens")
    log(f"[{label}] one bring-up run's wall clock, not a benchmark: engine "
        f"build + warmup {compile_s:.1f} s, serve {wall:.2f} s, "
        f"{n_tok / wall:.1f} tok/s{_peak_memory()}")
    bad = {u: c.status for u, c in done.items() if c.status != "ok"}
    if set(done) != {r.uid for r in reqs} or bad:
        raise SmokeFailure(f"[{label}] requests not served ok: {bad or sorted(done)}")
    return done, eng


def _peak_memory() -> str:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return "" if peak is None else f", device 0 peak_bytes_in_use {peak / 1e9:.2f} GB"


def near_tie(params, cfg, req, want, got, *, label: str,
             cache_len: int = CACHE_LEN) -> dict:
    """Compare two greedy runs of one request.  Where ``got`` parts from
    ``want``, the logits at that position (the shared prefix teacher-forced
    through ``lm.prefill``) must hold the two tokens within ``TIE_TOL`` of
    the largest |logit|: a near-tie that a different reduction order can
    flip, not an error."""
    import jax.numpy as jnp

    from repro.models import lm

    if np.array_equal(want, got):
        log(f"[{label}] uid {req.uid}: token-exact ({len(want)} tokens)")
        return {"uid": req.uid, "exact": True}
    j = int(np.argmax(want != got))
    ctx = np.concatenate([np.asarray(req.prompt, np.int32), want[:j]])
    cache, _ = lm.init_cache(cfg, 1, cache_len)
    logits, _ = lm.prefill(params, cfg, cache, jnp.asarray(ctx)[None],
                           last_logit_only=True)
    lg = np.asarray(logits[0, -1], np.float32)
    gap = float(abs(lg[want[j]] - lg[got[j]]))
    bound = TIE_TOL * float(np.abs(lg).max())
    log(f"[{label}] uid {req.uid}: parts at generated position {j}: tokens "
        f"{int(got[j])} vs {int(want[j])}, logit gap {gap:.4g} vs near-tie "
        f"bound {bound:.4g} (TIE_TOL {TIE_TOL:g} x max|logit|)")
    if gap > bound:
        raise SmokeFailure(f"[{label}] uid {req.uid}: the runs disagree beyond a near-tie")
    return {"uid": req.uid, "exact": False, "position": j, "gap": gap, "bound": bound}


def slot_vs_solo(params, cfg, req, tokens, *, cache_len: int = CACHE_LEN) -> dict:
    """Compare a slot's tokens with ``solo_generate`` of the same request."""
    from repro.launch.engine import solo_generate

    solo = solo_generate(params, cfg, req.prompt, req.max_new_tokens,
                         cache_len=cache_len)
    return near_tie(params, cfg, req, solo, tokens, label="slot vs solo",
                    cache_len=cache_len)


def engine_phase(cfg, params, *, n_requests: int = N_REQUESTS,
                 prompt_lens=PROMPT_LENS, max_new_tokens: int = MAX_NEW_TOKENS,
                 num_slots: int = NUM_SLOTS, cache_len: int = CACHE_LEN,
                 n_solo: int = N_SOLO):
    reqs = make_requests(cfg.vocab, n=n_requests, prompt_lens=prompt_lens,
                         max_new_tokens=max_new_tokens)
    done, eng = serve(params, cfg, reqs, label="engine", num_slots=num_slots,
                      cache_len=cache_len)
    del eng  # frees the KV pool before the solo runs build their caches
    gc.collect()
    # solo checks on requests of different prompt lengths where there are any
    picked = {}
    for r in reqs:
        picked.setdefault(len(r.prompt), r)
    checked = list(picked.values())[:n_solo]
    checked += [r for r in reqs if r not in checked][: n_solo - len(checked)]
    return [slot_vs_solo(params, cfg, r, done[r.uid].tokens, cache_len=cache_len)
            for r in checked]


# ---------------------------------------------------------------------------
# paper apps
# ---------------------------------------------------------------------------


def apps_phase(*, size: int = IMAGE_SIZE, require_kernel: bool = True) -> None:
    import jax
    import jax.numpy as jnp

    from repro.apps.images import rgb_test_image, test_image
    from repro.apps.kmeans import kmeans_quantize, resolve_fused_block
    from repro.apps.metrics_img import psnr
    from repro.apps.sobel import edge_map
    from repro.kernels.kmeans.ops import kmeans_assign
    from repro.kernels.sobel.ops import sobel_magnitude

    img = test_image("house", size)
    exact = edge_map(img, "exact")
    kern = edge_map(img, "e2afs", use_kernel=True)
    ref = edge_map(img, "e2afs")
    pk, pr = psnr(exact, kern), psnr(exact, ref)
    off = int((~np.isclose(kern, ref, rtol=SOBEL_RTOL, atol=SOBEL_ATOL)).sum())
    log(f"[apps] Sobel {size}x{size}: PSNR vs exact sqrt, kernel {pk:.3f} dB, "
        f"reference {pr:.3f} dB; kernel vs reference max|diff| "
        f"{float(np.abs(kern - ref).max()):.3g}, {off} pixels off the "
        f"rtol {SOBEL_RTOL:g}/atol {SOBEL_ATOL:g} bound")
    if not abs(pk - pr) < PSNR_DB:
        raise SmokeFailure(f"Sobel kernel PSNR is {abs(pk - pr):.3f} dB off")

    rgb = rgb_test_image("peppers", size)
    gray = rgb.mean(-1)
    qb, _ = kmeans_quantize(rgb, sqrt_unit="e2afs", fused=False)
    qf, _ = kmeans_quantize(rgb, sqrt_unit="e2afs", fused=True)
    pb, pf = psnr(gray, qb.mean(-1)), psnr(gray, qf.mean(-1))
    log(f"[apps] K-means K=20 {size}x{size}: PSNR fused {pf:.3f} dB, "
        f"broadcast {pb:.3f} dB")
    if not abs(pb - pf) < PSNR_DB:
        raise SmokeFailure(f"fused K-means PSNR is {abs(pb - pf):.3f} dB off")

    x = jnp.asarray(img, jnp.float32)
    pix = jnp.asarray(rgb.reshape(-1, 3), jnp.float32)
    cent = pix[:20]
    block = resolve_fused_block(pix, cent)
    hlo = {
        "sobel": jax.jit(sobel_magnitude).lower(x).as_text(),
        "kmeans_assign": jax.jit(kmeans_assign).lower(pix, cent).as_text(),
    }
    for name, text in hlo.items():
        has = "tpu_custom_call" in text
        log(f"[apps] {name}: tpu_custom_call in HLO: {has}"
            + (f" (tile {block})" if name == "kmeans_assign" else ""))
        if require_kernel and not has:
            raise SmokeFailure(f"{name} did not lower to a compiled TPU kernel")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def _device_count(tree) -> int:
    import jax

    return min(len(leaf.sharding.device_set) for leaf in jax.tree.leaves(tree))


def mesh_phase(cfg, *, n_requests: int = N_REQUESTS, prompt_lens=PROMPT_LENS,
               max_new_tokens: int = MAX_NEW_TOKENS, num_slots: int = NUM_SLOTS,
               cache_len: int = CACHE_LEN, shape=(2, 2)) -> dict:
    """The 1-device engine, then exact-mode and tp-mode mesh engines on the
    same requests; returns how many requests each mode emits as the
    1-device engine does.

    Exact mode splits no contraction across devices, yet the chip's
    compiler lays out and fuses the partitioned program differently from
    the 1-device one, which reorders sums (on one v5e, the same engine with
    2 slots and with 8 already parts on some requests).  So each request
    that exact mode emits differently must part at a near-tie."""
    import jax

    from repro.distributed.sharding import serve_rules
    from repro.launch.mesh import make_production_mesh

    reqs = make_requests(cfg.vocab, n=n_requests, prompt_lens=prompt_lens,
                         max_new_tokens=max_new_tokens)
    kw = dict(num_slots=num_slots, cache_len=cache_len)
    params = init_params(cfg)
    ref, eng = serve(params, cfg, reqs, label="1-device", **kw)
    # a replica on every device would not fit beside this one on device 0
    host_params = jax.device_get(params)
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params, eng
    gc.collect()

    mesh = make_production_mesh(shape=shape)
    agree, parted = {}, {}
    for mode, rules in (
        ("exact", serve_rules(cfg, mesh, replicate_params=True)),
        ("tp", serve_rules(cfg, mesh)),
    ):
        done, eng = serve(host_params, cfg, reqs, label=f"mesh {mode}",
                          mesh=mesh, rules=rules, **kw)
        same = [r.uid for r in reqs
                if np.array_equal(done[r.uid].tokens, ref[r.uid].tokens)]
        spans = {"kv pool": _device_count(eng._cache),
                 "params": _device_count(eng.params)}
        sharded = sum(not leaf.sharding.is_fully_replicated
                      for leaf in jax.tree.leaves(eng.params))
        log(f"[mesh {mode}] tokens equal to the 1-device engine for "
            f"{len(same)}/{len(reqs)} requests; devices spanned {spans}; "
            f"{sharded} sharded param leaves")
        if spans["kv pool"] != mesh.size:
            raise SmokeFailure(f"[mesh {mode}] KV pool spans {spans['kv pool']} devices")
        if mode == "tp" and (spans["params"] != mesh.size or not sharded):
            raise SmokeFailure("tp params are not sharded over the mesh")
        agree[mode] = len(same)
        parted[mode] = {r.uid: done[r.uid].tokens for r in reqs if r.uid not in same}
        for leaf in jax.tree.leaves((eng.params, eng._cache)):
            leaf.delete()
        del eng, done
        gc.collect()

    if parted["exact"]:
        params = jax.device_put(host_params, jax.devices()[0])
        for r in reqs:
            if r.uid in parted["exact"]:
                near_tie(params, cfg, r, ref[r.uid].tokens, parted["exact"][r.uid],
                         label="mesh exact vs 1-device", cache_len=cache_len)
    return agree


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase, on a (2, 2) mesh")
    args = ap.parse_args(argv)

    # keep the host backend beside the TPU: the datapath phase compares them
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    from repro.local_cache import use_compile_cache

    use_compile_cache()
    import jax

    from repro.configs import get_config

    try:
        device = check_device(jax.devices(), os.environ)
        log(f"[device] {device['platform']} {device['kind']} x{device['count']}")
        cfg = get_config(ARCH, sqrt_unit="e2afs")
        if args.chips == 4:
            if device["count"] < 4:
                raise SmokeFailure(f"--chips 4 needs 4 devices, found {device['count']}")
            mesh_phase(cfg)
        else:
            datapath_phase(jax.devices()[0], jax.devices("cpu")[0])
            params = init_params(cfg)
            engine_phase(cfg, params)
            del params
            gc.collect()
            apps_phase()
    except SmokeFailure as e:
        log(f"FAIL {e}")
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

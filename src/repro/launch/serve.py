"""Serving driver: one-shot batched prefill + scan-based greedy decode.

The fast path runs the whole solve in two heavy device calls instead of
``prompt_len + gen_len``: ``lm.prefill`` writes the full prompt KV cache in
a single jitted causal forward, and ``lm.generate_scan`` decodes under one
jitted ``lax.scan`` whose cache and token buffers are donated (the carry
reuses them; no second full-size cache is ever alive).  The per-token
Python loop survives behind ``mode="loop"`` as the correctness baseline —
the parity tests hold the fast path token-exact against it.

Toy widths by default (``smoke=True``); ``smoke=False`` (CLI
``--published-widths``) serves the architecture's published config.  The
same steps lower under the production mesh in the dry-run.  Supports the
int8-quantized cache."""
from __future__ import annotations

import argparse
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.local_cache import use_compile_cache
from repro.models import lm

MODES = ("scan", "loop")


def prefill_loop(decode, params, cache, prompt):
    """Baseline prefill: teacher-force the prompt one decode_step at a time
    (one device dispatch per prompt token).  Returns (last logits, cache)."""
    logits = None
    for i in range(prompt.shape[1]):
        logits, cache = decode(params, cache, prompt[:, i : i + 1], jnp.int32(i))
    return logits, cache


def decode_loop(decode, params, cache, tok, start, gen_len):
    """Baseline decode: per-token Python loop (one dispatch + one host
    argmax round-trip per generated token).  Returns (tokens, cache)."""
    out = []
    for i in range(gen_len):
        out.append(tok)
        logits, cache = decode(params, cache, tok, jnp.int32(start + i))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
    return jnp.concatenate(out, axis=1), cache


def generate(arch="qwen3-4b", *, batch=2, prompt_len=8, gen_len=16,
             sqrt_unit="e2afs", quantized_kv=False, seed=0, mode="scan",
             reps=3, verbose=True, mesh=None, rules=None, smoke=True):
    """Prefill a random prompt and greedily decode ``gen_len`` tokens with
    ``arch``'s toy config (``smoke=True``) or its published one.

    mode="scan" (default) is the fast path; mode="loop" the per-token
    baseline.  Compilation is warmed up on a throwaway cache before the
    timed passes, so the reported prefill ms / decode tok/s measure steady
    state; ``reps`` timed passes are taken and the best kept (scheduler
    noise only ever slows a pass down).  Returns (tokens (b, prompt+gen),
    stats dict).

    ``mesh=`` runs the scan fast path sharded (docs/serving.md §Sharded
    serving): params and the KV cache are committed to ``rules`` (default
    ``serve_rules(cfg, mesh)``; pass
    ``serve_rules(cfg, mesh, replicate_params=True)`` for the bit-exact
    mode) and prefill/decode trace inside the rule scope.  Scan mode only.
    """
    if mode not in MODES:
        raise ValueError(f"mode must be one of {MODES}, got {mode!r}")
    if mesh is not None and mode != "scan":
        raise ValueError("mesh serving is only wired into mode='scan'")
    if prompt_len < 1:
        raise ValueError(
            f"prompt_len must be >= 1 (got {prompt_len}): prefill needs at "
            f"least one prompt token to produce first-step logits"
        )
    cfg = (get_smoke_config if smoke else get_config)(arch, sqrt_unit=sqrt_unit)
    # MoE prefill routes with a sequence-level expert capacity, so scan-mode
    # greedy tokens may differ from the per-token loop (lm.prefill docs);
    # every other stack is held token-exact by the parity suite
    token_exact = cfg.moe is None
    if mode == "scan" and not token_exact and verbose:
        print(f"[serve] note: {arch} is MoE — prefill routing is not "
              f"token-exact vs mode='loop' (capacity is sequence-level)")
    params, specs = lm.init(cfg, jax.random.key(0))
    prompt = jax.random.randint(jax.random.key(seed), (batch, prompt_len), 0, cfg.vocab)
    fresh_cache = functools.partial(
        lm.init_cache, cfg, batch, prompt_len + gen_len, quantized=quantized_kv
    )
    cache_sh = None
    if mesh is not None:
        from repro.distributed.sharding import serve_rules, shardings_for

        rules = rules if rules is not None else serve_rules(cfg, mesh)
        params = jax.device_put(params, shardings_for(specs, mesh, rules, params))
        cache_abs, cache_specs = fresh_cache(abstract=True)
        cache_sh = shardings_for(cache_specs, mesh, rules, cache_abs)

    if mode == "loop":
        decode = jax.jit(lambda p, c, t, pos: lm.decode_step(p, cfg, c, t, pos))

        def run_once(cache):
            t0 = time.perf_counter()
            logits, cache = prefill_loop(decode, params, cache, prompt)
            jax.block_until_ready(logits)
            t_pf = time.perf_counter()
            tok = jnp.argmax(logits[:, -1:], axis=-1)
            gen, _ = decode_loop(decode, params, cache, tok, prompt_len, gen_len)
            jax.block_until_ready(gen)
            t_dec = time.perf_counter()
            return gen, t_pf - t0, t_dec - t_pf
    else:
        prefill_j = jax.jit(
            lambda p, c, t: lm.prefill(p, cfg, c, t, last_logit_only=True,
                                       mesh=mesh, rules=rules),
            donate_argnums=(1,),
        )
        generate_j = jax.jit(
            lambda p, c, t, pos: lm.generate_scan(p, cfg, c, t, pos, gen_len,
                                                  mesh=mesh, rules=rules),
            donate_argnums=(1, 2),
        )

        def run_once(cache):
            t0 = time.perf_counter()
            logits, cache = prefill_j(params, cache, prompt)
            jax.block_until_ready(logits)
            t_pf = time.perf_counter()
            tok = jnp.argmax(logits[:, -1:], axis=-1)
            gen, _, _ = generate_j(params, cache, tok, jnp.int32(prompt_len))
            jax.block_until_ready(gen)
            t_dec = time.perf_counter()
            return gen, t_pf - t0, t_dec - t_pf

    def new_cache():
        c = fresh_cache()[0]
        return jax.device_put(c, cache_sh) if cache_sh is not None else c

    run_once(new_cache())  # warmup: compile both steps off the clock
    prefill_s, decode_s = float("inf"), float("inf")
    for _ in range(max(1, reps)):
        # a fresh cache per pass (donation consumes it), allocated and
        # settled BEFORE the clock starts so prefill_ms is prefill alone
        cache = jax.block_until_ready(new_cache())
        gen, dt_pf, dt_dec = run_once(cache)
        prefill_s = min(prefill_s, dt_pf)
        decode_s = min(decode_s, dt_dec)
    stats = {
        "mode": mode,
        "prefill_ms": prefill_s * 1e3,
        "decode_tok_s": gen_len * batch / decode_s,
        "decode_ms_per_token": decode_s / gen_len * 1e3,
        "token_exact_vs_loop": token_exact,
    }
    toks = np.asarray(jnp.concatenate([prompt, gen], axis=1))
    if verbose:
        print(f"[serve] {arch} mode={mode} prefill({prompt_len} tok x{batch}) "
              f"{stats['prefill_ms']:.1f} ms; decode {gen_len} tok x{batch} "
              f"({stats['decode_tok_s']:.1f} tok/s, quantized_kv={quantized_kv})")
    return toks, stats


def main():
    """CLI wrapper over :func:`generate`:
    ``python -m repro.launch.serve [--arch qwen3-4b] [--gen-len N] ...``"""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-4b")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--gen-len", type=int, default=16)
    ap.add_argument("--sqrt-unit", default="e2afs")
    ap.add_argument("--quantized-kv", action="store_true")
    ap.add_argument("--mode", choices=MODES, default="scan",
                    help="scan: fused prefill + scan decode; loop: per-token baseline")
    ap.add_argument("--published-widths", action="store_true",
                    help="serve the architecture's published config, not its toy one")
    args = ap.parse_args()
    use_compile_cache()
    toks, _ = generate(args.arch, batch=args.batch, prompt_len=args.prompt_len,
                       gen_len=args.gen_len, sqrt_unit=args.sqrt_unit,
                       quantized_kv=args.quantized_kv, mode=args.mode,
                       smoke=not args.published_widths)
    print(toks[:, :24])


if __name__ == "__main__":
    main()

"""Continuous-batching engine vs the static lock-step baseline.

Replays a Poisson arrival trace of mixed-length requests (prompt lengths and
generation budgets drawn from small bucket sets — bounded compile count)
through ``launch.engine.Engine`` (slot-scheduled decode, admission into freed
slots mid-decode) and through ``run_static_baseline`` (the PR-3 lock-step
scheduler: arrival-order groups, padded prompts, group-max decode length).
Records aggregate useful tok/s and p50/p99 per-request latency for both to
``experiments/results/engine_bench.json``.

A subset of engine outputs is checked token-exact against solo
``prefill`` + ``generate_scan`` runs — the bench doubles as an end-to-end
slot-parity check (greedy, non-MoE archs only) and raises on divergence.

Shape knobs for CI smokes:
    REPRO_ENGINE_BENCH_ARCH      (default qwen3-4b)
    REPRO_ENGINE_BENCH_SLOTS    (default 4)
    REPRO_ENGINE_BENCH_REQUESTS (default 32)
    REPRO_ENGINE_BENCH_RATE_MS  (default 1.0, mean Poisson inter-arrival)
    REPRO_ENGINE_BENCH_CHUNK    (default 8, decode steps per admission point)
    REPRO_ENGINE_BENCH_PROMPTS  (default "4,8,12", prompt-length buckets)
    REPRO_ENGINE_BENCH_GENS     (default "4,16,96", generation budgets)
    REPRO_ENGINE_BENCH_SEED     (default 0)
    REPRO_ENGINE_BENCH_REPS     (default 3, best-of replays per scheduler)

Faults lane (``--faults`` or REPRO_ENGINE_BENCH_FAULTS=1): replays the same
trace three ways — detectors off, detectors on (guardrail overhead must stay
under ~5% and tokens must stay bit-equal), and under a seeded fault schedule
(recovery throughput: how much tok/s the quarantine + exact-fallback ladder
costs while every request still lands a non-failed status).  Artifact:
``experiments/results/engine_bench_faults.json``, gated (warn mode) by the
committed baseline in ``benchmarks/baselines/``.  Extra knobs:
    REPRO_ENGINE_BENCH_FAULT_SITE (default logit_nan; any core.faults site)
    REPRO_ENGINE_BENCH_FAULT_RATE (default 0.02)
    REPRO_ENGINE_BENCH_FAULT_SEED (default 0)

Overload lane (``--overload`` or REPRO_ENGINE_BENCH_OVERLOAD=1): probes the
pool's service capacity with an all-at-once burst, then replays Poisson
traces at 0.5x / 1.0x / 2.0x of that capacity against a BOUNDED queue
(``max_queue = 2 * slots`` by default) — the admission-control contract is
that at 2x saturation the queue depth stays bounded and excess load comes
back as structured ``rejected`` / ``evicted`` completions instead of
unbounded tail latency.  All three shed policies are compared at 2x.
Artifact: ``experiments/results/engine_bench_overload.json``, gated (warn
mode) by the committed baseline.  Extra knobs:
    REPRO_ENGINE_BENCH_MAX_QUEUE (default 2 * slots)

Accuracy-SLO lane (``--slo`` or REPRO_ENGINE_BENCH_SLO=1): the guarded
engine vs today's engine on the same trace — stride=∞ must be bit-exact
(anchor invariant), canaries must be read-only (tokens still bit-exact), a
stride sweep prices the shadow-exact recompute (default-stride overhead is
the warn-gated headline, contract <= ~5% tok/s), a guarded clean run with
budgets derived from the measured natural error must never demote, and
seeded high-bit ``sqrt_man`` pressure must demote with post-demotion
admissions token-exact vs the solo exact run.  Artifact:
``experiments/results/engine_bench_slo.json``, gated (warn mode) by the
committed baseline.  Extra knobs:
    REPRO_ENGINE_BENCH_SLO_STRIDE       (default 32, the headline stride)
    REPRO_ENGINE_BENCH_SLO_STRIDES      (default "8,<stride>,128", sweep)
    REPRO_ENGINE_BENCH_SLO_FAULT_STRIDE (default 4, faulted-run stride)
    REPRO_ENGINE_BENCH_SLO_FAULT_RATE   (default 1.0)
    REPRO_ENGINE_BENCH_SLO_FAULT_BIT    (default 21, pinned mantissa bit)
    REPRO_ENGINE_BENCH_SLO_FAULT_SEED   (default 7)

Speculative lane (``--spec`` or REPRO_ENGINE_BENCH_SPEC=1): draft-and-verify
speculative decoding vs the plain engine on the same trace.  Three replays —
non-speculative baseline, n-gram self-drafting (free, but acceptance tracks
how repetitive the token stream is), and model drafting with the target as
its own drafter (the acceptance ceiling: every draft agrees with the
verifier except where EOS or the budget truncates the block — but a
same-size drafter pays k sequential forwards per step, so its multiplier
can NEVER win wall-clock; it validates the acceptance plumbing, nothing
more).  Speculation is a pure throughput feature, so both speculative
replays must emit tokens BIT-EXACT vs the baseline (hard assertion); the
headline is the n-gram decode tok/s multiplier, warn-gated >1x by the
committed baseline at the CI smoke shape (gemma3-1b, k=2, long gens — a
repetitive stream where self-drafting earns its keep).
Artifact: ``experiments/results/engine_bench_spec.json``.  Extra knobs:
    REPRO_ENGINE_BENCH_SPEC_K (default 3, drafts per verify block)

Mesh lane (``--mesh`` or REPRO_ENGINE_BENCH_MESH=1): replays the same trace
through the engine on a forced-host-device ``(data=2, model=2)`` mesh, in
both serving shardings — ``exact`` (params replicated, slots sharded over
the whole mesh; held bit-exact against the 1-device engine) and ``tp``
(params tensor-parallel over 'model' per serve_rules) — and writes the
1-device-vs-mesh tok/s + p50/p99 comparison to
``experiments/results/engine_bench_mesh.json``.  Needs >= 4 devices: run as
``python -m benchmarks.engine_bench --mesh`` (which forces the host device
count before jax initializes) or set
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` yourself.
"""
from __future__ import annotations

import os
import sys

if __name__ == "__main__" and "--mesh" in sys.argv[1:]:
    # must precede the first jax import: jax locks the device count at init
    flags = os.environ.get("XLA_FLAGS", "")
    if "--xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4 " + flags
        ).strip()

import jax
import numpy as np

from benchmarks.common import md_table, save
from repro.configs import get_smoke_config
from repro.core import FaultConfig
from repro.launch.engine import (
    STATUSES,
    AccuracySLO,
    Engine,
    Request,
    SpecConfig,
    run_static_baseline,
    solo_generate,
)
from repro.local_cache import use_compile_cache
from repro.models import lm


def _env_ints(name, default):
    return tuple(int(v) for v in os.environ.get(name, default).split(","))


def _latencies(done):
    lat = np.asarray([c.latency_s for c in done.values()])
    return {
        "p50_latency_ms": float(np.percentile(lat, 50) * 1e3),
        "p99_latency_ms": float(np.percentile(lat, 99) * 1e3),
    }


def _run_mesh_lane(params, cfg, reqs, *, slots, cache_len, chunk, prompts,
                   reps, done_1dev):
    """1-device vs (data=2, model=2) mesh: same trace, same engine, sharded
    slot pool.  Returns the per-mode stats plus the exact-mode parity bit."""
    from repro.distributed.sharding import serve_rules
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(shape=(2, 2))
    out = {"mesh_shape": {"data": 2, "model": 2}}
    token_exact = cfg.moe is None
    for mode, replicate in (("exact", True), ("tp", False)):
        eng = Engine(
            params, cfg, num_slots=slots, cache_len=cache_len, chunk=chunk,
            mesh=mesh, rules=serve_rules(cfg, mesh, replicate_params=replicate),
        )
        eng.warmup(prompt_lens=prompts)
        done = best = None
        for _ in range(max(1, reps)):
            eng.reset()
            d = eng.run(reqs)
            if best is None or eng.stats["tok_s"] > best["tok_s"]:
                done, best = d, dict(eng.stats, **_latencies(d))
        out[f"mesh_{mode}"] = best
        if mode == "exact" and token_exact:
            mismatched = [
                r.uid for r in reqs
                if not np.array_equal(done[r.uid].tokens, done_1dev[r.uid].tokens)
            ]
            out["mesh_exact_token_equal"] = not mismatched
            out["mesh_exact_mismatched_uids"] = mismatched[:8]
    return out


def _run_faults_lane(params, cfg, reqs, *, arch, slots, cache_len, chunk,
                     prompts, reps):
    """Guardrail overhead + recovery throughput (docs/robustness.md §Bench).

    Three replays of the same trace: detectors off (the pre-guardrail
    engine), detectors on fault-free (overhead must be small and the tokens
    bit-equal — the health reductions never perturb the decode carry), and
    detectors on under a seeded fault schedule (the quarantine + exact-
    fallback ladder's throughput cost while every request still completes).
    """
    site = os.environ.get("REPRO_ENGINE_BENCH_FAULT_SITE", "logit_nan")
    rate = float(os.environ.get("REPRO_ENGINE_BENCH_FAULT_RATE", 0.02))
    fseed = int(os.environ.get("REPRO_ENGINE_BENCH_FAULT_SEED", 0))
    fault_cfg = FaultConfig(site, rate, seed=fseed)

    def best_of(**engine_kw):
        eng = Engine(params, cfg, num_slots=slots, cache_len=cache_len,
                     chunk=chunk, **engine_kw)
        eng.warmup(prompt_lens=prompts)
        done = best = None
        for _ in range(max(1, reps)):
            eng.reset()
            d = eng.run(reqs)
            if best is None or eng.stats["tok_s"] > best["tok_s"]:
                done, best = d, dict(eng.stats, **_latencies(d))
        return done, best

    done_off, s_off = best_of(detectors=False)
    done_on, s_on = best_of()
    overhead_pct = (1.0 - s_on["tok_s"] / max(s_off["tok_s"], 1e-9)) * 100.0
    token_exact = all(
        np.array_equal(done_on[r.uid].tokens, done_off[r.uid].tokens)
        for r in reqs
    )

    done_f, s_f = best_of(faults=fault_cfg, quarantine_retries=1)
    n = len(reqs)
    recovered_frac = (s_f["n_ok"] + s_f["n_degraded"]) / max(n, 1)
    recovery_tok_s_frac = s_f["tok_s"] / max(s_on["tok_s"], 1e-9)

    rows = [
        ["detectors off", f"{s_off['tok_s']:.0f}",
         f"{s_off['p50_latency_ms']:.0f}", f"{s_off['p99_latency_ms']:.0f}", "-"],
        ["detectors on", f"{s_on['tok_s']:.0f}",
         f"{s_on['p50_latency_ms']:.0f}", f"{s_on['p99_latency_ms']:.0f}",
         f"{overhead_pct:+.1f}% ovh"],
        [f"faulted[{site}@{rate}]", f"{s_f['tok_s']:.0f}",
         f"{s_f['p50_latency_ms']:.0f}", f"{s_f['p99_latency_ms']:.0f}",
         f"{s_f['faults_detected']} trips/{s_f['exact_fallbacks']} exact"],
    ]
    print(f"\n== Faults lane ({arch}, slots={slots}, n={n}, site={site}, "
          f"rate={rate}, seed={fseed}; informational) ==")
    print(md_table(["engine", "tok/s", "p50 ms", "p99 ms", "guardrails"], rows))
    print(f"detector overhead {overhead_pct:+.1f}% | detectors token-exact: "
          f"{token_exact} | recovered {recovered_frac:.0%} of requests at "
          f"{recovery_tok_s_frac:.0%} fault-free tok/s")

    payload = {
        "arch": arch,
        "num_slots": slots,
        "n_requests": n,
        "chunk": chunk,
        "fault_site": site,
        "fault_rate": rate,
        "fault_seed": fseed,
        "detectors_off": s_off,
        "detectors_on": s_on,
        "faulted": s_f,
        "detector_overhead_pct": overhead_pct,
        "detectors_token_exact": bool(token_exact),
        "recovered_frac": recovered_frac,
        "recovery_tok_s_frac": recovery_tok_s_frac,
        "statuses": {s: s_f[f"n_{s}"] for s in STATUSES},
    }
    save("engine_bench_faults", payload)
    # after save, so the JSON survives for debugging
    if not token_exact:
        raise AssertionError(
            "health detectors perturbed fault-free decode: detectors-on "
            "tokens diverged from detectors-off"
        )
    return payload


def _run_slo_lane(params, cfg, reqs, *, arch, slots, cache_len, chunk,
                  prompts, gens, reps):
    """Accuracy-SLO lane (docs/robustness.md §Accuracy SLO).

    Five probes of the guarded engine against the unguarded one on the same
    trace: (1) SLO configured but stride=∞ must be BIT-EXACT vs today's
    engine (anchor invariant); (2) canaries at the default stride are
    read-only — tokens still bit-exact — and measure the approximate
    datapath's natural max relative logit error R_clean; (3) a stride sweep
    prices the shadow-exact recompute (the default-stride overhead is the
    warn-gated headline, contract <= ~5% decode tok/s); (4) a guarded clean
    run with budgets derived from R_clean must never demote; (5) under a
    seeded high-bit sqrt_man fault schedule the guarded engine MUST demote,
    and fresh requests admitted into demoted (exact-rung) slots must be
    token-exact vs the solo exact-datapath run.
    """
    stride = int(os.environ.get("REPRO_ENGINE_BENCH_SLO_STRIDE", 32))
    strides = _env_ints("REPRO_ENGINE_BENCH_SLO_STRIDES", f"8,{stride},128")
    fstride = int(os.environ.get("REPRO_ENGINE_BENCH_SLO_FAULT_STRIDE", 4))
    frate = float(os.environ.get("REPRO_ENGINE_BENCH_SLO_FAULT_RATE", 1.0))
    fbit = int(os.environ.get("REPRO_ENGINE_BENCH_SLO_FAULT_BIT", 21))
    fseed = int(os.environ.get("REPRO_ENGINE_BENCH_SLO_FAULT_SEED", 7))
    fault_cfg = FaultConfig("sqrt_man", frate, seed=fseed, bit=fbit)
    # budgets off: huge relative budget, no divergence trigger — measures
    # the canary itself, never trips the ladder
    unbudgeted = dict(rel_err_budget=1e9, divergence_budget=None,
                      promote_after=None)

    def best_of(run_reqs=reqs, **engine_kw):
        eng = Engine(params, cfg, num_slots=slots, cache_len=cache_len,
                     chunk=chunk, **engine_kw)
        eng.warmup(prompt_lens=prompts)
        done = best = None
        for _ in range(max(1, reps)):
            eng.reset()
            d = eng.run(run_reqs)
            if best is None or eng.stats["tok_s"] > best["tok_s"]:
                done, best = d, dict(eng.stats, **_latencies(d))
        return done, best

    # (1) + baseline: unguarded engine, then stride=∞ (ladder routed, no
    # canaries) — the anchor invariant is bit-exactness between the two
    done_base, s_base = best_of()
    done_inf, s_inf = best_of(slo=AccuracySLO(canary_stride=None, **unbudgeted))
    parity_inf = all(
        np.array_equal(done_inf[r.uid].tokens, done_base[r.uid].tokens)
        for r in reqs
    )

    # (2)+(3) canary stride sweep, budgets off: overhead + read-only check
    sweep = {}
    canary_exact = True
    r_clean = 0.0
    for st in sorted(set(strides)):
        done_c, s_c = best_of(slo=AccuracySLO(canary_stride=st, **unbudgeted))
        ovh = (1.0 - s_c["tok_s"] / max(s_base["tok_s"], 1e-9)) * 100.0
        sweep[st] = {
            "tok_s": s_c["tok_s"],
            "overhead_pct": ovh,
            "canary_checks": s_c["canary_checks"],
            "canary_divergences": s_c["canary_divergences"],
            "canary_max_rel_err": s_c["canary_max_rel_err"],
        }
        canary_exact = canary_exact and all(
            np.array_equal(done_c[r.uid].tokens, done_base[r.uid].tokens)
            for r in reqs
        )
        r_clean = max(r_clean, s_c["canary_max_rel_err"])
    overhead_pct = sweep[stride]["overhead_pct"]

    # (4) guarded clean run: the relative-error budget scaled off the
    # measured natural error — 4x headroom over the worst clean canary,
    # floored at 5% — must not trip.  The divergence trigger stays OFF
    # here: an approximate datapath legitimately flips near-tie argmaxes at
    # a low natural rate (the sweep measures it), so token-divergence is a
    # per-deployment policy knob, not a clean-run invariant
    budget = max(4.0 * r_clean, 0.05)
    clean_div_rate = (
        sum(v["canary_divergences"] for v in sweep.values())
        / max(sum(v["canary_checks"] for v in sweep.values()), 1)
    )
    guarded = AccuracySLO(canary_stride=stride, rel_err_budget=budget,
                          divergence_budget=None, promote_after=None)
    _, s_clean = best_of(slo=guarded)

    # (5) seeded sqrt_man pressure: the guarded engine must demote, and
    # fresh requests admitted into demoted slots must match the solo exact
    # run bit-for-bit (the rung IS the exact datapath, prefill included)
    fg = AccuracySLO(canary_stride=fstride, rel_err_budget=budget,
                     divergence_budget=0, promote_after=None)
    eng_f = Engine(params, cfg, num_slots=slots, cache_len=cache_len,
                   chunk=chunk, faults=fault_cfg, slo=fg)
    eng_f.warmup(prompt_lens=prompts)
    done_f = eng_f.run(reqs)
    s_f = dict(eng_f.stats, **_latencies(done_f))
    demotions = int(s_f["demotions"])
    rng = np.random.RandomState(fseed + 1)
    probes = [
        Request(
            uid=100_000 + i,
            prompt=rng.randint(0, cfg.vocab, size=int(rng.choice(prompts))).astype(
                np.int32
            ),
            max_new_tokens=int(rng.choice(gens)),
        )
        for i in range(2 * slots)
    ]
    done_p = eng_f.run(probes)
    ecfg = lm.exact_twin(eng_f.cfg)
    post_exact = True
    post_compared = 0
    for p in probes:
        c = done_p[p.uid]
        # only probes that spent their whole life on the exact rung carry
        # the bit-exactness guarantee (a mid-request demotion mixes rungs)
        if c.unit_final != "exact" or c.unit_trips or c.status != "ok":
            continue
        post_compared += 1
        ref = solo_generate(params, ecfg, p.prompt, p.max_new_tokens,
                            cache_len=cache_len)
        post_exact = post_exact and np.array_equal(c.tokens, ref)

    n = len(reqs)
    rows = [
        ["unguarded", f"{s_base['tok_s']:.0f}", "-", "-", "-"],
        ["slo stride=inf", f"{s_inf['tok_s']:.0f}", "0", "0",
         "bit-exact" if parity_inf else "DIVERGED"],
    ] + [
        [f"canary stride={st}", f"{v['tok_s']:.0f}",
         f"{v['canary_checks']}", f"{v['overhead_pct']:+.1f}%",
         f"maxrel {v['canary_max_rel_err']:.3g}"]
        for st, v in sorted(sweep.items())
    ] + [
        [f"guarded clean (b={budget:.3g})", f"{s_clean['tok_s']:.0f}",
         f"{s_clean['canary_checks']}", "-",
         f"{s_clean['demotions']} demotions"],
        [f"faulted[sqrt_man bit={fbit}]", f"{s_f['tok_s']:.0f}",
         f"{s_f['canary_checks']}", "-",
         f"{demotions} demotions, rungs {list(eng_f.unit_levels)}"],
    ]
    print(f"\n== Accuracy-SLO lane ({arch}, slots={slots}, n={n}, "
          f"chunk={chunk}, default stride={stride}) ==")
    print(md_table(["engine", "tok/s", "canaries", "overhead", "slo"], rows))
    print(f"stride=inf bit-exact: {parity_inf} | canary read-only bit-exact: "
          f"{canary_exact} | R_clean={r_clean:.4g} -> budget={budget:.4g} | "
          f"clean demotions={s_clean['demotions']} | faulted demotions="
          f"{demotions} | post-demotion exact parity: {post_exact} "
          f"({post_compared} probes)")

    payload = {
        "arch": arch,
        "num_slots": slots,
        "n_requests": n,
        "chunk": chunk,
        "canary_stride": stride,
        "stride_sweep": {str(k): v for k, v in sweep.items()},
        "canary_overhead_pct": overhead_pct,
        "slo_parity_token_exact": bool(parity_inf),
        "canary_token_exact": bool(canary_exact),
        "r_clean_max_rel_err": r_clean,
        "clean_divergence_rate": clean_div_rate,
        "rel_err_budget": budget,
        "clean_run_demotions": int(s_clean["demotions"]),
        "fault_site": "sqrt_man",
        "fault_rate": frate,
        "fault_bit": fbit,
        "fault_seed": fseed,
        "fault_stride": fstride,
        "demoted_under_faults": demotions,
        "faulted_unit_levels": list(eng_f.unit_levels),
        "post_demotion_token_exact": bool(post_exact),
        "post_demotion_probes_compared": post_compared,
        "unguarded": s_base,
        "guarded_clean": s_clean,
        "faulted": s_f,
    }
    save("engine_bench_slo", payload)
    # after save, so the JSON survives for debugging
    if not parity_inf:
        raise AssertionError(
            "SLO anchor broken: stride=inf guarded engine diverged from the "
            "unguarded engine (must be bit-exact)"
        )
    if not canary_exact:
        raise AssertionError(
            "shadow-exact canary perturbed served tokens: canary-on decode "
            "diverged from the unguarded engine"
        )
    if s_clean["demotions"] != 0:
        raise AssertionError(
            f"guarded clean run demoted {s_clean['demotions']} slots with "
            f"budget {budget:.4g} (R_clean {r_clean:.4g}) — budget "
            f"derivation or canary stats are wrong"
        )
    if demotions < 1:
        raise AssertionError(
            f"seeded sqrt_man pressure (rate={frate}, bit={fbit}) did not "
            f"demote any slot — the SLO guard is not firing"
        )
    if post_compared < 1:
        raise AssertionError(
            "no post-demotion probe spent its whole life on the exact rung "
            "— cannot certify post-demotion exactness"
        )
    if not post_exact:
        raise AssertionError(
            "post-demotion tokens diverged from the solo exact-datapath run"
        )
    return payload


def _run_spec_lane(params, cfg, reqs, *, arch, slots, cache_len, chunk,
                   prompts, reps):
    """Speculative decoding lane (docs/serving.md §Speculative decoding).

    Same trace, three engines: non-speculative baseline, n-gram
    self-drafting, and model drafting with the target as its own drafter
    (the acceptance ceiling — smoke models are random-init, so a separate
    trained drafter has nothing to agree on; self-drafting isolates the
    acceptance plumbing from draft quality, but pays k same-size forwards
    per step so its wall-clock multiplier is structurally < 1).  Both
    speculative replays must be bit-exact vs the baseline; the n-gram
    tok/s multiplier is the warn-gated headline.
    """
    k = int(os.environ.get("REPRO_ENGINE_BENCH_SPEC_K", 3))

    def best_of(**engine_kw):
        eng = Engine(params, cfg, num_slots=slots, cache_len=cache_len,
                     chunk=chunk, **engine_kw)
        eng.warmup(prompt_lens=prompts)
        done = best = None
        for _ in range(max(1, reps)):
            eng.reset()
            d = eng.run(reqs)
            if best is None or eng.stats["tok_s"] > best["tok_s"]:
                done, best = d, dict(eng.stats, **_latencies(d))
        return done, best

    done_base, s_base = best_of()
    done_ng, s_ng = best_of(spec=SpecConfig(k=k, draft="ngram"))
    done_md, s_md = best_of(spec=SpecConfig(k=k, draft="model"),
                            draft_model=(params, cfg))

    def exact_vs_base(done):
        return all(
            np.array_equal(done[r.uid].tokens, done_base[r.uid].tokens)
            for r in reqs
        )

    exact_ng, exact_md = exact_vs_base(done_ng), exact_vs_base(done_md)
    mult_ng = s_ng["tok_s"] / max(s_base["tok_s"], 1e-9)
    mult_md = s_md["tok_s"] / max(s_base["tok_s"], 1e-9)

    n = len(reqs)
    rows = [
        ["non-spec", f"{s_base['tok_s']:.0f}", "-", "-", "-"],
        [f"ngram k={k}", f"{s_ng['tok_s']:.0f}", f"{mult_ng:.2f}x",
         f"{s_ng['accepted_per_step']:.2f}", f"{s_ng['acceptance_rate']:.2f}"],
        [f"model k={k}", f"{s_md['tok_s']:.0f}", f"{mult_md:.2f}x",
         f"{s_md['accepted_per_step']:.2f}", f"{s_md['acceptance_rate']:.2f}"],
    ]
    print(f"\n== Speculative lane ({arch}, slots={slots}, n={n}, k={k}) ==")
    print(md_table(["engine", "tok/s", "multiplier", "acc/step", "acc rate"],
                   rows))
    print(f"ngram bit-exact: {exact_ng} | model-draft bit-exact: {exact_md} "
          f"| headline ngram multiplier {mult_ng:.2f}x "
          f"(model-draft acceptance ceiling {s_md['accepted_per_step']:.2f}"
          f"/{k})")

    payload = {
        "arch": arch,
        "num_slots": slots,
        "n_requests": n,
        "chunk": chunk,
        "spec_k": k,
        "baseline": s_base,
        "ngram": s_ng,
        "model_draft": s_md,
        # flat gate keys (tools/check_bench.py reads top level only)
        "tok_s_multiplier_ngram": mult_ng,
        "tok_s_multiplier_model": mult_md,
        "accepted_per_step_ngram": s_ng["accepted_per_step"],
        "accepted_per_step_model": s_md["accepted_per_step"],
        "acceptance_rate_ngram": s_ng["acceptance_rate"],
        "acceptance_rate_model": s_md["acceptance_rate"],
        "spec_token_exact": bool(exact_ng and exact_md),
    }
    save("engine_bench_spec", payload)
    # after save, so the JSON survives for debugging
    if not exact_ng:
        raise AssertionError(
            "n-gram speculative engine diverged from the non-speculative "
            "engine (speculation must be a pure throughput feature)"
        )
    if not exact_md:
        raise AssertionError(
            "model-draft speculative engine diverged from the "
            "non-speculative engine"
        )
    if s_md["accepted_per_step"] <= 1.0:
        raise AssertionError(
            f"self-drafting accepted {s_md['accepted_per_step']:.2f} drafts "
            f"per step — the acceptance ceiling should beat 1.0 (draft == "
            f"verifier), so the verify/rollback plumbing is dropping accepts"
        )
    return payload


def _run_overload_lane(params, cfg, *, arch, slots, cache_len, chunk,
                       prompts, gens, seed, n_requests):
    """Admission control under saturation (docs/robustness.md §Overload).

    Probe capacity with an all-at-once burst (unbounded queue), then replay
    Poisson traces at 0.5x/1x/2x the measured service rate with
    ``max_queue`` set.  The contract: the queue stays bounded at every load,
    and past saturation excess requests come back as structured
    ``rejected``/``evicted`` completions rather than unbounded tail latency.
    """
    from repro.launch.engine import SHED_POLICIES

    max_queue = int(os.environ.get("REPRO_ENGINE_BENCH_MAX_QUEUE", 2 * slots))
    rng = np.random.RandomState(seed)
    bodies = [
        (rng.randint(0, cfg.vocab, size=int(rng.choice(prompts))).astype(np.int32),
         int(rng.choice(gens)))
        for _ in range(n_requests)
    ]

    def make_reqs(arrivals, deadlines):
        return [
            Request(uid=i, prompt=bodies[i][0], max_new_tokens=bodies[i][1],
                    arrival_s=float(arrivals[i]), deadline_s=deadlines[i])
            for i in range(n_requests)
        ]

    def serve(reqs, **engine_kw):
        eng = Engine(params, cfg, num_slots=slots, cache_len=cache_len,
                     chunk=chunk, **engine_kw)
        eng.warmup(prompt_lens=prompts)
        done = eng.run(reqs)
        served = {u: c for u, c in done.items() if c.status == "ok"}
        stats = dict(eng.stats)
        stats.update(_latencies(served) if served else
                     {"p50_latency_ms": 0.0, "p99_latency_ms": 0.0})
        stats["rejected_frac"] = stats["n_rejected"] / max(len(done), 1)
        stats["evicted_frac"] = stats["n_evicted"] / max(len(done), 1)
        return done, stats

    # capacity probe: the whole trace due at t=0, queue unbounded — the
    # steady-state service rate every load multiplier is measured against
    zeros = np.zeros(n_requests)
    done_probe, s_probe = serve(make_reqs(zeros, [None] * n_requests))
    capacity_rps = n_requests / max(s_probe["makespan_s"], 1e-9)
    # deadline buckets scaled to observed service latency: the tight bucket
    # is hopeless under queueing delay (exercises eviction / shed-by-slo),
    # the roomy one always survives
    base_lat = max(s_probe["p50_latency_ms"] / 1e3, 1e-3)
    deadline_choices = [base_lat * 2, base_lat * 16, None, None]
    deadlines = [deadline_choices[int(rng.randint(4))] for _ in range(n_requests)]

    loads = {}
    done_2x = None
    for mult in (0.5, 1.0, 2.0):
        arrivals = np.cumsum(
            rng.exponential(1.0 / (capacity_rps * mult), size=n_requests)
        )
        done, stats = serve(make_reqs(arrivals, deadlines),
                            max_queue=max_queue, shed_policy="reject-new")
        loads[mult] = stats
        if mult == 2.0:
            done_2x, arrivals_2x = done, arrivals

    # shed-policy comparison on the same 2x trace
    policies = {"reject-new": loads[2.0]}
    for policy in SHED_POLICIES:
        if policy == "reject-new":
            continue
        _, stats = serve(make_reqs(arrivals_2x, deadlines),
                         max_queue=max_queue, shed_policy=policy)
        policies[policy] = stats

    # structured-degradation spot check: a seeded random subset of the
    # requests served "ok" at 2x must still be bit-exact vs their solo runs
    # (greedy; MoE routing exempt) — seeded, not positional, so different
    # seeds audit different survivors of the shed policy
    token_exact = cfg.moe is None
    parity_ok = True
    if token_exact:
        ok_uids = [u for u, c in sorted(done_2x.items()) if c.status == "ok"]
        pick = np.random.RandomState(seed + 0x5EED).choice(
            len(ok_uids), size=min(3, len(ok_uids)), replace=False
        ) if ok_uids else []
        for uid in (ok_uids[i] for i in pick):
            solo = solo_generate(params, cfg, bodies[uid][0], bodies[uid][1],
                                 cache_len=cache_len)
            if not np.array_equal(done_2x[uid].tokens, solo):
                parity_ok = False
                break

    rows = [
        [f"{mult}x", f"{st['tok_s']:.0f}", f"{st['p50_latency_ms']:.0f}",
         f"{st['p99_latency_ms']:.0f}", f"{st['peak_queue_depth']}",
         f"{st['n_rejected']}", f"{st['n_evicted']}"]
        for mult, st in loads.items()
    ]
    print(f"\n== Overload lane ({arch}, slots={slots}, n={n_requests}, "
          f"max_queue={max_queue}, capacity~{capacity_rps:.1f} req/s; "
          f"informational) ==")
    print(md_table(
        ["load", "tok/s", "p50 ms", "p99 ms", "peak q", "rejected", "evicted"],
        rows,
    ))
    print(md_table(
        ["policy@2x", "rejected", "evicted", "peak q"],
        [[p, f"{st['n_rejected']}", f"{st['n_evicted']}",
          f"{st['peak_queue_depth']}"] for p, st in policies.items()],
    ))

    s2x = loads[2.0]
    payload = {
        "arch": arch,
        "num_slots": slots,
        "n_requests": n_requests,
        "chunk": chunk,
        "max_queue": max_queue,
        "capacity_rps": capacity_rps,
        "probe": s_probe,
        "loads": {str(m): st for m, st in loads.items()},
        "policies_2x": policies,
        # flat gate keys (tools/check_bench.py reads top level only)
        "tok_s_2x": s2x["tok_s"],
        "p99_latency_ms_2x": s2x["p99_latency_ms"],
        "peak_queue_depth_2x": s2x["peak_queue_depth"],
        "queue_bound_margin": max_queue - max(
            st["peak_queue_depth"] for st in loads.values()
        ),
        "rejected_frac_2x": s2x["rejected_frac"],
        "served_token_exact": bool(token_exact and parity_ok),
    }
    save("engine_bench_overload", payload)
    # after save, so the JSON survives for debugging
    if payload["queue_bound_margin"] < 0:
        raise AssertionError(
            f"admission control failed to bound the queue: peak depth "
            f"exceeded max_queue={max_queue} by {-payload['queue_bound_margin']}"
        )
    if s2x["n_rejected"] + s2x["n_evicted"] == 0:
        raise AssertionError(
            "2x-saturation trace shed no load: admission control never "
            "engaged (trace too short or queue bound too large?)"
        )
    if token_exact and not parity_ok:
        raise AssertionError(
            "a request served under overload diverged from its solo run"
        )
    return payload


def run(mesh_lane: bool = False, faults_lane: bool = False,
        overload_lane: bool = False, slo_lane: bool = False,
        spec_lane: bool = False):
    arch = os.environ.get("REPRO_ENGINE_BENCH_ARCH", "qwen3-4b")
    slots = int(os.environ.get("REPRO_ENGINE_BENCH_SLOTS", 4))
    n_requests = int(os.environ.get("REPRO_ENGINE_BENCH_REQUESTS", 32))
    rate_ms = float(os.environ.get("REPRO_ENGINE_BENCH_RATE_MS", 1.0))
    chunk = int(os.environ.get("REPRO_ENGINE_BENCH_CHUNK", 8))
    prompts = _env_ints("REPRO_ENGINE_BENCH_PROMPTS", "4,8,12")
    gens = _env_ints("REPRO_ENGINE_BENCH_GENS", "4,16,96")
    seed = int(os.environ.get("REPRO_ENGINE_BENCH_SEED", 0))
    reps = int(os.environ.get("REPRO_ENGINE_BENCH_REPS", 3))
    mesh_lane = mesh_lane or os.environ.get("REPRO_ENGINE_BENCH_MESH", "") == "1"
    faults_lane = (
        faults_lane or os.environ.get("REPRO_ENGINE_BENCH_FAULTS", "") == "1"
    )
    overload_lane = (
        overload_lane or os.environ.get("REPRO_ENGINE_BENCH_OVERLOAD", "") == "1"
    )
    slo_lane = slo_lane or os.environ.get("REPRO_ENGINE_BENCH_SLO", "") == "1"
    spec_lane = spec_lane or os.environ.get("REPRO_ENGINE_BENCH_SPEC", "") == "1"
    if mesh_lane and jax.device_count() < 4:
        raise RuntimeError(
            "mesh lane needs >= 4 devices: run `python -m benchmarks.engine_bench "
            "--mesh` or set XLA_FLAGS=--xla_force_host_platform_device_count=4 "
            "before the first jax import"
        )

    cfg = get_smoke_config(arch, sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0))

    rng = np.random.RandomState(seed)
    arrivals = np.cumsum(rng.exponential(rate_ms / 1e3, size=n_requests))
    reqs = [
        Request(
            uid=i,
            prompt=rng.randint(0, cfg.vocab, size=int(rng.choice(prompts))).astype(
                np.int32
            ),
            max_new_tokens=int(rng.choice(gens)),
            arrival_s=float(arrivals[i]),
        )
        for i in range(n_requests)
    ]
    cache_len = max(prompts) + max(gens) + 1

    if faults_lane:
        return _run_faults_lane(
            params, cfg, reqs, arch=arch, slots=slots, cache_len=cache_len,
            chunk=chunk, prompts=prompts, reps=reps,
        )
    if overload_lane:
        return _run_overload_lane(
            params, cfg, arch=arch, slots=slots, cache_len=cache_len,
            chunk=chunk, prompts=prompts, gens=gens, seed=seed,
            n_requests=n_requests,
        )
    if slo_lane:
        return _run_slo_lane(
            params, cfg, reqs, arch=arch, slots=slots, cache_len=cache_len,
            chunk=chunk, prompts=prompts, gens=gens, reps=reps,
        )
    if spec_lane:
        return _run_spec_lane(
            params, cfg, reqs, arch=arch, slots=slots, cache_len=cache_len,
            chunk=chunk, prompts=prompts, reps=reps,
        )

    # best-of-N replays per scheduler: both replay the same trace; scheduler
    # noise on a shared machine only ever slows a replay down
    eng = Engine(params, cfg, num_slots=slots, cache_len=cache_len, chunk=chunk)
    eng.warmup(prompt_lens=prompts)
    done_engine = s_engine = None
    for _ in range(max(1, reps)):
        eng.reset()
        done = eng.run(reqs)
        if s_engine is None or eng.stats["tok_s"] > s_engine["tok_s"]:
            done_engine, s_engine = done, dict(eng.stats, **_latencies(done))

    done_static = s_static = None
    warmed: set = set()  # share warm shapes across reps: warm-solve once each
    for _ in range(max(1, reps)):
        done, stats = run_static_baseline(
            params, cfg, reqs, num_slots=slots, warmed=warmed
        )
        if s_static is None or stats["tok_s"] > s_static["tok_s"]:
            done_static, s_static = done, dict(stats, **_latencies(done))

    speedup = s_engine["tok_s"] / max(s_static["tok_s"], 1e-9)
    rows = [
        ["static[lock-step]", f"{s_static['tok_s']:.0f}",
         f"{s_static['p50_latency_ms']:.0f}", f"{s_static['p99_latency_ms']:.0f}"],
        ["engine[continuous]", f"{s_engine['tok_s']:.0f}",
         f"{s_engine['p50_latency_ms']:.0f}", f"{s_engine['p99_latency_ms']:.0f}"],
    ]
    print(f"\n== Engine bench ({arch}, slots={slots}, n={n_requests}, "
          f"prompts={prompts}, gens={gens}; informational) ==")
    print(md_table(["scheduler", "tok/s", "p50 ms", "p99 ms"], rows))
    print(f"continuous-vs-static aggregate speedup {speedup:.2f}x")

    # slot-parity spot check: a seeded random subset must match its solo
    # runs token-for-token (greedy; MoE routing is exempt).  Seeded, not
    # fixed: a structurally-chosen subset (longest/shortest/mid) only ever
    # exercised the same three admit/finish interleavings; drawing from the
    # whole trace rotates coverage across seeds while staying reproducible
    token_exact = cfg.moe is None
    parity_rng = np.random.RandomState(seed + 0x5EED)
    parity_uids = [
        reqs[i].uid
        for i in parity_rng.choice(
            n_requests, size=min(3, n_requests), replace=False
        )
    ]
    parity_ok = True
    if token_exact:
        for uid in dict.fromkeys(parity_uids):
            solo = solo_generate(
                params, cfg, reqs[uid].prompt, reqs[uid].max_new_tokens,
                cache_len=cache_len,
            )
            if not np.array_equal(done_engine[uid].tokens, solo):
                parity_ok = False
                break

    payload = {
        "arch": arch,
        "num_slots": slots,
        "n_requests": n_requests,
        "rate_ms": rate_ms,
        "chunk": chunk,
        "prompt_buckets": list(prompts),
        "gen_buckets": list(gens),
        "engine": s_engine,
        "static": s_static,
        "continuous_vs_static_tok_s_speedup": speedup,
        "token_exact_vs_solo": bool(token_exact and parity_ok),
    }
    if mesh_lane:
        payload.update(
            _run_mesh_lane(
                params, cfg, reqs, slots=slots, cache_len=cache_len,
                chunk=chunk, prompts=prompts, reps=reps, done_1dev=done_engine,
            )
        )
        rows = [
            [name, f"{st['tok_s']:.0f}", f"{st['p50_latency_ms']:.0f}",
             f"{st['p99_latency_ms']:.0f}"]
            for name, st in (
                ("1-device", s_engine),
                ("mesh(2,2)[exact]", payload["mesh_exact"]),
                ("mesh(2,2)[tp]", payload["mesh_tp"]),
            )
        ]
        print(f"\n== Mesh lane ({arch}, {jax.device_count()} host devices; "
              f"informational) ==")
        print(md_table(["engine", "tok/s", "p50 ms", "p99 ms"], rows))
        save("engine_bench_mesh", payload)
    else:
        save("engine_bench", payload)
    # after save, so the JSON survives for debugging
    if token_exact and not parity_ok:
        raise AssertionError(
            "continuous-batching engine diverged from solo greedy decode"
        )
    if mesh_lane and payload.get("mesh_exact_token_equal") is False:
        raise AssertionError(
            "exact-mode mesh engine diverged from the 1-device engine on "
            f"uids {payload['mesh_exact_mismatched_uids']}"
        )
    return payload


def main():
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--mesh", action="store_true",
        help="also run the (data=2, model=2) sharded-engine lane "
             "(forces 4 host devices; artifact: engine_bench_mesh.json)",
    )
    ap.add_argument(
        "--faults", action="store_true",
        help="run the fault-tolerance lane instead: detector overhead, "
             "fault-free token parity, and recovery throughput under a "
             "seeded fault schedule (artifact: engine_bench_faults.json)",
    )
    ap.add_argument(
        "--overload", action="store_true",
        help="run the overload lane instead: capacity probe, bounded-queue "
             "Poisson replays at 0.5x/1x/2x saturation, shed-policy "
             "comparison (artifact: engine_bench_overload.json)",
    )
    ap.add_argument(
        "--spec", action="store_true",
        help="run the speculative-decoding lane instead: non-spec baseline "
             "vs n-gram and model drafting on the same trace — bit-exact "
             "tokens, acceptance rates, tok/s multipliers "
             "(artifact: engine_bench_spec.json)",
    )
    ap.add_argument(
        "--slo", action="store_true",
        help="run the accuracy-SLO lane instead: stride=inf bit-exactness, "
             "canary overhead stride sweep, demotion correctness under "
             "seeded sqrt_man pressure and post-demotion exact parity "
             "(artifact: engine_bench_slo.json)",
    )
    args = ap.parse_args()
    use_compile_cache()
    run(mesh_lane=args.mesh, faults_lane=args.faults,
        overload_lane=args.overload, slo_lane=args.slo, spec_lane=args.spec)


if __name__ == "__main__":
    main()

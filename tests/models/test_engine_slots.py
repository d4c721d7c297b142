"""Slot lifecycle correctness: per-slot positions, admission into a live
cache, and the parity anchor of the continuous-batching refactor — a request
decoded in a staggered slot emits tokens bit-identical to a solo
``prefill`` + ``generate_scan`` run (greedy, non-MoE), for every cache
family (dense GQA, sliding-window ring, SSD state, RG-LRU state; float and
int8 caches).

This suite drives the lm-level pool primitives by hand (raw arrays, exact
staggerings); Engine-level suites express the same anchor through the
shared harness in tests/models/parity.py (docs/testing.md)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.launch.engine import solo_generate
from repro.models import lm

POOL_ARCHS = ["qwen3-4b", "gemma3-1b", "mamba2-2.7b", "recurrentgemma-2b"]


def _setup(arch):
    cfg = get_smoke_config(arch, sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0))
    return cfg, params


def _solo(params, cfg, prompt, gen_len, *, cache_len=32, quantized=False):
    """Reference: the request alone through the PR-3 fast path."""
    return solo_generate(params, cfg, prompt, gen_len, cache_len=cache_len,
                         quantized_kv=quantized)


class _Pool:
    """Minimal host-side slot pool over the lm-level primitives (the Engine
    scheduler adds arrival timing on top; these tests drive admissions by
    hand to hit exact staggerings)."""

    def __init__(self, cfg, params, num_slots, cache_len, *, quantized=False):
        self.cfg, self.params = cfg, params
        self.cache, _ = lm.init_cache(cfg, num_slots, cache_len, quantized=quantized)
        self.tok = jnp.zeros((num_slots, 1), jnp.int32)
        self.pos = jnp.zeros((num_slots,), jnp.int32)
        self.active = jnp.zeros((num_slots,), bool)
        self.remaining = jnp.zeros((num_slots,), jnp.int32)

    def admit(self, prompt, slot, budget):
        logits, self.cache = lm.prefill_into_slots(
            self.params, self.cfg, self.cache, prompt, jnp.asarray([slot])
        )
        self.tok = self.tok.at[slot, 0].set(
            jnp.argmax(logits[0, -1]).astype(jnp.int32)
        )
        self.pos = self.pos.at[slot].set(prompt.shape[1])
        self.active = self.active.at[slot].set(True)
        self.remaining = self.remaining.at[slot].set(budget)

    def decode(self, steps, **kw):
        toks, emitted, self.tok, self.pos, self.active, self.remaining, self.cache = (
            lm.decode_slots_scan(
                self.params, self.cfg, self.cache, self.tok, self.pos,
                self.active, self.remaining, steps, **kw,
            )
        )
        return np.asarray(toks), np.asarray(emitted)


@pytest.mark.parametrize("arch", POOL_ARCHS)
def test_staggered_slots_match_solo_runs(arch):
    """The correctness anchor: two requests admitted at different times into
    one pool each decode bit-identically to their solo runs."""
    cfg, params = _setup(arch)
    pA = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab)
    pB = jax.random.randint(jax.random.key(2), (1, 7), 0, cfg.vocab)
    solA, solB = _solo(params, cfg, pA, 6), _solo(params, cfg, pB, 6)

    pool = _Pool(cfg, params, num_slots=3, cache_len=32)
    pool.admit(pA, slot=1, budget=6)
    t1, e1 = pool.decode(3)
    pool.admit(pB, slot=0, budget=6)  # admitted mid-decode of A
    t2, e2 = pool.decode(9)
    toks = np.concatenate([t1, t2], axis=1)
    emitted = np.concatenate([e1, e2], axis=1)
    np.testing.assert_array_equal(toks[1][emitted[1]], solA)
    np.testing.assert_array_equal(toks[0][emitted[0]], solB)
    assert not np.asarray(pool.active).any()


def test_staggered_slots_match_solo_runs_int8_cache():
    """Same anchor through the int8-quantized cache: per-slot writes quantize
    through the same path, so staggered decode stays bit-exact vs solo."""
    cfg, params = _setup("qwen3-4b")
    pA = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab)
    pB = jax.random.randint(jax.random.key(2), (1, 7), 0, cfg.vocab)
    solA = _solo(params, cfg, pA, 5, quantized=True)
    solB = _solo(params, cfg, pB, 5, quantized=True)

    pool = _Pool(cfg, params, num_slots=2, cache_len=32, quantized=True)
    pool.admit(pA, slot=0, budget=5)
    t1, e1 = pool.decode(2)
    pool.admit(pB, slot=1, budget=5)
    t2, e2 = pool.decode(8)
    toks = np.concatenate([t1, t2], axis=1)
    emitted = np.concatenate([e1, e2], axis=1)
    np.testing.assert_array_equal(toks[0][emitted[0]], solA)
    np.testing.assert_array_equal(toks[1][emitted[1]], solB)


def test_eos_early_exit_frees_slot():
    """A slot goes inactive as soon as it emits the EOS token (chosen here as
    a token the greedy run actually emits), freeing it mid-stream."""
    cfg, params = _setup("qwen3-4b")
    prompt = jax.random.randint(jax.random.key(1), (1, 6), 0, cfg.vocab)
    solo = _solo(params, cfg, prompt, 8)
    eos = int(solo[3])  # the 4th emitted token doubles as EOS
    # greedy decode is deterministic, so the engine must emit exactly
    # tokens [0..3] (EOS included) and then free the slot
    stop = np.flatnonzero(solo == eos)[0]

    pool = _Pool(cfg, params, num_slots=2, cache_len=32)
    pool.admit(prompt, slot=0, budget=8)
    toks, emitted = pool.decode(8, eos_id=eos)
    got = toks[0][emitted[0]]
    np.testing.assert_array_equal(got, solo[: stop + 1])
    assert not np.asarray(pool.active)[0]


def test_slot_reuse_sees_no_stale_kv():
    """A slot freed by one request and re-admitted to another must decode the
    newcomer exactly as a solo run — whole-row insertion plus the per-slot
    validity mask clear and fence the previous occupant's KV."""
    cfg, params = _setup("qwen3-4b")
    pA = jax.random.randint(jax.random.key(1), (1, 9), 0, cfg.vocab)
    pB = jax.random.randint(jax.random.key(2), (1, 4), 0, cfg.vocab)
    solB = _solo(params, cfg, pB, 6)

    pool = _Pool(cfg, params, num_slots=1, cache_len=32)
    pool.admit(pA, slot=0, budget=10)  # fills positions [0, 19) of slot 0
    pool.decode(10)
    assert not np.asarray(pool.active)[0]
    pool.admit(pB, slot=0, budget=6)  # same slot, much shorter occupant
    toks, emitted = pool.decode(6)
    np.testing.assert_array_equal(toks[0][emitted[0]], solB)


def test_window_overflow_request_in_mixed_batch():
    """A sliding-window request whose prompt exceeds its window, decoded in a
    pool next to a short request, matches its solo run (ring roll + per-slot
    wrap validity)."""
    cfg, params = _setup("gemma3-1b")  # smoke window = 8
    long = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab)
    short = jax.random.randint(jax.random.key(2), (1, 3), 0, cfg.vocab)
    sol_long = _solo(params, cfg, long, 6)

    pool = _Pool(cfg, params, num_slots=2, cache_len=32)
    pool.admit(short, slot=1, budget=6)
    pool.decode(2)
    pool.admit(long, slot=0, budget=6)  # prompt 12 > window 8, mid-decode
    t, e = pool.decode(8)
    np.testing.assert_array_equal(t[0][e[0]], sol_long)


def test_short_request_tokens_survive_mixed_batch():
    """Companion to the window-overflow case: the short neighbor is also
    token-exact, including across its own early finish."""
    cfg, params = _setup("gemma3-1b")
    long = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab)
    short = jax.random.randint(jax.random.key(2), (1, 3), 0, cfg.vocab)
    sol_short = _solo(params, cfg, short, 6)
    pool = _Pool(cfg, params, num_slots=2, cache_len=32)
    pool.admit(short, slot=1, budget=6)
    t1, e1 = pool.decode(2)
    pool.admit(long, slot=0, budget=6)
    t2, e2 = pool.decode(8)
    toks = np.concatenate([t1, t2], axis=1)
    emitted = np.concatenate([e1, e2], axis=1)
    np.testing.assert_array_equal(toks[1][emitted[1]], sol_short)


def test_budget_exhaustion_deactivates_and_next_tok_chains():
    """A slot stops after exactly ``budget`` emissions and its pending token
    equals the solo run's continuation (the generate_scan next_tok contract,
    slot-pool edition)."""
    cfg, params = _setup("qwen3-4b")
    prompt = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab)
    solo9 = _solo(params, cfg, prompt, 9)

    pool = _Pool(cfg, params, num_slots=1, cache_len=32)
    pool.admit(prompt, slot=0, budget=4)
    toks, emitted = pool.decode(6)
    assert emitted[0].sum() == 4
    np.testing.assert_array_equal(toks[0][emitted[0]], solo9[:4])
    # the pool's pending token is the solo run's 5th emission
    assert int(np.asarray(pool.tok)[0, 0]) == int(solo9[4])


@pytest.mark.parametrize("arch,quantized", [
    ("qwen3-4b", False),   # dense GQA cache
    ("qwen3-4b", True),    # int8 cache: scales folded inside the kernel
    ("gemma3-1b", False),  # sliding-window ring (wrap validity in-kernel)
])
def test_staggered_slots_match_solo_runs_fused_kernel(arch, quantized):
    """The staggered-vs-solo anchor with ``decode_kernel='fused'``: every
    decode step routes scored attention through the Pallas kernel (on both
    sides), and the smoke configs run float32, where the kernel is bit-exact
    against the inline path — so tokens must also match the inline solo run."""
    cfg, params = _setup(arch)
    fused = cfg.replace(decode_kernel="fused").validate()
    pA = jax.random.randint(jax.random.key(1), (1, 12), 0, cfg.vocab)
    pB = jax.random.randint(jax.random.key(2), (1, 7), 0, cfg.vocab)
    solA = _solo(params, fused, pA, 6, quantized=quantized)
    solB = _solo(params, fused, pB, 6, quantized=quantized)
    # token-exact vs the inline-XLA path in float32 (documented in
    # docs/kernels.md; bf16 runs carry a small documented tolerance instead)
    np.testing.assert_array_equal(solA, _solo(params, cfg, pA, 6, quantized=quantized))

    pool = _Pool(fused, params, num_slots=3, cache_len=32, quantized=quantized)
    pool.admit(pA, slot=1, budget=6)
    t1, e1 = pool.decode(3)
    pool.admit(pB, slot=0, budget=6)
    t2, e2 = pool.decode(9)
    toks = np.concatenate([t1, t2], axis=1)
    emitted = np.concatenate([e1, e2], axis=1)
    np.testing.assert_array_equal(toks[1][emitted[1]], solA)
    np.testing.assert_array_equal(toks[0][emitted[0]], solB)


def test_sampling_path_runs_and_is_deterministic():
    """Opt-in temperature/top-k sampling: per-slot PRNG keyed by request
    stream, deterministic across replays, tokens stay in vocab."""
    cfg, params = _setup("qwen3-4b")
    prompt = jax.random.randint(jax.random.key(1), (1, 5), 0, cfg.vocab)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)

    def run_once():
        pool = _Pool(cfg, params, num_slots=2, cache_len=32)
        pool.admit(prompt, slot=0, budget=6)
        toks, emitted = pool.decode(6, temperature=0.8, top_k=8, keys=keys)
        return toks[0][emitted[0]]

    a, b = run_once(), run_once()
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < cfg.vocab and len(a) == 6


def test_engine_kernel_route_matches_solo_and_counts_blocks(monkeypatch):
    """``Engine`` on the decode-attention kernel route (forced; interpret
    mode here) with 8-line t-blocks over a 32-line cache: three requests
    share two slots, so the third is admitted into a freed slot mid-run,
    and each emits exactly its solo run on the same route.
    ``kv_blocks_read_share`` is the hand count for this schedule, per
    layer (4 blocks a row, 2 rows, 4 steps a chunk):

    chunk 1: A at 5, 6, 7, 8 -> 1+1+1+2; B at 11, 12, 13, then done -> 2+2+2
    chunk 2: A at 9, 10 -> 2+2; C (admitted into B's slot) at 7..10 -> 1+2+2+2
    chunk 3: C at 11 -> 2; three steps with no live row fetch one block each

    27 of 3 chunks x 4 steps x 2 rows x 4 blocks = 96."""
    import dataclasses

    from repro.kernels import dispatch
    from repro.launch.engine import Engine, Request

    spec = dispatch.get("decode_attention")
    monkeypatch.setitem(dispatch._REGISTRY, "decode_attention", dataclasses.replace(
        spec, tiling=dispatch.TilingSpec(default=(8,), candidates=((8,),))))
    cfg, params = _setup("qwen3-4b")
    fused = cfg.replace(decode_kernel="fused").validate()
    prompts = {uid: jax.random.randint(jax.random.key(uid + 1), (n,), 0, cfg.vocab)
               for uid, n in ((0, 5), (1, 11), (2, 7))}
    budgets = {0: 6, 1: 3, 2: 5}
    eng = Engine(params, fused, num_slots=2, cache_len=32, chunk=4)
    done = eng.run([Request(uid=u, prompt=np.asarray(p), max_new_tokens=budgets[u],
                            arrival_s=0.0) for u, p in prompts.items()])
    for uid, prompt in prompts.items():
        solo = _solo(params, fused, prompt[None], budgets[uid])
        np.testing.assert_array_equal(done[uid].tokens, solo)
    assert eng.stats["decode_chunks"] == 3
    assert eng.stats["kv_blocks_read_share"] == 27 / 96
    # the inline route reads every row whole and reports no share
    inline = Engine(params, cfg, num_slots=2, cache_len=32, chunk=4)
    inline.run([Request(uid=0, prompt=np.asarray(prompts[0]), max_new_tokens=2,
                        arrival_s=0.0)])
    assert inline.stats["kv_blocks_read_share"] is None

"""The plain reference: its E2AFS-R matches the program's datapath bit for
bit, its weights are the program's bit for bit, its forward pass agrees with
the program's in float32, and its fp8 control reads wider gaps than a sound
run of the program at toy size."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_tiny import make_root

from bench import check, harness, spec, weights
from bench.control import weight_mismatches
from bench.reference import Reference, rsqrt_e2afs
from repro.core.e2afs import e2afs_rsqrt
from repro.models import lm


def test_rsqrt_matches_the_program_datapath():
    rng = np.random.default_rng(0)
    bits = rng.integers(0x00800000, 0x7F800000, 1 << 18, dtype=np.uint32)
    x = jnp.asarray(bits.view(np.float32))
    np.testing.assert_array_equal(np.asarray(rsqrt_e2afs(x)), np.asarray(e2afs_rsqrt(x)))


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("ref"))


@pytest.mark.parametrize("name", ["toy-qwen.chat", "toy-sc.code", "toy-sc-tp4.code",
                                  "toy-mixed.chat"])
def test_reference_redraws_the_program_weights(root, name):
    cell = spec.cell(name, root)
    diff = weight_mismatches(cell, 2**33 + 5)
    assert "embed" in diff and any(k.startswith("attn/") for k in diff)
    assert diff == dict.fromkeys(diff, 0)


@pytest.mark.parametrize("rule", sorted(weights._RULES))
def test_every_level_is_a_bf16_number(rule):
    """No rounding is left between the draw and bf16, for any width."""
    mean, _ = weights._RULES[rule]
    for shape in ((64, 4, 16), (2560, 32, 128), (9728, 2560), (151936, 2560), (16,)):
        _, std = weights.leaf_spec(rule, shape)
        m, step = weights.levels(mean, std)
        k = np.arange(-(2**m - 1), 2**m, 2)
        v = (k * step + mean).astype(np.float32)
        np.testing.assert_array_equal(np.asarray(jnp.asarray(v).astype(jnp.bfloat16),
                                                 np.float32), v)
        assert std / 2**0.5 <= v.std() <= std * 2**0.5


@pytest.mark.parametrize("name", ["toy-qwen.chat", "toy-sc.code"])
def test_reference_forward_agrees_with_the_program_in_fp32(root, name):
    cell = spec.cell(name, root)
    cfg = harness.program_config(cell["config"]).replace(act_dtype="float32", remat="none")
    shapes, _ = lm.init(cfg.replace(act_dtype="bfloat16"), jax.random.PRNGKey(0), abstract=True)
    p = weights.program_params(11, shapes, cfg.n_layers)
    p32 = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    toks = np.random.default_rng(1).integers(0, cfg.vocab, 80).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        prog, _ = lm.forward(p32, cfg, {"tokens": jnp.asarray(toks)[None]})
    prog = np.asarray(prog[0, :, : cfg.vocab])
    ref = Reference(cell["config"]["model"], 11).logits([toks], [0], [512])[0]
    scale = np.abs(ref).max()
    np.testing.assert_allclose(ref, prog, atol=1e-3 * scale)
    assert (np.argmax(ref, -1) == np.argmax(prog, -1)).mean() > 0.95


def test_control_reads_wider_than_a_sound_run(root):
    cell = spec.cell("toy-qwen.chat", root)
    ref_seeds = []
    for seed in (1, 2, 3):
        served = harness.serve(cell, seed, 2.0, trace=False, t_start=time.perf_counter(),
                               log=lambda m: None)
        prompts = {r.uid: r.prompt for r in served["requests"]}
        r = check.run_check(Reference(cell["config"]["model"], seed), served["completions"],
                            prompts, seed, cell["traffic"], Reference.Q_BLOCK, control=True)
        r["not_served"] = check.not_served(served["requests"], served["completions"])
        ref_seeds.append(r)
    lower = max(r["logit_gap"] for r in ref_seeds)
    upper = min(r["control_gap"] for r in ref_seeds)
    print("readings", [(r["logit_gap"], r["control_gap"]) for r in ref_seeds])
    limit = cell["limits"]["logit_gap"]
    assert lower <= limit < upper
    assert upper >= 3 * max(lower, 1e-3)
    # through the harness's own comparison: sound runs correct, control not
    for r in ref_seeds:
        assert check.verdict(r, cell["limits"])[0] is True
        assert check.verdict(dict(r, logit_gap=r["control_gap"]), cell["limits"])[0] is False


# one toy StarCoder2 block with attention biases: LayerNorm, GELU, GQA (4
# query heads over 2 KV heads), a window of 5 over 8 positions
BIASED = dict(hidden_size=32, intermediate_size=64, num_hidden_layers=2,
              num_attention_heads=4, num_key_value_heads=2, head_dim=8, vocab_size=256,
              hidden_act="gelu_pytorch_tanh", norm="layernorm", norm_eps=1e-5,
              rope_theta=100000.0, sliding_window=5, tie_word_embeddings=False,
              qk_norm=False, use_bias=True)


def _loop_layer(w, x, m):
    """The block token by token in float64 numpy, with the E2AFS-R rsqrt of
    each float32 variance."""
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    h, kv, hd, win = (m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"],
                      m["sliding_window"])
    g, half = h // kv, hd // 2

    def ln(v, name):
        mu = v.mean()
        var = np.float32(((v - mu) ** 2).mean() + m["norm_eps"])
        return (v - mu) * float(rsqrt_e2afs(jnp.float32(var))) * w[f"{name}_scale"] + w[
            f"{name}_bias"]

    def rope(v, p):
        ang = p / m["rope_theta"] ** (np.arange(half) / half)
        a, b = v[..., :half], v[..., half:]
        return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                               b * np.cos(ang) + a * np.sin(ang)], axis=-1)

    ks, vs, out = [], [], []
    for t in range(x.shape[0]):
        a = ln(x[t], "ln1")
        q = rope(np.einsum("d,dhk->hk", a, w["attn/wq"]) + w["attn/bq"], t)
        ks.append(rope(np.einsum("d,dhk->hk", a, w["attn/wk"]) + w["attn/bk"], t))
        vs.append(np.einsum("d,dhk->hk", a, w["attn/wv"]) + w["attn/bv"])
        seen = range(max(0, t - win + 1), t + 1)
        att = np.zeros((h, hd))
        for i in range(h):
            s = np.array([q[i] @ ks[j][i // g] for j in seen]) / np.sqrt(hd)
            p = np.exp(s - s.max())
            att[i] = sum(pj * vs[j][i // g] for pj, j in zip(p / p.sum(), seen))
        y = x[t] + np.einsum("hk,hkd->d", att, w["attn/wo"]) + w["attn/bo"]
        u = ln(y, "ln2") @ w["mlp/wi_up"] + w["mlp/bi"]
        u = 0.5 * u * (1 + np.tanh(np.sqrt(2 / np.pi) * (u + 0.044715 * u ** 3)))
        out.append(y + u @ w["mlp/wo"] + w["mlp/bo"])
    return np.stack(out)


def test_reference_attention_biases_match_a_per_token_loop():
    ref = Reference(BIASED, 2**32 + 3)
    w = ref.draw_layer(1)
    assert {"attn/bq", "attn/bk", "attn/bv", "attn/bo", "mlp/bo"} <= set(w)
    x = np.random.default_rng(4).normal(size=(8, BIASED["hidden_size"])).astype(np.float32)
    pos = jnp.arange(8, dtype=jnp.int32)
    got = np.asarray(ref._layer(w, jnp.asarray(x), pos, False, BIASED["sliding_window"]))
    want = _loop_layer(w, x.astype(np.float64), BIASED)
    # float32 rounding of sums over at most 64 terms of unit-scale values
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-5 * np.abs(want).max())
    # the biases matter at that tolerance: the same weights without them differ
    plain = Reference(dict(BIASED, use_bias=False), 2**32 + 3)
    wp = plain.draw_layer(1)
    assert "attn/bq" not in wp
    without = np.asarray(plain._layer(wp, jnp.asarray(x), pos, False, BIASED["sliding_window"]))
    assert np.abs(without - want).max() > 100 * 1e-5 * np.abs(want).max()

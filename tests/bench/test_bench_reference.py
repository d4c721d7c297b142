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


@pytest.mark.parametrize("name", ["toy-qwen.chat", "toy-sc.code"])
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

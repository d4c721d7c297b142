"""Architecture modules (bench/archs/): ``qwen3-4b`` resolves to the dense
default and reads as it did; a mixed-layer architecture enters as new files
alone and reads correct while its fp8 control does not; the weight draw
takes stacked and listed layers alike, and an architecture's own rules."""
import json
import math
import time

import jax
import numpy as np
import pytest

from bench_tiny import CPU_DEVICE, MIXED_PATTERN, PEAKS, REPO, make_root

from bench import check, flops, harness, spec, weights
from bench.archs import dense
from bench.run import run_cell
from repro.configs import get_smoke_config
from repro.models import lm

MIXED = "toy-mixed.chat"
SEED = 123456789012


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("archs"))


def _qwen_model():
    return json.loads((REPO / "bench" / "configs" / "qwen3-4b.json").read_text())["model"]


# -- qwen3-4b is pinned ------------------------------------------------------

def test_qwen_cell_resolves_to_the_dense_default():
    cell = spec.cell("qwen3-4b.chat-steady", REPO)
    assert cell["arch"] is dense
    assert cell["arch"].RULES == {} and cell["arch"].Reference.RULES == {}
    assert harness.program_config(cell["config"], cell["arch"]).uniform


# qwen3-4b's counts as the parent's bench/flops.py gave them
QWEN_PREFILL = {1: (8045133824, 8044696576), 128: (935776354304, 8064073728),
                1000: (7562616504320, 8197120000), 1024: (7751348387840, 8200781824)}
QWEN_DECODE = [([0], (8045133824, 8044844032)),
               ([5, 2047, 300], (25522667520, 8392260608)),
               (list(range(100, 112)), (97288323072, 8234823680)),
               ([], (0, 0))]


@pytest.mark.parametrize("s", sorted(QWEN_PREFILL))
def test_dense_prefill_counts_are_flops(s):
    model = _qwen_model()
    got = dense.work(model).prefill(s)
    assert got == flops.prefill(flops.dims(model), s) == QWEN_PREFILL[s]


@pytest.mark.parametrize("positions,want", QWEN_DECODE)
def test_dense_decode_counts_are_flops(positions, want):
    model = _qwen_model()
    got = dense.work(model).decode_step(positions)
    assert got == flops.decode_step(flops.dims(model), positions) == want


# -- a mixed-layer architecture as new files ---------------------------------

def test_mixed_cell_takes_its_own_module(root):
    cell = spec.cell(MIXED, root)
    assert cell["arch"] is not dense
    assert cell["arch"].__file__ == str(root / "bench" / "archs" / "toy_mixed.py")
    cfg = harness.program_config(cell["config"], cell["arch"])
    assert cfg.blocks == tuple(MIXED_PATTERN) and not cfg.uniform
    ref = cell["arch"].Reference(cell["config"]["model"], 1)
    assert ref.windows == [8, 8, 8, None]


def test_mixed_cell_runs_correct(root):
    r = run_cell(spec.cell(MIXED, root), SEED, 2.0, False, CPU_DEVICE, PEAKS,
                 time.perf_counter(), log=lambda m: None)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] == 16
    assert r["checked"]["logit_gap"]["value"] <= r["checked"]["logit_gap"]["limit"]


def test_mixed_cell_control_is_not_correct(root):
    """The fp8 control fails the limit, and so does the dense reference,
    which windows every layer: the architecture's own reference is what
    makes the sound run correct."""
    cell = spec.cell(MIXED, root)
    seed = 2**33 + 1
    served = harness.serve(cell, seed, 2.0, trace=False, t_start=time.perf_counter(),
                           log=lambda m: None)
    prompts = {r.uid: r.prompt for r in served["requests"]}
    failed = check.not_served(served["requests"], served["completions"])
    ref = cell["arch"].Reference(cell["config"]["model"], seed)
    r = check.run_check(ref, served["completions"], prompts, seed, cell["traffic"],
                        ref.Q_BLOCK, control=True)
    r["not_served"] = failed
    assert check.verdict(r, cell["limits"])[0] is True
    assert check.verdict(dict(r, logit_gap=r["control_gap"]), cell["limits"])[0] is False
    one_window = check.run_check(dense.Reference(cell["config"]["model"], seed),
                                 served["completions"], prompts, seed, cell["traffic"],
                                 ref.Q_BLOCK)
    assert check.verdict(dict(one_window, not_served=failed), cell["limits"])[0] is False


@pytest.mark.parametrize("path", ["/abs/toy_mixed.py", "bench/../../toy_mixed.py"])
def test_architecture_outside_the_root_is_refused(path):
    with pytest.raises(ValueError, match="not a path under the root"):
        spec.architecture({"name": "x", "architecture": path}, REPO)


def test_dense_default_refuses_mixed_layers(root):
    config = spec.cell(MIXED, root)["config"]
    with pytest.raises(ValueError, match="toy-mixed: sliding window set.*windows every layer"):
        harness.program_config(config)
    bad = dict(config, model=dict(config["model"], layer_types=["full_attention"] * 4))
    with pytest.raises(ValueError, match="layer_types"):
        harness.program_config(bad, spec.cell(MIXED, root)["arch"])


def test_dense_default_compares_layer_types():
    c = json.loads((REPO / "bench" / "configs" / "qwen3-4b.json").read_text())
    c["model"]["layer_types"] = ["full_attention"] * 36
    harness.program_config(c)
    c["model"]["layer_types"][5] = "sliding_attention"
    with pytest.raises(ValueError, match="layer_types"):
        harness.program_config(c)


@pytest.mark.parametrize("kind", ["sliding_attention", "full_attention"])
def test_mixed_work_of_uniform_layers_is_the_dense_count(root, kind):
    """With every layer of one kind, the toy's per-layer counts are the
    dense counts with that window (or none)."""
    arch = spec.cell(MIXED, root)["arch"]
    model = dict(spec.cell(MIXED, root)["config"]["model"], layer_types=[kind] * 4)
    want = flops.dims(model if kind == "sliding_attention"
                      else dict(model, sliding_window=None))
    got = arch.work(model)
    for s in (1, 8, 9, 40):
        assert got.prefill(s) == want.prefill(s)
    for pos in ([0], [7, 8, 50], list(range(30))):
        assert got.decode_step(pos) == want.decode_step(pos)


def test_mixed_work_by_hand(root):
    cell = spec.cell(MIXED, root)
    m = cell["config"]["model"]
    w = cell["arch"].work(m)
    full = flops.dims(dict(m, sliding_window=None))
    # position 20: three window layers attend 8 of its 21 positions, the
    # full layer all 21
    per_pos = 4 * m["num_attention_heads"] * m["head_dim"]
    line = 2 * m["num_key_value_heads"] * m["head_dim"] * 2
    f, b = w.decode_step([20])
    f_full, b_full = full.decode_step([20])
    assert f == f_full - per_pos * 3 * 13
    assert b == b_full - line * 3 * 13


# -- the weight draw ---------------------------------------------------------

def _leaves(tree):
    return {jax.tree_util.keystr(k): v for k, v in jax.tree_util.tree_leaves_with_path(tree)}


def _at(tree, path):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def test_listed_layers_draw_the_stacked_bits(root):
    cell = spec.cell(MIXED, root)
    listed_cfg = harness.program_config(cell["config"], cell["arch"])
    stacked_cfg = listed_cfg.replace(block_pattern=("global",))
    listed = harness.program_weights(listed_cfg, SEED)
    stacked = harness.program_weights(stacked_cfg, SEED)
    assert isinstance(listed["layers"], list) and isinstance(stacked["layers"], dict)
    ref = cell["arch"].Reference(cell["config"]["model"], SEED)
    for i, layer in enumerate(listed["layers"]):
        redrawn = ref.draw_layer(i)
        flat = {weights.path_and_layer(k)[0]: v
                for k, v in jax.tree_util.tree_leaves_with_path(layer)}
        assert set(flat) == set(redrawn)
        for path, leaf in flat.items():
            from_stack = np.asarray(_at(stacked["layers"], path)[i])
            np.testing.assert_array_equal(np.asarray(leaf).view(np.uint16),
                                          from_stack.view(np.uint16), err_msg=path)
            np.testing.assert_array_equal(np.asarray(leaf, np.float32),
                                          np.asarray(redrawn[path]), err_msg=path)
    for k in ("embed", "ln_f"):
        np.testing.assert_array_equal(np.asarray(listed[k]).view(np.uint16),
                                      np.asarray(stacked[k]).view(np.uint16))


def test_listed_layers_must_number_n_layers(root):
    cell = spec.cell(MIXED, root)
    cfg = harness.program_config(cell["config"], cell["arch"])
    abstract, _ = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    with pytest.raises(ValueError, match="4 listed != 5 layers"):
        weights.program_params(SEED, abstract, 5)


# weight rules for expert stacks and state-space mixers, as an architecture
# module would bring them
RULES = {
    "moe/router": (0.0, "first"),
    "moe/wi_gate": (0.0, "axis:1"),
    "moe/wi_up": (0.0, "axis:1"),
    "moe/wo": (0.0, "axis:1"),
    "in_proj": (0.0, "first"),
    "out_proj": (0.0, "first"),
    "conv_w": (0.0, 0.25),
    "a_log": (0.0, 0.1),
    "d_skip": (1.0, 0.1),
    "dt_bias": (0.0, 0.1),
}
SMOKE_FIRST_UNRULED = {"mixtral-8x22b": "layers/moe/router",
                       "mamba2-2.7b": "layers/mixer/a_log"}


@pytest.mark.parametrize("name", sorted(SMOKE_FIRST_UNRULED))
def test_rules_cover_expert_and_state_space_trees(name):
    cfg = get_smoke_config(name)
    abstract, _ = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    drawn = weights.program_params(SEED, abstract, cfg.n_layers, rules=RULES)
    for path, leaf in _leaves(drawn).items():
        assert leaf.dtype == weights.DTYPE and np.isfinite(np.asarray(leaf, np.float32)).all()
        assert np.asarray(leaf, np.float32).std() > 0, path


def test_expert_stacks_draw_at_their_fan_in():
    cfg = get_smoke_config("mixtral-8x22b")
    e, d, f = cfg.moe.n_experts, cfg.d_model, cfg.moe.d_ff_expert
    assert weights.leaf_spec("layers/moe/wi_gate", (e, d, f), RULES) == (0.0, 1 / math.sqrt(d))
    assert weights.leaf_spec("layers/moe/wo", (e, f, d), RULES) == (0.0, 1 / math.sqrt(f))
    # without the rules the last component's default reads the expert axis
    assert weights.leaf_spec("layers/moe/wi_gate", (e, d, f)) == (0.0, 1 / math.sqrt(e))
    # a rule by path comes before one by the last component, and both
    # before the defaults
    rules = {"wo": (0.0, 0.5), "attn/wo": (0.0, 0.25)}
    assert weights.leaf_spec("layers/attn/wo", (4, 16, 64), rules) == (0.0, 0.25)
    assert weights.leaf_spec("layers/mlp/wo", (128, 64), rules) == (0.0, 0.5)
    assert weights.leaf_spec("layers/mlp/wo", (128, 64)) == (0.0, 1 / math.sqrt(128))


@pytest.mark.parametrize("name", sorted(SMOKE_FIRST_UNRULED))
def test_a_leaf_without_a_rule_is_named(name):
    cfg = get_smoke_config(name)
    abstract, _ = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    with pytest.raises(KeyError, match=f"no weight rule for '{SMOKE_FIRST_UNRULED[name]}'"):
        weights.program_params(SEED, abstract, cfg.n_layers)

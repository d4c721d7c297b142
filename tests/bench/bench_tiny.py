"""A tiny benchmark root for the CPU tests: the real BENCHMARK.json with
four toy cells added, toy configuration, mix and limit files beside it, the
real metric readers and peaks, and a toy architecture module
(fixtures/archs/toy_mixed.py).  The code is the repository's ``bench``
package; only the data files and the architecture module live under
``root``."""
import json
import shutil
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

TOY_WIDTHS = dict(hidden_size=64, intermediate_size=128, num_hidden_layers=2,
                  num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                  vocab_size=256)
# the model block's widths -> the program's config fields
_PROGRAM = dict(hidden_size="d_model", intermediate_size="d_ff",
                num_hidden_layers="n_layers", num_attention_heads="n_heads",
                num_key_value_heads="n_kv_heads", head_dim="d_head", vocab_size="vocab")

CHAT = {"arrival": {"process": "poisson", "rate_rps": 8.0},
        "prompt": {"dist": "lognormal", "median": 12, "sigma": 0.8, "buckets": [8, 16, 32]},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2, "max": 24},
        "strata": 4, "drain_s": 30.0, "check_tokens": 100000}
CODE = {"arrival": {"process": "gamma", "cv": 3.0, "rate_rps": 8.0},
        "prompt": {"dist": "choice", "values": [16, 48], "weights": [1, 1]},
        "output": {"dist": "lognormal", "median": 8, "sigma": 0.6, "min": 2, "max": 16},
        "strata": 4, "drain_s": 30.0, "check_tokens": 100000}

# Toy limits on the widest logit gap of served tokens, set like the cells'
# (PERF.md): between the widest gap of sound runs over seeds 1-8 (toy-qwen
# 0.0063, toy-sc 0.034, toy-sc-tp4 0.026, toy-mixed 0.0062) and the
# narrowest fp8-control gap over seeds 1-4 (0.044, 0.27, 0.26 and 0.056),
# on a host CPU.
LIMITS = {"toy-qwen.chat": {"logit_gap": 0.02, "not_served": 0},
          "toy-sc.code": {"logit_gap": 0.1, "not_served": 0},
          "toy-sc-tp4.code": {"logit_gap": 0.1, "not_served": 0},
          "toy-mixed.chat": {"logit_gap": 0.02, "not_served": 0}}

# the (data 1, model 4) mesh of tests/conftest.py's four host devices
MESH = {"data": 1, "model": 4}

# a LayerNorm, GELU, sliding-window model with untied embeddings (the
# StarCoder2 family), so the toy cells cover both norms and the window ring
_SC_BASE = {
    "name": "toy-sc",
    "model": {"hidden_act": "gelu_pytorch_tanh", "tie_word_embeddings": False,
              "rope_theta": 100000.0, "torch_dtype": "bfloat16", "norm": "layernorm",
              "norm_eps": 1e-05, "qk_norm": False},
    "program": {"arch": "starcoder2-15b",
                "overrides": {"block_pattern": ["window"], "sqrt_unit": "e2afs"}},
}


def _toy_config(base: dict, name: str, **model) -> dict:
    c = json.loads(json.dumps(base))
    c["name"] = name
    c["model"].update(TOY_WIDTHS, **model)
    over = {v: c["model"][k] for k, v in _PROGRAM.items()}
    if model.get("sliding_window"):
        over["window"] = model["sliding_window"]
    c["program"]["overrides"].update(over)
    return c


# three sliding-window layers and one of full attention, as a
# ``layer_types`` list and the program's ``block_pattern``; the window is
# shorter than most of the chat mix's prompts
MIXED_TYPES = ["sliding_attention"] * 3 + ["full_attention"]
MIXED_PATTERN = ["window"] * 3 + ["global"]
MIXED_WINDOW = 8
ARCHS = Path(__file__).resolve().parent / "fixtures" / "archs"


def make_root(tmp: Path) -> Path:
    """Write the toy root under ``tmp``; cells ``toy-qwen.chat`` (RMSNorm,
    qk-norm, tied), ``toy-sc.code`` (LayerNorm, window 32, prompts past
    the window) and ``toy-sc-tp4.code``: the same model with 8 query and 4
    KV heads, served on :data:`MESH`, so every device holds a quarter of
    the heads, the KV heads, the MLP and the vocabulary; and
    ``toy-mixed.chat``, the qwen toy with 4 layers of :data:`MIXED_TYPES`,
    whose configuration names its architecture module,
    ``bench/archs/toy_mixed.py``."""
    root = Path(tmp) / "root"
    (root / "bench").mkdir(parents=True)
    shutil.copytree(REPO / "bench" / "metrics", root / "bench" / "metrics")
    shutil.copy(REPO / "bench" / "peaks.json", root / "bench" / "peaks.json")
    shutil.copytree(ARCHS, root / "bench" / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "traffic", "limits"):
        (root / "bench" / d).mkdir()
    q = _toy_config(json.loads((REPO / "bench" / "configs" / "qwen3-4b.json").read_text()),
                    "toy-qwen")
    q["engine"] = dict(num_slots=4, cache_len=128, chunk=4)
    s = _toy_config(_SC_BASE, "toy-sc", sliding_window=32)
    s["engine"] = dict(num_slots=4, cache_len=32, chunk=4)
    t = _toy_config(_SC_BASE, "toy-sc-tp4", sliding_window=32, num_attention_heads=8,
                    num_key_value_heads=4)
    t["engine"] = dict(num_slots=4, cache_len=32, chunk=4, mesh=MESH)
    x = _toy_config(q, "toy-mixed", num_hidden_layers=len(MIXED_TYPES),
                    sliding_window=MIXED_WINDOW, layer_types=MIXED_TYPES)
    x["program"]["overrides"]["block_pattern"] = MIXED_PATTERN
    x["architecture"] = "bench/archs/toy_mixed.py"
    x["engine"] = dict(num_slots=4, cache_len=64, chunk=4)
    files = {"configs/toy-qwen.json": q, "configs/toy-sc.json": s,
             "configs/toy-sc-tp4.json": t, "configs/toy-mixed.json": x,
             "traffic/chat.json": CHAT, "traffic/code.json": CODE,
             "limits/toy-qwen.chat.json": LIMITS["toy-qwen.chat"],
             "limits/toy-sc.code.json": LIMITS["toy-sc.code"],
             "limits/toy-sc-tp4.code.json": LIMITS["toy-sc-tp4.code"],
             "limits/toy-mixed.chat.json": LIMITS["toy-mixed.chat"]}
    for rel, obj in files.items():
        (root / "bench" / rel).write_text(json.dumps(obj))
    b = json.loads((REPO / "BENCHMARK.json").read_text())
    b["configs"] += [
        {"name": "toy-qwen", "source": "test", "file": "bench/configs/toy-qwen.json",
         "reduced": [], "why": "toy"},
        {"name": "toy-sc", "source": "test", "file": "bench/configs/toy-sc.json",
         "reduced": [], "why": "toy"},
        {"name": "toy-sc-tp4", "source": "test", "file": "bench/configs/toy-sc-tp4.json",
         "reduced": [], "why": "toy"},
        {"name": "toy-mixed", "source": "test", "file": "bench/configs/toy-mixed.json",
         "reduced": [], "why": "toy"}]
    b["workloads"] += [
        {"name": "toy-qwen.chat", "config": "toy-qwen", "traffic": "chat", "chips": 1, "why": "toy"},
        {"name": "toy-sc.code", "config": "toy-sc", "traffic": "code", "chips": 1, "why": "toy"},
        {"name": "toy-sc-tp4.code", "config": "toy-sc-tp4", "traffic": "code", "chips": 4,
         "why": "toy"},
        {"name": "toy-mixed.chat", "config": "toy-mixed", "traffic": "chat", "chips": 1,
         "why": "toy"}]
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    return root


CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_s": 819e9, "hbm_bytes": 16e9}

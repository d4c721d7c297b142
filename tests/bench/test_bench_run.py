"""A whole run of a toy cell on CPU, past the harness's look for a chip:
the result's shape, a sound run reading correct, a token altered where the
engine produces it reading not correct, and a cell, mix and metric added as
files alone."""
import json
import time

import numpy as np
import pytest

from bench_tiny import CPU_DEVICE, PEAKS, make_root

from bench import spec
from bench.run import run_cell


def _run(root, name, seed=123456789012, trace=False):
    cell = spec.cell(name, root)
    return run_cell(cell, seed, 2.0, trace, CPU_DEVICE, PEAKS, time.perf_counter(),
                    log=lambda m: None)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("bench"))


# the widest served gap of each toy cell at _run's seed, as the dense
# reference read it before configurations could name an architecture
# module: the dense default reads the same
GAPS = {"toy-qwen.chat": 0.002255469560623169, "toy-sc.code": 0.009819746017456055}


@pytest.mark.parametrize("name", ["toy-qwen.chat", "toy-sc.code"])
def test_sound_run_is_correct(root, name):
    r = _run(root, name)
    assert r["correct"] is True
    assert r["failed"] == 0 and r["attempted"] == 16
    assert set(r["metrics"]) == {"tok_s", "ttft_p90_ms", "tpot_p90_ms", "setup_s"}
    assert all(v["value"] > 0 for v in r["metrics"].values())
    assert list(r)[-1] == "checked"
    assert r["checked"]["logit_gap"]["value"] <= r["checked"]["logit_gap"]["limit"]
    assert r["checked"]["logit_gap"]["value"] == GAPS[name]
    json.dumps(r)


def test_altered_token_is_not_correct(root, monkeypatch):
    from repro.launch.engine import Engine

    orig = Engine._decode_chunk
    state = {"n": 0}

    def altered(self):
        toks, emitted, *rest = orig(self)
        state["n"] += 1
        if state["n"] == 3:
            toks = np.array(toks)
            row = int(np.argmax(np.asarray(emitted).any(axis=1)))
            col = int(np.argmax(np.asarray(emitted)[row]))
            toks[row, col] = (toks[row, col] + 1) % self.cfg.vocab
        return (toks, emitted, *rest)

    monkeypatch.setattr(Engine, "_decode_chunk", altered)
    r = _run(root, "toy-qwen.chat")
    assert r["correct"] is False
    assert r["checked"]["logit_gap"]["value"] > r["checked"]["logit_gap"]["limit"]


def test_cell_mix_and_metric_added_as_files(root):
    b = json.loads((root / "BENCHMARK.json").read_text())
    mix = json.loads((root / "bench" / "traffic" / "chat.json").read_text())
    mix["output"] = dict(mix["output"], max=6)
    (root / "bench" / "traffic" / "chat-short.json").write_text(json.dumps(mix))
    (root / "bench" / "limits" / "toy-qwen.chat-short.json").write_text(
        (root / "bench" / "limits" / "toy-qwen.chat.json").read_text())
    (root / "bench" / "metrics" / "requests_seen.py").write_text(
        "def read(ctx):\n    return float(len(ctx['completions']))\n")
    b["workloads"].append({"name": "toy-qwen.chat-short", "config": "toy-qwen",
                           "traffic": "chat-short", "chips": 1, "why": "toy"})
    b["per_layer"].append({"name": "requests_seen", "unit": "1", "better": "higher",
                           "source": "program_counter", "layer": "scheduler",
                           "moves": "tok_s", "workloads": ["toy-qwen.chat-short"]})
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cell = spec.cell("toy-qwen.chat-short", root)
    assert "requests_seen" in [m["name"] for m in cell["per_layer"]]
    assert "requests_seen" not in [m["name"] for m in spec.cell("toy-qwen.chat", root)["per_layer"]]
    r = _run(root, "toy-qwen.chat-short", trace=True)
    assert r["metrics"]["requests_seen"]["value"] == 16.0
    # no device trace on CPU: the trace readers find nothing and are left out
    assert "device_idle" not in r["metrics"]
    assert r["correct"] is True

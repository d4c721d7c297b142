"""A cell whose configuration names a mesh, on the (data 1, model 4) mesh of
the four host devices (tests/conftest.py): the weights drawn straight onto
the shardings the engine serves them from, and equal bit for bit to the
one-device draw; the engine serving on that mesh in a whole run that reads
correct; a mesh whose size is not the cell's chips refused before anything
is drawn; and shares of a roofline or of the peak taken over all the mesh's
chips."""
import json
import time
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from bench_tiny import CPU_DEVICE, MESH, PEAKS, make_root
from test_bench_trace import HAND

from bench import harness, spec, weights
from bench.run import read_per_layer, run_cell
from repro.launch.engine import Engine

NAME = "toy-sc-tp4.code"
SEED = 2**33 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("mesh"))


@pytest.fixture(scope="module")
def drawn(root):
    cell = spec.cell(NAME, root)
    cfg = harness.program_config(cell["config"])
    mesh = harness.program_mesh(cell["config"])
    return cell, cfg, mesh, harness.program_weights(cfg, SEED, mesh)


def _leaves(tree):
    return jax.tree_util.tree_leaves_with_path(tree)


def test_weights_land_on_every_device_of_the_mesh(drawn):
    _, cfg, mesh, params = drawn
    assert dict(mesh.shape) == MESH
    four = set(jax.devices()[:4])
    for path, leaf in _leaves(params):
        assert leaf.sharding.device_set == four, jax.tree_util.keystr(path)
    # wq is (layers, d, heads, head_dim): a quarter of the heads on each device
    wq = params["layers"]["attn"]["wq"]
    h = cfg.n_heads // 4
    assert {s.data.shape for s in wq.addressable_shards} == {
        (cfg.n_layers, cfg.d_model, h, cfg.d_head)}
    assert sorted(s.index[2].start for s in wq.addressable_shards) == [0, h, 2 * h, 3 * h]
    # one KV head on each device: kv_heads really shard over model
    wk = params["layers"]["attn"]["wk"]
    assert {s.data.shape[2] for s in wk.addressable_shards} == {cfg.n_kv_heads // 4}


def test_engine_keeps_the_drawn_shardings(drawn):
    cell, cfg, mesh, params = drawn
    e = cell["config"]["engine"]
    eng = Engine(params, cfg, num_slots=e["num_slots"], cache_len=e["cache_len"],
                 chunk=e["chunk"], mesh=mesh)
    for (path, mine), (_, theirs) in zip(_leaves(params), _leaves(eng.params)):
        assert theirs.sharding == mine.sharding, jax.tree_util.keystr(path)
        # the engine's placement moved nothing: it holds the drawn arrays
        assert theirs is mine, jax.tree_util.keystr(path)


def test_sharded_draw_equals_the_one_device_draw(drawn):
    _, cfg, _, params = drawn
    one = harness.program_weights(cfg, SEED)
    assert all(len(leaf.sharding.device_set) == 1 for leaf in jax.tree.leaves(one))
    for (path, a), (_, b) in zip(_leaves(params), _leaves(one)):
        x, y = np.asarray(a), np.asarray(b)
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(x.view(np.uint16), y.view(np.uint16),
                                      err_msg=jax.tree_util.keystr(path))


def test_run_serves_on_the_mesh_and_is_correct(root, monkeypatch):
    meshes = []
    init = Engine.__init__

    def spy(self, *a, **k):
        meshes.append(k.get("mesh"))
        init(self, *a, **k)

    monkeypatch.setattr(Engine, "__init__", spy)
    r = run_cell(spec.cell(NAME, root), SEED, 2.0, False, CPU_DEVICE, PEAKS,
                 time.perf_counter(), log=lambda m: None)
    assert [dict(m.shape) for m in meshes] == [MESH]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] == 16
    assert r["checked"]["logit_gap"]["value"] <= r["checked"]["logit_gap"]["limit"]


def test_mesh_that_is_not_the_cells_chips_is_refused(tmp_path, monkeypatch):
    root = make_root(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    next(w for w in b["workloads"] if w["name"] == NAME)["chips"] = 1
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    drawn = []
    monkeypatch.setattr(weights, "program_params", lambda *a, **k: drawn.append(a))
    with pytest.raises(ValueError, match="spans 4 devices, the cell asks for 1 chips"):
        spec.cell(NAME, root)
    assert drawn == []


def _served():
    """HAND's trace (an admission of 16 tokens, decode chunks 4 and 5 of 4
    steps) and one request admitted in it that served 9 tokens."""
    done = {0: SimpleNamespace(uid=0, prompt_len=16, tokens=list(range(9)), status="ok",
                               arrival_s=0.0, admitted_s=0.1, first_token_s=0.2)}
    return {"completions": done, "records": [], "trace_data": HAND,
            "trace_opened_s": None}, {0: (0.2, 4)}


def test_shares_on_four_chips_read_a_quarter(root):
    four = spec.cell(NAME, root)
    engine = {k: v for k, v in four["config"]["engine"].items() if k != "mesh"}
    one = dict(four, config=dict(four["config"], engine=engine))
    served, first = _served()
    r1 = read_per_layer(one, served, first, PEAKS)
    r4 = read_per_layer(four, served, first, PEAKS)
    shares = ("prefill_roofline", "decode_roofline", "serve_mfu")
    assert set(shares) <= set(r1) and set(r1) == set(r4)
    for name in r1:
        want = r1[name]["value"] / 4 if name in shares else r1[name]["value"]
        assert r4[name]["value"] == pytest.approx(want, rel=1e-12), name

"""Pure-python unit tests for the logical-axis sharding machinery."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.constraints import logical_to_spec
from repro.distributed.sharding import divisible_spec, serve_rules, train_rules


@pytest.fixture
def mesh():
    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("data", "model"))


class TestLogicalToSpec:
    RULES = {"embed": ("pod", "data"), "heads": "model", "mlp": "model", "batch": ("data",)}

    def test_basic_mapping(self):
        assert logical_to_spec(("embed", "heads", None), self.RULES) == P(
            ("pod", "data"), "model", None
        )

    def test_axis_claimed_once(self):
        # second claimant of 'model' degrades to replication
        spec = logical_to_spec(("heads", "mlp"), self.RULES)
        assert spec == P("model", None)

    def test_unknown_axis_replicates(self):
        assert logical_to_spec(("nope", None), self.RULES) == P(None, None)


class TestDivisibleSpec:
    def _mesh(self, shape=(4, 8), axes=("data", "model")):
        n = int(np.prod(shape))
        dev = np.asarray([jax.devices()[0]] * n).reshape(shape)
        return Mesh(dev, axes)

    def test_indivisible_dim_replicates(self):
        mesh = self._mesh()
        spec = divisible_spec(P("model", None), (10, 3), mesh)  # 10 % 8 != 0
        assert spec == P(None, None)

    def test_divisible_dim_kept(self):
        mesh = self._mesh()
        assert divisible_spec(P("model", None), (16, 3), mesh) == P("model", None)

    def test_tuple_axes_partial_keep(self):
        mesh = self._mesh()
        # 8 divides by data(4) but then not by model(8): keep only data
        spec = divisible_spec(P(("data", "model"), None), (8, 3), mesh)
        assert spec == P("data", None)


class TestRuleTables:
    def _mesh(self, shape=(16, 16), axes=("data", "model")):
        dev = np.asarray([jax.devices()[0]] * int(np.prod(shape))).reshape(shape)
        return Mesh(dev, axes)

    def test_train_rules_fsdp_tp(self):
        cfg = get_config("qwen3-4b")
        r = train_rules(cfg, self._mesh())
        assert r["embed"] == ("data",) and r["heads"] == "model"
        assert r["batch"] == ("data",)

    def test_train_rules_moe_ep(self):
        cfg = get_config("qwen3-moe-235b-a22b")
        r = train_rules(cfg, self._mesh())
        assert r["expert"] == "model"  # 128 % 16 == 0
        cfg2 = get_config("mixtral-8x22b")
        r2 = train_rules(cfg2, self._mesh())
        assert r2["expert"] is None  # 8 % 16 != 0 -> replicate experts

    def test_serve_rules_never_shard_kv_seq(self):
        for arch in ("qwen3-4b", "deepseek-67b", "gemma3-1b"):
            r = serve_rules(get_config(arch), self._mesh())
            assert r["kv_seq"] is None  # the DUS-on-sharded-dim trap (§Perf)

    def test_serve_rules_kv_mesh(self):
        cfg = get_config("deepseek-67b")
        mesh = self._mesh((16, 8, 2), ("data", "kv", "qg"))
        r = serve_rules(cfg, mesh)
        assert r["kv_heads"] == "kv"
        assert r["heads"] == ("kv", "qg")

    def test_seq_parallel_toggles_seq(self):
        cfg = get_config("qwen3-4b")
        assert train_rules(cfg, self._mesh())["seq"] is None
        assert train_rules(cfg, self._mesh(), seq_parallel=True)["seq"] == "model"


class TestServeAttentionLayout:
    """The serving attention block groups queries per kv head wherever the
    serve rules split (kv, g) over as many devices as the flat h."""

    def _mesh(self, shape, axes):
        dev = np.asarray([jax.devices()[0]] * int(np.prod(shape))).reshape(shape)
        return Mesh(dev, axes)

    def test_shard_count_outside_rules(self):
        from repro.distributed.constraints import shard_count

        assert shard_count(("heads",), (48,)) == 1

    @pytest.mark.parametrize("name,shape,axes,grouped", [
        ("qwen3-4b", (4, 4), ("data", "model"), True),  # kv 8 over 4
        ("qwen3-4b", (16, 16), ("data", "model"), False),  # 8 x 4, h 32 over 16
        ("starcoder2-15b", (16, 16), ("data", "model"), False),  # 4 x 12, h 48
        ("deepseek-67b", (16, 16), ("data", "model"), False),  # 8 x 8, h 64
        ("deepseek-67b", (16, 8, 2), ("data", "kv", "qg"), True),  # kv x qg mesh
        ("gemma3-1b", (4, 4), ("data", "model"), True),  # 1 x 4 over 4
    ])
    def test_layout_under_serve_rules(self, name, shape, axes, grouped):
        from repro.distributed.constraints import axis_rules, shard_count
        from repro.layers.attention import _serve_grouped

        cfg = get_config(name)
        h, kv = cfg.n_heads, cfg.n_kv_heads
        mesh = self._mesh(shape, axes)
        with axis_rules(mesh, serve_rules(cfg, mesh)):
            flat = shard_count(("heads",), (h,))
            assert _serve_grouped(h, kv) is grouped
            if grouped:  # the scores shard as far as the flat layout's
                assert shard_count(("kv_heads", "heads"), (kv, h // kv)) == flat
        assert _serve_grouped(h, kv)  # one device: every GQA config groups

    def test_mha_stays_flat(self):
        from repro.layers.attention import _serve_grouped

        assert not _serve_grouped(32, 32)

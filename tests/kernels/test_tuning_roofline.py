"""Roofline-seeded autotune: cache JSON roundtrip, stale-entry invalidation
on TilingSpec change, sweep narrowing via the admissible plan, the occupancy
floor, and the pinned no-more-block-8 rmsnorm regression."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch, tuning


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    path = tmp_path / "kernel_tune.json"
    monkeypatch.setenv(tuning.ENV_CACHE, str(path))
    monkeypatch.delenv(tuning.ENV_AUTOTUNE, raising=False)
    return path


def _rmsnorm_args(rows, width):
    x = jnp.asarray(np.random.default_rng(0).normal(size=(rows, width)), jnp.float32)
    g = jnp.ones((width,), jnp.float32)
    return (x, g)


class TestCacheRoundtrip:
    def test_record_then_lookup_through_json(self, cache):
        key = tuning.problem_key("rmsnorm", _rmsnorm_args(64, 256), True)
        tuning.record(key, (16,), {"[16]": 12.5})
        # the entry really went through the on-disk JSON, not just memory
        on_disk = json.loads(cache.read_text())
        assert on_disk["version"] == tuning.CACHE_VERSION
        assert on_disk["entries"][key]["block"] == [16]
        assert on_disk["entries"][key]["timings_us"]["[16]"] == 12.5
        # cold re-read: wipe the in-memory mirror and resolve from disk
        tuning._mem.pop(str(cache), None)
        assert tuning.lookup(key, [(8,), (16,), (32,)]) == (16,)

    def test_stale_entry_invalidated_on_tilingspec_change(self, cache):
        """A cached block that a revised TilingSpec no longer offers must be
        ignored (lookup validates against the live candidate list)."""
        key = tuning.problem_key("rmsnorm", _rmsnorm_args(64, 256), True)
        tuning.record(key, (16,), {})
        assert tuning.lookup(key, [(8,), (16,)]) == (16,)
        assert tuning.lookup(key, [(8,), (32,)]) is None  # (16,) retired

    def test_choose_block_prefers_cache_hit_over_prior(self, cache):
        args = _rmsnorm_args(512, 1024)
        key = tuning.problem_key("rmsnorm", args, True)
        tuning.record(key, (64,), {})
        block = tuning.choose_block(
            "rmsnorm", [(8,), (64,), (512,)], (8,), lambda b: None, args,
            interpret=True,
        )
        assert block == (64,)


class TestRooflinePrior:
    def test_occupancy_floor_rejects_overhead_bound_tiles(self):
        """On a big rmsnorm problem, tiny blocks spend their time in grid-step
        launch overhead and must fall below OCC_FLOOR."""
        from repro.core.hw_model import chip_for_backend

        geom = tuning.tile_geometry(_rmsnorm_args(512, 1024))
        chip = chip_for_backend(True)
        _, occ_small, _ = tuning.predict_block_time((8,), geom, chip)
        _, occ_big, _ = tuning.predict_block_time((512,), geom, chip)
        assert occ_small < tuning.OCC_FLOOR < occ_big

    def test_plan_narrows_to_admissible(self):
        spec = dispatch.get("rmsnorm")
        prior, admissible = tuning.roofline_plan(
            spec.tiling.candidates, spec.tiling.default,
            _rmsnorm_args(512, 1024), interpret=True,
        )
        assert len(admissible) < len(spec.tiling.candidates)
        assert prior in admissible
        assert all(c in tuple(tuple(x) for x in spec.tiling.candidates)
                   for c in admissible)

    def test_tiny_input_keeps_tilingspec_default(self):
        """Every candidate is overhead-bound on a (5, 256) input; ties break
        toward the smallest block, keeping the TilingSpec default pick."""
        spec = dispatch.get("rmsnorm")
        prior, admissible = tuning.roofline_plan(
            spec.tiling.candidates, spec.tiling.default,
            _rmsnorm_args(5, 256), interpret=True,
        )
        assert prior == tuple(spec.tiling.default)
        assert len(admissible) <= tuning._NARROW_TOP

    def test_kmeans_tile_cap_keeps_memory_contract(self):
        """The kmeans geometry caps the tile at a fraction of the input: a
        whole-input tile would re-materialize the (N, K, 3) working set the
        kernel exists to avoid (pinned in test_kmeans_kernel's HLO check)."""
        spec = dispatch.get("kmeans_assign")
        px = jnp.zeros((2048, 3), jnp.float32)
        cent = jnp.zeros((5, 3), jnp.float32)
        prior, admissible = tuning.roofline_plan(
            spec.tiling.candidates, spec.tiling.default, (px, cent),
            interpret=True, geometry=spec.tiling.geometry,
        )
        assert prior[0] < 2048
        assert all(c[0] <= 2048 // 4 for c in admissible)

    def test_chip_constants_keyed_by_device_kind(self):
        """A compiled backend takes the constants of the device JAX runs on;
        a device with none is an error, never the v5e by default."""
        from repro.core import hw_model

        assert hw_model.chip_for_kind("TPU v5 lite") is hw_model.TPU_V5E
        with pytest.raises(ValueError, match="no chip constants"):
            hw_model.chip_for_kind("cpu")
        with pytest.raises(ValueError, match="no chip constants"):
            tuning.roofline_plan([(8,), (16,)], (8,), _rmsnorm_args(64, 256),
                                 interpret=False)

    def test_vmem_check_counts_both_pipeline_buffers(self):
        """A tile fits VMEM only if every stream's two pipeline buffers do:
        adam's registered 7 streams refuse a tile that 2 streams admit."""
        from repro.core.hw_model import TPU_V5E

        spec = dispatch.get("adam")
        p = jnp.zeros((2560 * 9728,), jnp.float32)
        geom = spec.tiling.geometry((p, p, p, p))
        assert geom["streams"] == 7
        _, _, ok_adam = tuning.predict_block_time((256,), geom, TPU_V5E)
        _, _, ok_two = tuning.predict_block_time(
            (256,), {**geom, "streams": 2, "vmem_tiles": 4}, TPU_V5E)
        assert ok_two and not ok_adam

    def test_modeling_failure_falls_back_to_blind_grid(self):
        prior, admissible = tuning.roofline_plan(
            [(8,), (16,)], (8,), ("not", "arrays"), interpret=True,
        )
        assert prior == (8,)
        assert admissible == ((8,), (16,))

    def test_rmsnorm_pick_no_longer_block_8(self, cache):
        """Pinned regression for the degenerate block-8 pick: the bench-shape
        rmsnorm (512, 1024) must resolve to a tile that amortizes grid-step
        overhead, without any sweep."""
        spec = dispatch.get("rmsnorm")
        block = tuning.choose_block(
            "rmsnorm", spec.tiling.candidates, spec.tiling.default,
            lambda b: None, _rmsnorm_args(512, 1024), interpret=True,
        )
        assert block != (8,)
        assert block[0] >= 128


class TestSweepNarrowing:
    def test_sweep_only_times_admissible_candidates(self, cache):
        """tune=True sweeps the roofline-admissible set, not the blind grid:
        the run callable fires once per admissible candidate (plus one warmup
        each), never len(candidates) times."""
        spec = dispatch.get("rmsnorm")
        args = _rmsnorm_args(512, 1024)
        _, admissible = tuning.roofline_plan(
            spec.tiling.candidates, spec.tiling.default, args, interpret=True,
        )
        timed = []

        def run(block):
            timed.append(tuple(block))
            return jnp.zeros(())

        block = tuning.choose_block(
            "rmsnorm", spec.tiling.candidates, spec.tiling.default, run, args,
            interpret=True, tune=True,
        )
        assert set(timed) == set(admissible)
        assert block in admissible
        # the winner was persisted for the next call
        key = tuning.problem_key("rmsnorm", args, True)
        assert tuning.lookup(key, spec.tiling.candidates) == block

    def test_sweep_failure_falls_back_to_prior(self, cache):
        spec = dispatch.get("rmsnorm")
        args = _rmsnorm_args(512, 1024)
        prior, _ = tuning.roofline_plan(
            spec.tiling.candidates, spec.tiling.default, args, interpret=True,
        )

        def boom(block):
            raise RuntimeError("no backend")

        block = tuning.choose_block(
            "rmsnorm", spec.tiling.candidates, spec.tiling.default, boom, args,
            interpret=True, tune=True,
        )
        assert block == prior
        if cache.exists():  # no bogus winner persisted
            assert not json.loads(cache.read_text())["entries"]

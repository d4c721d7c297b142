"""Serve driver: fast path vs loop baseline agreement, timing stats shape,
and argument validation."""
import numpy as np
import pytest

from repro.launch.serve import generate


def test_scan_and_loop_modes_token_identical():
    kw = dict(batch=2, prompt_len=6, gen_len=5, reps=1, verbose=False)
    toks_loop, stats_loop = generate("qwen3-4b", mode="loop", **kw)
    toks_scan, stats_scan = generate("qwen3-4b", mode="scan", **kw)
    np.testing.assert_array_equal(toks_loop, toks_scan)
    assert toks_scan.shape == (2, 11)
    for stats in (stats_loop, stats_scan):
        assert stats["prefill_ms"] > 0
        assert stats["decode_tok_s"] > 0
        assert stats["decode_ms_per_token"] > 0


def test_quantized_kv_scan_path_runs():
    toks, stats = generate("qwen3-4b", batch=2, prompt_len=4, gen_len=4,
                           quantized_kv=True, reps=1, verbose=False)
    assert toks.shape == (2, 8)
    assert stats["mode"] == "scan"


def test_prompt_len_zero_raises():
    with pytest.raises(ValueError, match="prompt_len must be >= 1"):
        generate("qwen3-4b", prompt_len=0, gen_len=2, verbose=False)


def test_unknown_mode_raises():
    with pytest.raises(ValueError, match="mode"):
        generate("qwen3-4b", mode="beam", verbose=False)


def test_mesh_scan_matches_loop_token_exact():
    """The sharded fast path (exact serving rules on a (2,2) mesh) emits the
    same greedy tokens as the single-device per-token loop."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs 4 host devices (tests/conftest.py forces them)")
    from repro.configs import get_smoke_config
    from repro.distributed.sharding import serve_rules
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(shape=(2, 2))
    rules = serve_rules(get_smoke_config("qwen3-4b"), mesh, replicate_params=True)
    kw = dict(batch=2, prompt_len=6, gen_len=5, reps=1, verbose=False)
    toks_loop, _ = generate("qwen3-4b", mode="loop", **kw)
    toks_mesh, stats = generate("qwen3-4b", mode="scan", mesh=mesh, rules=rules, **kw)
    np.testing.assert_array_equal(toks_loop, toks_mesh)
    assert stats["decode_tok_s"] > 0


def test_mesh_rejects_loop_mode():
    with pytest.raises(ValueError, match="scan"):
        generate("qwen3-4b", mode="loop", mesh=object(), verbose=False)


def test_published_widths_flag_serves_the_published_config(monkeypatch, capsys):
    """`--published-widths` asks the registry for the published config (a
    toy one stands in for it here) and sets up the compile cache first."""
    import sys

    from repro.configs import get_smoke_config
    from repro.launch import serve

    calls = []
    monkeypatch.setattr(serve, "get_config", lambda arch, **kw: calls.append(
        ("published", arch)) or get_smoke_config(arch, **kw))
    monkeypatch.setattr(serve, "use_compile_cache", lambda: calls.append("cache"))
    monkeypatch.setattr(sys, "argv", ["serve", "--published-widths", "--batch", "1",
                                      "--prompt-len", "3", "--gen-len", "2"])
    serve.main()
    assert calls == ["cache", ("published", "qwen3-4b")]
    assert "[serve] qwen3-4b" in capsys.readouterr().out

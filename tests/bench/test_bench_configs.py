"""The configuration files resolve to the program's configs at the published
widths, and a width that differs from the program is refused."""
import json

import jax
import pytest

from bench_tiny import REPO

from bench import harness
from repro.models import lm

COUNTS = {"qwen3-4b": 4.02e9}


def _config(name):
    return json.loads((REPO / "bench" / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", sorted(COUNTS))
def test_config_resolves_to_published_params(name):
    cfg = harness.program_config(_config(name))
    assert cfg.sqrt_unit == "e2afs"
    shapes, _ = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    n = sum(x.size for x in jax.tree.leaves(shapes))
    assert n == pytest.approx(COUNTS[name], rel=5e-3)
    assert all(x.dtype == jax.numpy.bfloat16 for x in jax.tree.leaves(shapes))


def test_qwen_ties_and_starcoder_windows():
    q = harness.program_config(_config("qwen3-4b"))
    assert q.tie_embeddings and q.qk_norm and q.norm == "rmsnorm"
    # a configuration that sets a sliding window runs the program's window blocks
    c = _config("qwen3-4b")
    c["model"]["sliding_window"] = 1024
    c["program"]["overrides"].update(window=1024, block_pattern=["window"])
    s = harness.program_config(c)
    assert s.window == 1024 and set(s.blocks) == {"window"}


def test_width_that_differs_is_refused():
    c = _config("qwen3-4b")
    c["model"]["intermediate_size"] = 9729
    with pytest.raises(ValueError, match="intermediate_size"):
        harness.program_config(c)


def test_use_bias_the_program_lacks_is_refused():
    """The program's tree has no attention biases, so a model block that
    sets ``use_bias`` (as StarCoder2's published config does) is refused by
    name, and one that says false passes."""
    c = _config("qwen3-4b")
    c["model"]["use_bias"] = True
    with pytest.raises(ValueError, match="use_bias=True.*has no layers/attn/bq"):
        harness.program_config(c)
    c["model"]["use_bias"] = False
    harness.program_config(c)

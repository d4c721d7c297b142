"""First-token time from the chunk clock, checked against ``Completion``
through ``Engine.run`` on a smoke config: a request's first token reaches
the host at the first chunk boundary after its admission, and it then emits
one token per step, ``chunk`` to a boundary, until its last."""
import math

import jax
import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401

from bench import harness
from repro.configs import get_smoke_config
from repro.launch.engine import Engine, Request
from repro.models import lm

CHUNK = 4


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    clock = harness.chunk_clock()
    eng = Engine(params, cfg, num_slots=3, cache_len=64, chunk=CHUNK, telemetry=clock)
    rng = np.random.default_rng(0)
    lens = [1, 2, 5, 9, 4, 13, 1, 7, 3]
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 6 + i % 3).astype(np.int32),
                    max_new_tokens=n, arrival_s=0.02 * i) for i, n in enumerate(lens)]
    eng.warmup({6, 7, 8})
    clock.records.clear()
    done = eng.run(reqs)
    return done, clock.records


def test_clock_records_every_chunk(served):
    done, records = served
    chunks = [r[1] for r in records]
    assert chunks == list(range(chunks[0], chunks[0] + len(chunks)))
    assert sum(r[2] for r in records) == sum(len(c.tokens) for c in done.values())


def test_first_token_boundary_matches_completion(served):
    done, records = served
    first = harness.first_token_times(done, records)
    by_chunk = {r[1]: r[0] for r in records}
    assert set(first) == set(done)
    for uid, c in done.items():
        t, c0 = first[uid]
        assert c.admitted_s < t <= c.finished_s
        # the last token lands ceil(n / chunk) - 1 chunks after the first
        assert by_chunk[c0 + math.ceil(len(c.tokens) / CHUNK) - 1] == c.finished_s
        if len(c.tokens) == 1:
            assert t == c.finished_s


def test_end_to_end_metrics_from_the_clock(served):
    done, records = served
    window = records[len(records) // 2][0]
    m = harness.end_to_end(done, harness.first_token_times(done, records), records, window)
    assert m["tok_s"] == pytest.approx(
        sum(n for t, _, n in records if t <= window) / window)
    assert 0 < m["ttft_p90_ms"] < math.inf and 0 < m["tpot_p90_ms"] < math.inf

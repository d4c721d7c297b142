"""Host-time accounting of a serve loop (launch/telemetry.py HostSpans):
nested spans count their own time once, garbage collection comes off the
span it interrupted, and Engine.run reports every span, journal and
snapshot included."""
import gc
import time

import jax
import numpy as np

from repro.configs import get_smoke_config
from repro.launch.engine import HOST_SPANS, Engine, Request
from repro.launch.telemetry import HostSpans
from repro.models import lm


def test_nested_spans_count_their_own_time():
    s = HostSpans()
    with s.span("run") as t0:
        time.sleep(0.01)
        with s.span("admit", 16) as ta:
            time.sleep(0.02)
            with s.span("journal"):
                time.sleep(0.01)
        admit_len = time.perf_counter() - ta
        with s.span("journal"):
            time.sleep(0.01)
    total = time.perf_counter() - t0
    assert s.seconds["admit"] >= 0.02 and s.seconds["journal"] >= 0.02
    assert s.seconds["run"] >= 0.01
    # a span's nested time is not its own
    assert s.seconds["admit"] < admit_len - 0.0099
    assert s.seconds["run"] < total - 0.0399
    # every second inside the outermost span counts once
    assert total - 0.05 < sum(s.seconds.values()) <= total


def test_gc_comes_off_the_span_it_interrupted():
    s = HostSpans()
    junk = [[i] for i in range(200_000)]
    with s.collect_gc():
        with s.span("bookkeeping") as t0:
            gc.collect()
        total = time.perf_counter() - t0
    del junk
    assert s.seconds["gc"] > 0.0
    assert total - 0.05 < s.seconds["bookkeeping"] + s.seconds["gc"] <= total
    # outside collect_gc nothing is counted
    before = s.seconds["gc"]
    gc.collect()
    assert s.seconds["gc"] == before and s._on_gc not in gc.callbacks


def test_engine_reports_journal_and_snapshot_time(tmp_path):
    cfg = get_smoke_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    eng = Engine(params, cfg, num_slots=2, cache_len=32, chunk=2,
                 journal=tmp_path / "journal.jsonl", snapshot_dir=tmp_path / "snap",
                 snapshot_every_chunks=2)
    rng = np.random.default_rng(0)
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 5).astype(np.int32),
                    max_new_tokens=4) for i in range(3)]
    done = eng.run(reqs)
    assert all(c.status == "ok" for c in done.values())
    st = eng.stats
    assert st["host_journal_s"] > 0.0 and st["host_snapshot_s"] > 0.0
    assert sum(st[f"host_{k}_s"] for k in HOST_SPANS) <= st["makespan_s"]
    # the run's collector hook is gone once it ends
    assert eng._spans._on_gc not in gc.callbacks

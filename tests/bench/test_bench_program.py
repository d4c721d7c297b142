"""What the program itself tells the benchmark, checked through ``Engine.run``
on a smoke config: the first-token time on ``Completion``, the host-time
stats of a run, the stable program names the device-trace readers match,
and the named scopes in the compiled decode chunk."""
import re

import jax
import numpy as np
import pytest

from bench_tiny import REPO  # noqa: F401

from bench import harness
from repro.configs import get_smoke_config
from repro.launch.engine import HOST_SPANS, Engine, Request
from repro.models import lm


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.PRNGKey(0))
    clock = harness.chunk_clock()
    eng = Engine(params, cfg, num_slots=3, cache_len=64, chunk=4, telemetry=clock)
    rng = np.random.default_rng(1)
    lens = [3, 1, 6, 9, 2, 5, 1, 7]
    reqs = [Request(uid=i, prompt=rng.integers(0, cfg.vocab, 6 + i % 2).astype(np.int32),
                    max_new_tokens=n, arrival_s=0.03 * i) for i, n in enumerate(lens)]
    # one request that is never admitted: it expires in the queue
    reqs.append(Request(uid=99, prompt=np.ones(6, np.int32), max_new_tokens=2,
                        arrival_s=0.0, deadline_s=1e-9))
    eng.warmup({6, 7})
    clock.records.clear()
    done = eng.run(reqs)
    return eng, done, clock.records


def test_first_token_time_is_the_chunk_clocks(served):
    _, done, records = served
    first = harness.first_token_times(done, records)
    served_uids = {u for u, c in done.items() if len(c.tokens)}
    assert set(first) == served_uids and len(served_uids) == 8
    for uid, c in done.items():
        if uid in first:
            assert c.first_token_s == first[uid][0]
            assert c.admitted_s < c.first_token_s <= c.finished_s
        else:
            assert c.first_token_s == -1.0


def test_host_stats_cover_the_run_once(served):
    eng, _, records = served
    st = eng.stats
    keys = [f"host_{k}_s" for k in HOST_SPANS]
    assert all(isinstance(st[k], float) and st[k] >= 0.0 for k in keys)
    assert sum(st[k] for k in keys) <= st["makespan_s"]
    # the spans leave little of the run unaccounted for
    assert sum(st[k] for k in keys) > 0.9 * st["makespan_s"]
    for k in ("host_admit_s", "host_decode_dispatch_s", "host_decode_sync_s",
              "host_bookkeeping_s", "host_telemetry_s"):
        assert st[k] > 0.0, k
    assert st["host_snapshot_s"] == 0.0 and st["host_journal_s"] == 0.0
    assert 0.0 < st["longest_turn_s"] < st["makespan_s"]
    chunks = [r[1] for r in records]
    assert chunks[0] <= st["longest_turn_chunk"] < chunks[-1]


def _decode_args(eng):
    return (eng.params, eng._cache, eng._tok, eng._pos, eng._active,
            eng._remaining, eng._keys)


def test_programs_carry_their_stable_names(served):
    """bench/metrics readers find the admit and decode programs in a device
    trace by these module names."""
    eng = served[0]
    decode = eng._decode_j.lower(*_decode_args(eng)).as_text()
    assert re.search(r"^module @jit_decode_fn\b", decode, re.M)
    admit = eng._admit_jits[0].lower(
        *_decode_args(eng), np.zeros((1, 6), np.int32), np.zeros(1, np.int32),
        np.ones(1, np.int32), np.zeros(1, np.int32)).as_text()
    assert re.search(r"^module @jit_admit_fn\b", admit, re.M)


def test_compiled_decode_chunk_carries_the_scopes(served):
    eng = served[0]
    cfg = eng.cfg
    hlo = eng._decode_j.lower(*_decode_args(eng)).compile().as_text()
    assert hlo.startswith("HloModule jit_decode_fn")
    scoped = {"decode_attention": [], "norm": []}
    for line in hlo.splitlines():
        m = re.search(r'op_name="jit\(decode_fn\)/([^"]*)"', line)
        if not m:
            continue
        path = m.group(1).split("/")
        inner = [p for p in path if p in scoped]
        if inner:
            scoped[inner[-1]].append(line.split(" = ")[1].split(" ")[0])
    assert scoped["decode_attention"] and scoped["norm"]
    b = eng.num_slots
    # the layer norms (over d_model) and the q and k norms (per head) all
    # run under the norm scope
    for width in (f"[{b},1,{cfg.d_model}]", f"[{b},1,{cfg.n_heads},",
                  f"[{b},1,{cfg.n_kv_heads},"):
        assert any(width in s for s in scoped["norm"]), width

"""Chaos suite: the engine under seeded fault schedules (docs/robustness.md).

Pins the three acceptance properties of the fault-tolerance layer:

* every request finishes with a structured status — ``run()`` never raises
  mid-batch under activation, datapath or dispatch faults;
* a quarantined request that degrades to the exact datapath reproduces the
  fault-free exact-path tokens bit-exactly;
* a zero-fault run with detectors enabled is token-exact against the solo
  parity reference (the detectors only add reductions, never perturb the
  decode carry).

Request traces and solo references ride the shared parity harness in
tests/models/parity.py (docs/testing.md).
"""
import dataclasses

import jax
import numpy as np
import parity
import pytest

from repro.configs import get_smoke_config
from repro.core import FaultConfig
from repro.core.faults import DispatchFault
from repro.launch.engine import STATUSES, Engine, solo_generate
from repro.models import lm


@pytest.fixture(scope="module")
def setup():
    cfg = get_smoke_config("qwen3-4b", sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0))
    return cfg, params


_requests = parity.random_requests
_fresh = parity.fresh


def _exact_solo(params, cfg, req, cache_len=24):
    """The fault-free exact-datapath reference a degraded request must hit."""
    return parity.solo_reference(
        params, lm.exact_twin(cfg), [req], cache_len=cache_len
    )[req.uid]


def test_zero_fault_detectors_token_exact(setup):
    """Detectors on, no faults: tokens bit-equal to the approximate-path solo
    reference (the pre-detector engine contract), all statuses 'ok', every
    fault counter zero."""
    cfg, params = setup
    reqs = _requests(cfg, 5)
    eng = Engine(params, cfg, num_slots=2, cache_len=24, chunk=3)
    assert eng.detectors
    done = eng.run(_fresh(reqs))
    assert set(done) == {r.uid for r in reqs}
    for r in reqs:
        c = done[r.uid]
        assert c.status == "ok" and c.trips == 0
        np.testing.assert_array_equal(
            c.tokens, solo_generate(params, cfg, r.prompt, r.max_new_tokens,
                                    cache_len=24)
        )
    s = eng.stats
    assert s["n_ok"] == 5 and s["faults_detected"] == 0
    assert s["exact_fallbacks"] == 0 and s["dispatch_faults"] == 0
    assert not s["deadline_expired"]


def test_logit_faults_degrade_to_exact_bit_exact(setup):
    """NaN activation injection: the detector latch trips every poisoned
    slot, the ladder lands on the exact datapath, and the degraded tokens
    are bit-exact vs the fault-free exact-path solo run."""
    cfg, params = setup
    reqs = _requests(cfg, 4)
    eng = Engine(
        params, cfg, num_slots=2, cache_len=24, chunk=3,
        faults=FaultConfig("logit_nan", rate=0.5, seed=1),
    )
    done = eng.run(_fresh(reqs))
    assert set(done) == {r.uid for r in reqs}
    degraded = [r for r in reqs if done[r.uid].status == "degraded"]
    assert degraded, "seeded schedule should trip at least one slot"
    for r in reqs:
        assert done[r.uid].status in ("ok", "degraded")
    for r in degraded:
        assert done[r.uid].trips >= 1
        np.testing.assert_array_equal(
            done[r.uid].tokens, _exact_solo(params, cfg, r)
        )
    assert eng.stats["faults_detected"] == eng.stats["exact_fallbacks"] == len(degraded)


def test_sqrt_exponent_faults_trip_sentinel(setup):
    """High-bit exponent flips in the rsqrt datapath blow up the logits;
    the magnitude sentinel / finiteness latch quarantines the slot and the
    exact fallback reproduces the clean exact tokens."""
    cfg, params = setup
    reqs = _requests(cfg, 3)
    eng = Engine(
        params, cfg, num_slots=2, cache_len=24, chunk=3,
        faults=FaultConfig("sqrt_exp", rate=0.3, seed=2, bit=7),
    )
    assert eng.cfg.sqrt_faults is not None  # schedule rides the serving cfg
    done = eng.run(_fresh(reqs))
    assert {done[r.uid].status for r in reqs} <= {"ok", "degraded"}
    assert any(done[r.uid].status == "degraded" for r in reqs)
    for r in reqs:
        if done[r.uid].status == "degraded":
            np.testing.assert_array_equal(
                done[r.uid].tokens, _exact_solo(params, cfg, r)
            )


def test_quarantine_retries_before_fallback(setup):
    """With retry budget, a tripped request gets fresh approximate-path
    attempts first; a value-deterministic fault schedule re-trips each one,
    so the trip count ends at retries+1 and the ladder still lands exact."""
    cfg, params = setup
    req = _requests(cfg, 1)[0]
    eng = Engine(
        params, cfg, num_slots=1, cache_len=24, chunk=3,
        faults=FaultConfig("logit_nan", rate=1.0, seed=3),
        quarantine_retries=2,
    )
    done = eng.run([dataclasses.replace(req)])
    c = done[req.uid]
    assert c.status == "degraded" and c.trips == 3
    assert eng.stats["quarantine_retries"] == 2
    assert eng.stats["faults_detected"] == 3 and eng.stats["exact_fallbacks"] == 1
    np.testing.assert_array_equal(c.tokens, _exact_solo(params, cfg, req))


def test_dispatch_faults_retried_transparently(setup):
    """Injected dispatch failures raise before the device call, so bounded
    retry-with-backoff serves the exact same tokens as a clean run."""
    cfg, params = setup
    reqs = _requests(cfg, 4)
    clean = Engine(params, cfg, num_slots=2, cache_len=24, chunk=3).run(_fresh(reqs))
    eng = Engine(
        params, cfg, num_slots=2, cache_len=24, chunk=3,
        faults=FaultConfig("dispatch", rate=0.4, seed=5),
    )
    done = eng.run(_fresh(reqs))
    for r in reqs:
        assert done[r.uid].status == "ok"
        np.testing.assert_array_equal(done[r.uid].tokens, clean[r.uid].tokens)
    assert eng.stats["dispatch_faults"] > 0
    assert eng.stats["dispatch_retries"] == eng.stats["dispatch_faults"]


def test_dispatch_fault_exhaustion_escalates(setup):
    """A dispatch schedule that never succeeds escalates as DispatchFault
    after the retry budget — with the donated pool buffers still intact
    (injection happens before the call, so reset()+run() recovers)."""
    cfg, params = setup
    req = _requests(cfg, 1)[0]
    eng = Engine(
        params, cfg, num_slots=1, cache_len=24, chunk=3,
        faults=FaultConfig("dispatch", rate=1.0, seed=0),
        max_dispatch_retries=2, dispatch_backoff_s=1e-4,
    )
    with pytest.raises(DispatchFault, match="max_dispatch_retries"):
        eng.run([dataclasses.replace(req)])


def test_seeded_schedule_replays_identically(setup):
    """The whole chaos run — statuses, trip counts, tokens, counters — is a
    pure function of the seed: reset() + rerun reproduces it bit-exactly."""
    cfg, params = setup
    reqs = _requests(cfg, 5)
    eng = Engine(
        params, cfg, num_slots=2, cache_len=24, chunk=3,
        faults=FaultConfig("logit_inf", rate=0.4, seed=7),
    )
    first = eng.run(_fresh(reqs))
    stats1 = {k: v for k, v in eng.stats.items() if not k.endswith("_s")}
    eng.reset()
    second = eng.run(_fresh(reqs))
    stats2 = {k: v for k, v in eng.stats.items() if not k.endswith("_s")}
    for r in reqs:
        assert first[r.uid].status == second[r.uid].status
        assert first[r.uid].trips == second[r.uid].trips
        np.testing.assert_array_equal(first[r.uid].tokens, second[r.uid].tokens)
    # timings, and where on the lifetime chunk count the longest host turn fell
    drop = ("makespan_s", "tok_s", "longest_turn_chunk")
    assert {k: v for k, v in stats1.items() if k not in drop} == {
        k: v for k, v in stats2.items() if k not in drop
    }


def test_failed_status_when_exact_path_unhealthy(setup):
    """If even the exact datapath yields non-finite logits (poisoned
    weights), the ladder bottoms out at status 'failed' — still a structured
    completion, not an exception."""
    cfg, params = setup
    bad_params = jax.tree.map(lambda p: p * np.nan, params)
    req = _requests(cfg, 1)[0]
    eng = Engine(bad_params, cfg, num_slots=1, cache_len=24, chunk=3)
    done = eng.run([dataclasses.replace(req)])
    c = done[req.uid]
    assert c.status == "failed" and len(c.tokens) == 0
    assert eng.stats["n_failed"] == 1 and eng.stats["exact_fallbacks"] == 1


def test_every_request_gets_a_structured_status(setup):
    """Mixed chaos — activation faults + per-request deadlines + more
    requests than slots: the status partition exactly covers the request
    set and the stats counters agree with it."""
    cfg, params = setup
    reqs = _requests(cfg, 6)
    reqs[4] = dataclasses.replace(reqs[4], deadline_s=1e-9)  # evicted at t=0
    eng = Engine(
        params, cfg, num_slots=2, cache_len=24, chunk=3,
        faults=FaultConfig("logit_nan", rate=0.3, seed=11),
    )
    done = eng.run(_fresh(reqs))
    assert set(done) == {r.uid for r in reqs}
    for c in done.values():
        assert c.status in STATUSES
    assert done[reqs[4].uid].status == "evicted"
    s = eng.stats
    assert sum(s[f"n_{st}"] for st in STATUSES) == len(reqs)
    assert s["n_requests"] == len(reqs)

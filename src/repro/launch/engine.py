"""Continuous-batching serving engine: slot-scheduled decode over a KV-cache
pool with per-request positions and ragged prefill.

The PR-3 fast path is lock-step — every request in a batch shares one prompt
length, decodes the same ``gen_len`` and finishes together, so mixed-length
traffic pays padding and idle-slot waste.  This engine breaks the lock step:

* a **slot pool** — one KV cache of ``num_slots`` batch rows, where each row
  is an independent request with its own position counter (``lm.decode_step``
  threads the (b,) position vector through RoPE, the ring-buffer write index
  and the validity mask);
* a **scheduler** that admits queued requests into freed slots mid-decode:
  ``lm.prefill_into_slots`` prefills the new prompt into staging rows and
  lands them in the *live donated* cache with whole-row writes (stale KV from
  the slot's previous occupant is cleared; positions past the prompt stay
  masked until the new occupant writes them);
* **chunked decode** — between admission points the pool advances by jitted
  ``lm.decode_slots_scan`` segments of ``chunk`` steps whose carry (cache,
  tok, pos, active, remaining) is donated, so the pool buffers are aliased
  across the whole serve loop;
* per-slot EOS / budget early-exit and per-slot PRNG sampling (greedy by
  default; ``temperature`` / ``top_k`` opt in).

Correctness anchor: a request decoded in a staggered slot emits tokens
bit-identical to a solo ``prefill`` + ``generate_scan`` run (greedy,
non-MoE) — the slot-parity suite in tests/models/test_engine_slots.py holds
every cache family (dense, ring, SSD, RG-LRU; float and int8) to it.

Prompts are prefilled at their exact length.  The scheduler admits one
request per dispatch (``lm.prefill_into_slots`` itself is batch-k, but a
fixed admit width of 1 keeps the compile set to one trace per prompt-length
bucket — draw lengths from a small bucket set, as ``engine_bench`` does, and
``warmup`` covers them all off the serving clock).

Fault tolerance (docs/robustness.md): with ``detectors=True`` (default) the
jitted decode chunk also reduces two per-slot health signals — a non-finite
logit latch and a max-|logit| sentinel — riding the chunk's existing single
host sync.  A tripped slot is quarantined: its request is re-queued for a
bounded number of approximate-path retries, then re-served solo on the
exact datapath (``lm.exact_twin``) — the approximate→exact degradation
ladder.  ``Engine.run`` never raises mid-batch: every request ends in a
:class:`Completion` with a structured ``status`` (``ok`` / ``degraded`` /
``evicted`` / ``failed``), deadlines (global and per-request) evict with
partial tokens, and injected dispatch failures (``faults=`` with
``site="dispatch"``) are retried with exponential backoff.

Crash consistency and overload (docs/robustness.md §Crash-consistent
serving): ``snapshot()`` serializes the COMPLETE live serving state — the
device pool (every cache family, float and int8, per-slot tok/pos/active/
remaining vectors and PRNG keys) plus host-side request metadata and the
pending queue — through ``checkpoint.save``'s atomic tmp→rename commit;
``snapshot_every_chunks=`` autosaves at the existing one-sync-per-chunk
boundary.  ``Engine.resume`` rebuilds the pool from the latest committed
snapshot (onto a *different* mesh shape if asked — the elastic resharding
path) and reconciles the write-ahead request journal (``journal=``, see
launch/journal.py) on top: requests journaled ``finished`` are never
re-served, accepted-but-unfinished requests missing from the snapshot are
replayed.  Greedy exact-mode tokens after a kill+resume are bit-exact vs an
uninterrupted run.  Overload is admission-controlled: ``max_queue=`` bounds
the due-request queue and a ``shed_policy`` (``reject-new`` /
``evict-latest-deadline`` / ``shed-by-slo``) picks what to drop (status
``rejected``) when traffic exceeds capacity.

Speculative decoding (docs/serving.md §Speculative decoding): ``spec=
SpecConfig(k=...)`` turns each decode chunk into ``chunk`` draft-and-verify
steps over the same slot pool — per step every active slot drafts ``k``
candidate tokens (self-drafting n-gram lookup over its own fed-token
history, or a small draft model via ``draft_model=``), ONE batched verify
forward scores all ``k+1`` positions through the target datapath, the
longest agreeing prefix commits and rejected rows roll the per-slot cache
write index back bit-for-bit.  Greedy speculative output is bit-identical
to non-speculative greedy by construction (attention-only decoder stacks,
dense/ring/int8 caches — tests/models/test_spec_decode.py), so speculation
composes with everything above: health detectors latch over committed rows
only, SLO canaries fire on row 0 (always an accepted position) and a
demoted slot decodes non-speculatively until promoted back, and snapshots
resume n-gram speculation by rebuilding the history from slot metadata.

Accuracy SLO (docs/robustness.md §Accuracy SLO): ``slo=AccuracySLO(...)``
makes the *silently* approximate datapath self-guarding — the detectors
above only fire on loud failures (non-finite, magnitude blow-up), but an
approximate sqrt unit can drift tokens off the exact output without ever
tripping one.  Every ``canary_stride``-th decode step the jitted chunk
recomputes that step's logits through the exact datapath from the same
cache read (a shadow, not a second dispatch) and reduces per-slot
divergence gauges onto the chunk's single host sync; a slot over its
argmax-divergence or relative-logit-error budget is demoted one rung down
a per-slot datapath ladder (e.g. ``e2afs → exact``) mid-request without
re-prefill, and promoted back after ``promote_after`` consecutive clean
canaries.  Slot rungs are sticky across admissions, persist through
snapshot/resume, and are journaled (``demoted``/``promoted`` records), so
a crash during degraded mode resumes degraded.  ``telemetry=`` streams
per-chunk gauges as JSONL (launch/telemetry.py).  With ``slo=None`` the
engine traces the exact same computation as before the SLO existed.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import time
from collections import deque
from pathlib import Path
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint
from repro.core.faults import (
    DispatchFault,
    DispatchFaultInjector,
    FaultConfig,
    logits_hook as _make_logits_hook,
)
from repro.core.units import resolve_ladder
from repro.distributed.constraints import axis_rules
from repro.distributed.sharding import (
    serve_pool_shardings,
    serve_pool_tree,
    serve_rules,
    shardings_for,
)
from repro.launch.journal import (
    RequestJournal,
    read_journal,
    replay_plan,
    replay_unit_levels,
)
from repro.launch.telemetry import HostSpans, Telemetry
from repro.models import lm
from repro.models.config import ModelConfig

__all__ = [
    "Request",
    "Completion",
    "Engine",
    "AccuracySLO",
    "SpecConfig",
    "run_static_baseline",
    "solo_generate",
    "STATUSES",
    "SHED_POLICIES",
    "HOST_SPANS",
]

# Completion.status values, in degradation order (docs/robustness.md):
#   ok       — served on the configured (possibly approximate) datapath
#   degraded — health detectors tripped; re-served solo on the exact datapath
#   evicted  — deadline expiry (global or per-request); tokens are partial
#   failed   — the exact datapath itself produced non-finite logits
#   rejected — shed by admission control before taking a slot (overload)
STATUSES = ("ok", "degraded", "evicted", "failed", "rejected")

# Admission-control shed policies (active only with ``max_queue=`` set):
#   reject-new            — shed from the queue tail: the most recently
#                           arrived work is turned away first
#   evict-latest-deadline — shed the queued request whose effective deadline
#                           (arrival + deadline_s; none = infinity) is
#                           furthest away — lowest urgency loses its place
#   shed-by-slo           — shed the queued request least likely to meet its
#                           SLO (smallest deadline slack right now);
#                           deadline-free requests shed newest-first
SHED_POLICIES = ("reject-new", "evict-latest-deadline", "shed-by-slo")

# Host spans of Engine.run (launch/telemetry.py HostSpans), each reported as
# ``stats["host_<name>_s"]``, its own seconds with nested spans taken out,
# so together they cover the run once (docs/serving.md, "Taking a profile"):
#   run             — the loop's own work: due arrivals, evictions, shedding
#   admit           — one admission: validation and the prefill dispatch
#   decode_dispatch — the decode chunk's jitted call, until it returns
#   decode_sync     — waiting for the chunk and its one device-to-host transfer
#   bookkeeping     — emission, finish and SLO updates after the sync
#   journal         — journal writes in the loop
#   telemetry       — building and writing the chunk's telemetry record
#   snapshot        — autosaves
#   wait_arrival    — sleeping with an idle pool until the next arrival
#   gc              — Python garbage collection, whichever span it interrupted
HOST_SPANS = ("run", "admit", "decode_dispatch", "decode_sync", "bookkeeping",
              "journal", "telemetry", "snapshot", "wait_arrival", "gc")

# snapshot meta-blob layout version (bumped on incompatible change)
_SNAPSHOT_FORMAT = 1


@dataclasses.dataclass(frozen=True)
class AccuracySLO:
    """Accuracy service-level objective for :class:`Engine` (``slo=``).

    * ``ladder`` — datapath rung names, approximate → exact.  ``None``
      resolves to ``(cfg.sqrt_unit, "exact")``.  Rung 0 must be the serving
      config's own ``sqrt_unit`` and the last rung must be ``"exact"``
      (``ModelConfig.validate`` pins both); only rung 0 sees injected sqrt
      faults, so one demotion steps out of a seeded fault schedule.
    * ``canary_stride`` — run one shadow-exact canary per slot every this
      many decode steps, counted on the engine's *lifetime* step clock so
      the cadence survives chunk boundaries, resets and resume.  ``None``
      means ∞: never canary — the ladder still routes, but nothing can trip
      it and served tokens stay bit-exact vs an SLO-free engine.
    * ``rel_err_budget`` — demote a slot one rung when a chunk's worst
      canary max-relative logit error (max|served − exact| / max|exact|)
      exceeds this.
    * ``divergence_budget`` — demote when MORE than this many canary argmax
      divergences accumulate at the slot's current rung (0 = the first
      divergent token demotes).  ``None`` disables the divergence trigger.
    * ``promote_after`` — promote one rung back up after this many
      consecutive clean canaries (hysteresis: a demotion needs sustained
      clean evidence to unwind).  ``None`` disables promotion — demotions
      stick for the engine's lifetime, which is what the deterministic
      post-demotion parity checks want.
    """

    ladder: Optional[tuple] = None
    canary_stride: Optional[int] = 32
    rel_err_budget: float = 0.25
    divergence_budget: Optional[int] = 0
    promote_after: Optional[int] = 4

    def __post_init__(self):
        if self.ladder is not None:
            object.__setattr__(self, "ladder", tuple(self.ladder))
        if self.canary_stride is not None and self.canary_stride < 1:
            raise ValueError(
                f"canary_stride must be >= 1 when set (None = never canary); "
                f"got {self.canary_stride}"
            )
        if not self.rel_err_budget > 0:
            raise ValueError(
                f"rel_err_budget must be positive, got {self.rel_err_budget}"
            )
        if self.divergence_budget is not None and self.divergence_budget < 0:
            raise ValueError(
                f"divergence_budget must be >= 0 when set, "
                f"got {self.divergence_budget}"
            )
        if self.promote_after is not None and self.promote_after < 1:
            raise ValueError(
                f"promote_after must be >= 1 when set (None = demotions "
                f"stick), got {self.promote_after}"
            )


@dataclasses.dataclass(frozen=True)
class SpecConfig:
    """Speculative-decoding config for :class:`Engine` (``spec=``).

    * ``k`` — drafts proposed per step; each speculative step commits
      1..k+1 tokens (the verified prefix plus the verify forward's own next
      token).  For sliding-window stacks ``k + 1`` must fit the window.
    * ``draft`` — draft source: ``"ngram"`` (default) self-drafts from the
      slot's own fed-token history (no extra model, no extra forward);
      ``"model"`` greedily continues a small draft model passed to the
      engine as ``draft_model=(draft_params, draft_cfg)``, which then keeps
      its own slot-pool KV cache in lock step with the committed stream.

    Correctness never depends on the drafts: greedy speculative output is
    bit-identical to non-speculative greedy by construction (row 0 of every
    verify block is the committed token), so ``draft`` only moves the
    acceptance rate.  Speculation auto-disables per slot while an accuracy
    SLO holds the slot on a demoted rung, and quarantined requests re-enter
    through the normal admission path (docs/serving.md §Speculative
    decoding).
    """

    k: int = 3
    draft: str = "ngram"

    def __post_init__(self):
        if self.k < 1:
            raise ValueError(f"spec.k must be >= 1 draft tokens, got {self.k}")
        if self.draft not in ("ngram", "model"):
            raise ValueError(
                f"spec.draft must be 'ngram' or 'model', got {self.draft!r}"
            )


def solo_generate(params, cfg: ModelConfig, prompt, max_new_tokens: int, *,
                  cache_len: int, quantized_kv: bool = False) -> np.ndarray:
    """The parity reference: one request alone through the PR-3 fast path
    (prefill + greedy generate_scan).  A staggered engine slot must emit
    exactly these tokens — the slot-parity tests and ``engine_bench`` all
    check against this ONE definition of the solo run."""
    prompt = jnp.asarray(prompt, jnp.int32)
    if prompt.ndim == 1:
        prompt = prompt[None]
    cache, _ = lm.init_cache(cfg, 1, cache_len, quantized=quantized_kv)
    logits, cache = lm.prefill(params, cfg, cache, prompt, last_logit_only=True)
    tok = jnp.argmax(logits[:, -1:], axis=-1)
    toks, _, _ = lm.generate_scan(
        params, cfg, cache, tok, prompt.shape[1], max_new_tokens
    )
    return np.asarray(toks)[0]


@dataclasses.dataclass
class Request:
    """One serving request: ``prompt`` (s,) int32 tokens, a generation budget
    and an arrival offset (seconds from trace start; 0 = already queued).
    ``deadline_s`` (optional) bounds the request's wall-clock residency,
    measured from its *arrival*: once overdue it is evicted with whatever
    tokens it has (status ``evicted``) instead of blocking the pool."""

    uid: int
    prompt: np.ndarray
    max_new_tokens: int
    arrival_s: float = 0.0
    deadline_s: Optional[float] = None


@dataclasses.dataclass
class Completion:
    """A finished request: its emitted tokens plus the serving timeline
    (arrival → admission into a slot → finish, seconds from trace start).
    ``Engine.run`` / ``run_static_baseline`` return ``{uid: Completion}``.

    ``status`` is one of :data:`STATUSES`; ``trips`` counts how many times
    health detectors quarantined this request before it finished.  A request
    evicted straight from the queue (never admitted) has ``admitted_s=-1.0``
    and empty ``tokens``.

    With an accuracy SLO configured (docs/robustness.md §Accuracy SLO) the
    request also carries its canary audit trail: ``unit_final`` names the
    datapath rung its slot sat on when it finished, ``canary_checks`` /
    ``canary_divergences`` count the shadow-exact canaries (and argmax
    disagreements) run against it, and ``unit_trips`` records every
    demotion/promotion event that fired while it held the slot.  All stay
    at their defaults without an SLO (or for never-admitted requests).

    With speculative decoding (``spec=``), ``spec_steps`` counts the
    draft-and-verify steps the request's slot ran while it held it and
    ``spec_accepted`` the drafts those steps accepted;
    :attr:`accepted_per_step` is their ratio.

    ``first_token_s`` is the chunk boundary (the decode chunk's host sync)
    that delivered the request's first token, on the same clock as
    ``arrival_s``; -1.0 for a request with no token.  A request restored by
    :meth:`Engine.resume` with tokens already emitted reads 0.0, like its
    ``admitted_s``.
    """

    uid: int
    prompt_len: int
    tokens: np.ndarray  # emitted tokens (<= max_new_tokens; ends at EOS)
    arrival_s: float
    admitted_s: float
    finished_s: float
    status: str = "ok"
    trips: int = 0
    unit_final: Optional[str] = None
    canary_checks: int = 0
    canary_divergences: int = 0
    unit_trips: tuple = ()
    spec_steps: int = 0
    spec_accepted: int = 0
    first_token_s: float = -1.0

    @property
    def latency_s(self) -> float:
        """End-to-end request latency: arrival to final token, seconds."""
        return self.finished_s - self.arrival_s

    @property
    def accepted_per_step(self) -> float:
        """Mean drafts accepted per speculative step for this request (0..k;
        0.0 without speculation or for never-admitted requests)."""
        return (self.spec_accepted / self.spec_steps) if self.spec_steps else 0.0


@dataclasses.dataclass
class _Ticket:
    """A queue entry: the request plus its quarantine count so far."""

    req: Request
    trips: int = 0


def _ticket_record(t: _Ticket) -> dict:
    """A JSON-serializable snapshot record for one queued/in-flight request —
    the same field set the journal's ``accepted`` record carries."""
    r = t.req
    return {
        "uid": int(r.uid),
        "prompt": [int(x) for x in np.asarray(r.prompt)],
        "max_new_tokens": int(r.max_new_tokens),
        "arrival_s": float(r.arrival_s),
        "deadline_s": None if r.deadline_s is None else float(r.deadline_s),
        "trips": int(t.trips),
    }


def _ticket_from_record(rec: dict, *, arrival_s: float = 0.0) -> _Ticket:
    """Rebuild a queue ticket from a snapshot/journal record.  Wall-clock
    fields are rebased: the dead run's clock is meaningless here, so restored
    requests are due immediately (``arrival_s=0``) and any ``deadline_s``
    window restarts at resume."""
    req = Request(
        uid=int(rec["uid"]),
        prompt=np.asarray(rec["prompt"], np.int32),
        max_new_tokens=int(rec["max_new_tokens"]),
        arrival_s=arrival_s,
        deadline_s=rec.get("deadline_s"),
    )
    return _Ticket(req, trips=int(rec.get("trips", 0)))


class Engine:
    """Slot-pool scheduler around the jitted admit / decode-chunk steps.

    Typical use::

        eng = Engine(params, cfg, num_slots=4, cache_len=64)
        eng.warmup(prompt_lens={6, 8})
        done = eng.run(requests)          # {uid: Completion}

    ``mesh=`` runs the same scheduler on a device mesh (``rules=`` defaults
    to ``serve_rules(cfg, mesh)``): params TP-sharded over 'model'
    (replicated across 'data' — the serving-latency policy), the KV slot
    pool sharded batch-over-'data' and kv-heads-over-'model', the per-slot
    scheduler vectors riding the batch sharding.  The jitted admit /
    decode-chunk steps carry explicit in/out shardings so admissions
    scatter into the sharded pool and a decode chunk stays ONE dispatch —
    no host round-trips per slot — with donation aliasing preserved across
    shards.  With ``serve_rules(..., replicate_params=True)`` tokens are
    bit-exact against the unsharded engine (greedy, non-MoE); under TP they
    agree to bf16-reassociation tolerance — docs/serving.md §Sharded
    serving and tests/launch/test_engine_mesh.py.
    """

    def __init__(self, params, cfg: ModelConfig, *, num_slots: int = 4,
                 cache_len: int = 64, quantized_kv: bool = False,
                 chunk: int = 8, eos_id: Optional[int] = None,
                 temperature: float = 0.0, top_k: int = 0, seed: int = 0,
                 mesh=None, rules=None,
                 faults: Optional[FaultConfig] = None, detectors: bool = True,
                 logit_sentinel: float = 1e4, quarantine_retries: int = 0,
                 max_dispatch_retries: int = 3,
                 dispatch_backoff_s: float = 0.001,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject-new",
                 snapshot_dir=None,
                 snapshot_every_chunks: Optional[int] = None,
                 journal=None,
                 slo: Optional[AccuracySLO] = None,
                 telemetry=None,
                 spec: Optional[SpecConfig] = None,
                 draft_model: Optional[tuple] = None):
        if num_slots < 1 or cache_len < 2 or chunk < 1:
            raise ValueError(
                f"need num_slots >= 1, cache_len >= 2, chunk >= 1 "
                f"(got {num_slots}, {cache_len}, {chunk})"
            )
        if shed_policy not in SHED_POLICIES:
            raise ValueError(
                f"shed_policy must be one of {SHED_POLICIES} (got {shed_policy!r})"
            )
        if max_queue is not None and max_queue < 1:
            raise ValueError(f"max_queue must be >= 1 when set (got {max_queue})")
        if snapshot_every_chunks is not None:
            if snapshot_every_chunks < 1:
                raise ValueError(
                    f"snapshot_every_chunks must be >= 1 when set "
                    f"(got {snapshot_every_chunks})"
                )
            if snapshot_dir is None:
                raise ValueError(
                    "snapshot_every_chunks needs snapshot_dir= (nowhere to "
                    "commit the autosaves)"
                )
        if spec is not None:
            if not isinstance(spec, SpecConfig):
                raise TypeError(f"spec must be a SpecConfig (got {type(spec)!r})")
            if temperature != 0.0 or top_k != 0:
                raise ValueError(
                    "speculative decoding is greedy-only (the acceptance rule "
                    "compares argmaxes); drop temperature/top_k or spec="
                )
            if mesh is not None:
                raise ValueError(
                    "speculative decoding does not run on a mesh yet; drop "
                    "mesh= or spec="
                )
            lm._validate_spec_cfg(cfg)
            if "window" in cfg.blocks and spec.k + 1 > cfg.window:
                raise ValueError(
                    f"spec.k+1={spec.k + 1} exceeds the sliding window "
                    f"({cfg.window}); pick k <= window - 1"
                )
            if spec.draft == "model":
                if draft_model is None:
                    raise ValueError(
                        "spec.draft='model' needs draft_model=(draft_params, "
                        "draft_cfg)"
                    )
                dparams, dcfg = draft_model
                lm._validate_spec_cfg(dcfg, what="draft model")
                if dcfg.vocab != cfg.vocab:
                    raise ValueError(
                        f"draft vocab {dcfg.vocab} != target vocab {cfg.vocab}"
                    )
                if snapshot_dir is not None or snapshot_every_chunks is not None:
                    raise ValueError(
                        "snapshots cover n-gram speculation only: the n-gram "
                        "history rebuilds from slot metadata at resume, but a "
                        "draft-model KV cache does not serialize in snapshot "
                        "format 1 — use spec.draft='ngram' with snapshot_dir="
                    )
        elif draft_model is not None:
            raise ValueError("draft_model= without spec= has no effect; pass "
                             "spec=SpecConfig(draft='model')")
        self.spec = spec
        self._spans = HostSpans()  # replaced by each run()
        self._draft_model = draft_model if (
            spec is not None and spec.draft == "model") else None
        self.params = params
        # sqrt-site fault schedules ride the serving config itself (hashable,
        # so the jitted steps key their caches correctly); activation faults
        # become a logits hook inside the decode chunk; dispatch faults stay
        # host-side.  The degradation ladder strips all of them via exact_twin.
        if faults is not None and faults.targets_sqrt:
            cfg = cfg.replace(sqrt_faults=faults)
        if slo is not None and not isinstance(slo, AccuracySLO):
            raise TypeError(f"slo must be an AccuracySLO (got {type(slo)!r})")
        self.slo = slo
        if slo is not None:
            ladder = (slo.ladder if slo.ladder is not None
                      else (cfg.sqrt_unit, "exact"))
            if ladder[0] != cfg.sqrt_unit:
                raise ValueError(
                    f"slo.ladder rung 0 must be the serving config's "
                    f"sqrt_unit {cfg.sqrt_unit!r} (got {ladder[0]!r}) — the "
                    f"ladder demotes FROM the configured datapath"
                )
            resolve_ladder(ladder)  # shape/name validation, fail fast
            self._ladder: Optional[tuple] = tuple(ladder)
            # the ladder rides the frozen config so the jitted steps key
            # their caches on it and decode accepts a per-row rung vector
            cfg = cfg.replace(sqrt_ladder=self._ladder)
        else:
            self._ladder = None
        self._canary_stride = (
            0 if slo is None or slo.canary_stride is None
            else int(slo.canary_stride)
        )
        if telemetry is None or isinstance(telemetry, Telemetry):
            self._telemetry = telemetry
        else:
            self._telemetry = Telemetry(telemetry)
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_len = cache_len
        self.quantized_kv = quantized_kv
        self.chunk = chunk
        self.eos_id = eos_id
        self.temperature = float(temperature)
        self.top_k = int(top_k)
        self.seed = int(seed)
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.snapshot_dir = None if snapshot_dir is None else Path(snapshot_dir)
        self.snapshot_every_chunks = snapshot_every_chunks
        if journal is None or isinstance(journal, RequestJournal):
            self._journal = journal
        else:
            self._journal = RequestJournal(journal)
        self.faults = faults
        self.detectors = detectors
        self.logit_sentinel = float(logit_sentinel)
        self.quarantine_retries = int(quarantine_retries)
        self.max_dispatch_retries = int(max_dispatch_retries)
        self.dispatch_backoff_s = float(dispatch_backoff_s)
        self._injector = (
            DispatchFaultInjector(faults)
            if faults is not None and faults.targets_dispatch
            else None
        )
        self._hook = _make_logits_hook(faults)
        self._base_key = jax.random.PRNGKey(seed)

        self.mesh = mesh
        self.rules = rules if rules is not None else (
            serve_rules(cfg, mesh) if mesh is not None else None
        )
        if mesh is not None:
            # one abstract init for the param logical axes; the concrete
            # params are then committed to the mesh once, up front
            _, specs = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
            self._param_sh = shardings_for(specs, mesh, self.rules, params)
            self.params = jax.device_put(params, self._param_sh)
            self._pool_sh = serve_pool_shardings(
                cfg, mesh, self.rules, num_slots=num_slots,
                cache_len=cache_len, quantized=quantized_kv,
            )
            rules_ctx = lambda: axis_rules(mesh, self.rules)  # noqa: E731
        else:
            rules_ctx = contextlib.nullcontext

        base_key = self._base_key

        if mesh is not None:
            # explicit in/out shardings: the pool state keeps its committed
            # placement through every donated step (no resharding between
            # chunks) and scheduler-side host operands stay replicated
            sh = self._pool_sh
            pool_in = (sh["cache"], sh["tok"], sh["vec"], sh["vec"], sh["vec"],
                       sh["keys"])
            rep = sh["replicated"]

        spec_on = spec is not None
        draft_on = self._draft_model is not None
        if draft_on:
            dparams, dcfg = self._draft_model

        def make_admit(acfg):
            """Build the jitted admission step for one datapath config.
            Without an SLO there is exactly one (the serving config); with a
            ladder there is one per rung — a request admitted into a demoted
            slot must PREFILL on that slot's rung too, because the KV cache
            is datapath-dependent (qk-norm routes cached keys through the
            sqrt unit), so mixing an approximate prefill with exact decode
            would break the post-demotion exactness guarantee.  With
            speculation the step also lands the prompt in the slot's
            fed-token history row (the n-gram draft source) and, when
            drafting with a model, prefills the draft model's own cache —
            still one dispatch per admission."""

            # stable program name jit_admit_fn: device traces find admissions by it
            def admit_fn(p, cache, tok, pos, active, remaining, keys,
                         *rest):
                i = 0
                if spec_on:
                    hist = rest[i]
                    i += 1
                if draft_on:
                    dcache = rest[i]
                    i += 1
                prompt, slots, budgets, uids = rest[i:]
                with rules_ctx():
                    logits, cache = lm.prefill_into_slots(
                        p, acfg, cache, prompt, slots
                    )
                    new_keys = jax.vmap(
                        lambda u: jax.random.fold_in(base_key, u)
                    )(uids)
                    # the prompt's last token sits at position s-1, so its
                    # successor draws from fold_in(key, s-1) — exactly what
                    # decode_slots_scan does for every later token
                    last_pos = jnp.full(
                        (prompt.shape[0],), prompt.shape[1] - 1, jnp.int32
                    )
                    first = lm.sample_tokens(
                        logits[:, -1, :].astype(jnp.float32), last_pos,
                        new_keys, temperature, top_k,
                    )
                    tok = tok.at[slots, 0].set(first)
                    pos = pos.at[slots].set(prompt.shape[1])
                    active = active.at[slots].set(True)
                    remaining = remaining.at[slots].set(budgets)
                    keys = keys.at[slots].set(new_keys)
                    out = (cache, tok, pos, active, remaining, keys)
                    if spec_on:
                        # hist[p] = token fed at step p; stale entries from
                        # the slot's previous occupant past the new prompt
                        # stay masked (readers check idx < pos) until the
                        # decode scan overwrites them in commit order
                        s_w = min(prompt.shape[1], hist.shape[1])
                        hist = hist.at[slots, :s_w].set(prompt[:, :s_w])
                        out = out + (hist,)
                    if draft_on:
                        _, dcache = lm.prefill_into_slots(
                            dparams, dcfg, dcache, prompt, slots
                        )
                        out = out + (dcache,)
                    return out

            donate = tuple(range(1, 7 + spec_on + draft_on))
            if mesh is None:
                return jax.jit(admit_fn, donate_argnums=donate)
            return jax.jit(
                admit_fn,
                donate_argnums=donate,
                in_shardings=(self._param_sh, *pool_in, rep, rep, rep, rep),
                out_shardings=pool_in,
            )

        self._make_admit = make_admit
        # ladder level -> jitted admit; level 0 (the serving datapath) is
        # the only entry most runs ever build
        self._admit_jits: dict = {0: make_admit(cfg)}

        hook = self._hook
        with_health = self.detectors
        slo_on = slo is not None
        canary_stride = self._canary_stride

        if spec_on:
            spec_k = spec.k

            # stable program name jit_decode_fn: device traces find chunks by it
            def decode_fn(p, c, tok, pos, act, rem, hist, *rest):
                i = 0
                kw = {}
                if draft_on:
                    kw = dict(draft_params=dparams, draft_cfg=dcfg,
                              draft_cache=rest[i])
                    i += 1
                if slo_on:
                    levels, offset = rest[i:]
                    # a demoted slot's rung is the accuracy-critical state:
                    # it decodes non-speculatively (acceptance clamped to 0,
                    # row 0 of the block IS its sequential step) until the
                    # SLO promotes it back
                    kw.update(unit_levels=levels, spec_disable=levels > 0,
                              canary_stride=canary_stride,
                              canary_offset=offset)
                return lm.decode_slots_spec_scan(
                    p, cfg, c, tok, pos, act, rem, hist, chunk, k=spec_k,
                    eos_id=eos_id, with_health=with_health,
                    logits_hook=hook, **kw,
                )

            self._decode_j = jax.jit(
                decode_fn, donate_argnums=tuple(range(1, 7 + draft_on))
            )
            self.reset()
            return

        # stable program name jit_decode_fn: device traces find chunks by it
        def decode_fn(p, c, tok, pos, act, rem, keys, *slo_args):
            with rules_ctx():
                kw = {}
                if slo_on:
                    levels, offset = slo_args
                    kw = dict(unit_levels=levels, canary_stride=canary_stride,
                              canary_offset=offset)
                return lm.decode_slots_scan(
                    p, cfg, c, tok, pos, act, rem, chunk, eos_id=eos_id,
                    temperature=temperature, top_k=top_k, keys=keys,
                    with_health=with_health, logits_hook=hook, **kw,
                )

        if mesh is None:
            self._decode_j = jax.jit(decode_fn, donate_argnums=(1, 2, 3, 4, 5))
        else:
            # toks/emitted (b, chunk) follow the slot sharding (batch over
            # data, time replicated); the carried pool state keeps its
            # committed placement; the (b,) health and canary signals ride
            # the same per-slot vector sharding
            decode_in = (self._param_sh, *pool_in)
            decode_out = (sh["tok"], sh["tok"], sh["tok"], sh["vec"],
                          sh["vec"], sh["vec"], sh["cache"])
            if with_health:
                decode_out = decode_out + (sh["vec"], sh["vec"])
            if slo_on:
                decode_in = decode_in + (sh["vec"], rep)  # levels, offset
                if canary_stride:
                    decode_out = decode_out + (sh["vec"],) * 4
            self._decode_j = jax.jit(
                decode_fn,
                donate_argnums=(1, 2, 3, 4, 5),
                in_shardings=decode_in,
                out_shardings=decode_out,
            )
        self.reset()

    # -- pool state ---------------------------------------------------------

    def reset(self):
        """Zero the pool: fresh cache, all slots free, queues empty.  In mesh
        mode the pool state is committed to its serving shardings here, once;
        the jitted steps' matching in/out shardings keep it there.  The
        snapshot step counter (total decode chunks ever served) survives a
        reset so autosaves to the same ``snapshot_dir`` never collide."""
        b = self.num_slots
        self._set_pool(
            lm.init_pool_state(
                self.cfg, b, self.cache_len, quantized=self.quantized_kv,
                key=self._base_key,
            )
        )
        self._owner: list[Optional[Request]] = [None] * b
        self._emitted: list[list[int]] = [[] for _ in range(b)]
        self._admitted_s = [0.0] * b
        self._first_s = [-1.0] * b
        self._trips = [0] * b
        self._queue: deque = deque()      # due tickets waiting for a slot
        self._arrivals: deque = deque()   # accepted tickets not yet due
        self._dispatch_faults = 0
        self._dispatch_retries = 0
        self._snapshots_written = 0
        self._journal_replays = 0
        self._chunks_total = getattr(self, "_chunks_total", 0)
        # accuracy-SLO slot state (all-zeros and inert without slo=): the
        # ladder rung each slot decodes at, the promotion hysteresis streak,
        # divergences at the current rung, and per-request canary audit
        # fields (the last four reset at _admit; the rung itself is STICKY —
        # a demoted slot serves its next occupant on the demoted rung too,
        # because the KV cache it prefills into is datapath-dependent)
        self._unit_levels = np.zeros(b, np.int32)
        self._clean_streak = np.zeros(b, np.int32)
        self._rung_div = np.zeros(b, np.int32)
        self._slot_canary_checks = np.zeros(b, np.int64)
        self._slot_canary_div = np.zeros(b, np.int64)
        self._slot_events: list[list] = [[] for _ in range(b)]
        # speculative-decoding state: the per-slot fed-token history rows
        # (the n-gram draft source — device-resident, donated through the
        # admit/decode jits alongside the pool), the draft model's own slot
        # cache when model-drafting, and host-side acceptance counters (per
        # current occupant, reset at _admit; and engine-lifetime totals)
        if self.spec is not None:
            self._hist = jnp.zeros((b, self.cache_len), jnp.int32)
            if self._draft_model is not None:
                dcfg = self._draft_model[1]
                self._dcache, _ = lm.init_cache(dcfg, b, self.cache_len)
            self._slot_spec_steps = np.zeros(b, np.int64)
            self._slot_spec_acc = np.zeros(b, np.int64)
            self._spec_steps_total = 0
            self._spec_acc_total = 0
        if self._injector is not None:
            self._injector.reset()

    @property
    def unit_levels(self) -> tuple:
        """Per-slot ladder rung indices (0 = the serving datapath).  Empty
        without an accuracy SLO."""
        if self._ladder is None:
            return ()
        return tuple(int(x) for x in self._unit_levels)

    @property
    def unit_names(self) -> tuple:
        """Per-slot datapath names at the current rungs; empty without an
        accuracy SLO."""
        if self._ladder is None:
            return ()
        return tuple(self._ladder[int(x)] for x in self._unit_levels)

    def _pool_state(self) -> dict:
        """The live device pool as the single ``lm.init_pool_state`` tree —
        the serialization unit ``snapshot`` hands to ``checkpoint.save``."""
        return {
            "cache": self._cache,
            "tok": self._tok,
            "pos": self._pos,
            "active": self._active,
            "remaining": self._remaining,
            "keys": self._keys,
        }

    def _set_pool(self, pool: dict) -> None:
        """Install a pool-state tree as the live device state; in mesh mode
        every leaf is committed to its serving sharding."""
        if self.mesh is not None:
            pool = jax.device_put(pool, serve_pool_tree(self._pool_sh))
        self._cache = pool["cache"]
        self._tok = pool["tok"]
        self._pos = pool["pos"]
        self._active = pool["active"]
        self._remaining = pool["remaining"]
        self._keys = pool["keys"]

    def warmup(self, prompt_lens):
        """Compile the admit step for each prompt-length bucket plus one
        decode chunk, off the serving clock, then reset the pool.  NOTE: the
        trailing reset wipes restored state — do not warmup an engine built
        by :meth:`resume`; its first chunk compiles on the serving clock
        instead."""
        for s in sorted(set(int(s) for s in prompt_lens)):
            dummy = Request(uid=-1, prompt=np.zeros(s, np.int32), max_new_tokens=1)
            self._admit(dummy, slot=0, now=0.0)
        self._decode_chunk()
        self.reset()

    # -- crash consistency: snapshot / resume / journal replay --------------

    def snapshot(self, ckpt_dir=None, *, step: Optional[int] = None) -> Path:
        """Serialize the COMPLETE live serving state through
        ``checkpoint.save``'s atomic tmp→rename commit and return the
        committed directory.

        One snapshot holds (a) the device pool as the single
        ``lm.init_pool_state`` tree — every cache family, float and int8,
        plus per-slot tok/pos/active/remaining vectors and PRNG keys — and
        (b) a host-metadata blob: per-slot request records (uid, prompt,
        budget, tokens emitted so far, trips), the pending queue, and the
        engine shape.  ``step`` defaults to the lifetime decode-chunk
        counter, so autosaves are monotonic and never collide.  A resumed
        engine continues greedy exact-mode decode bit-exactly
        (tests/launch/test_engine_snapshot.py).
        """
        ckpt_dir = ckpt_dir if ckpt_dir is not None else self.snapshot_dir
        if ckpt_dir is None:
            raise ValueError("snapshot needs a directory: pass ckpt_dir= or "
                             "construct the Engine with snapshot_dir=")
        if self._draft_model is not None:
            raise ValueError(
                "snapshot covers n-gram speculation only (the draft-model KV "
                "cache does not serialize in snapshot format 1)"
            )
        step = self._chunks_total if step is None else int(step)
        slots_meta = []
        for slot in range(self.num_slots):
            req = self._owner[slot]
            if req is None:
                slots_meta.append(None)
            else:
                rec = _ticket_record(_Ticket(req, self._trips[slot]))
                rec["emitted"] = [int(x) for x in self._emitted[slot]]
                slots_meta.append(rec)
        meta = {
            "format": _SNAPSHOT_FORMAT,
            "engine": {
                "num_slots": self.num_slots,
                "cache_len": self.cache_len,
                "quantized_kv": self.quantized_kv,
                "chunk": self.chunk,
                "eos_id": self.eos_id,
                "temperature": self.temperature,
                "top_k": self.top_k,
                "seed": self.seed,
                "max_queue": self.max_queue,
                "shed_policy": self.shed_policy,
                "slo": (None if self.slo is None
                        else dataclasses.asdict(self.slo)),
                # additive key: readers without speculation ignore it
                "spec": (None if self.spec is None
                         else dataclasses.asdict(self.spec)),
            },
            "chunks_total": int(self._chunks_total),
            "slots": slots_meta,
            # pending work in service order: due queue first, then future
            # arrivals — all of it is due immediately after a resume
            "queue": [_ticket_record(t) for t in self._queue]
            + [_ticket_record(t) for t in self._arrivals],
        }
        if self._ladder is not None:
            # additive key (format unchanged: readers without an SLO ignore
            # it) — the authoritative copy of the ladder state; the journal's
            # demoted/promoted trail is the flushed-not-fsynced shadow
            meta["slo"] = {
                "unit_levels": [int(x) for x in self._unit_levels],
                "clean_streak": [int(x) for x in self._clean_streak],
                "rung_div": [int(x) for x in self._rung_div],
                "canary_checks": [int(x) for x in self._slot_canary_checks],
                "canary_divergences": [int(x) for x in self._slot_canary_div],
                "events": [list(e) for e in self._slot_events],
            }
        blob = np.frombuffer(json.dumps(meta).encode("utf-8"), np.uint8)
        path = checkpoint.save(
            ckpt_dir, step, {"pool": self._pool_state(), "meta": blob}
        )
        self._snapshots_written += 1
        if self._journal is not None:
            self._journal.snapshot(step)
        return path

    @staticmethod
    def _read_snapshot_meta(ckpt_dir, step: int) -> dict:
        """Read just the host-metadata blob of a committed snapshot (needed
        before the pool restore target can even be shaped)."""
        final = Path(ckpt_dir) / f"step-{step}"
        man_path = final / "manifest.json"
        if not man_path.exists():
            raise checkpoint.CheckpointError(
                f"no committed engine snapshot at {final}"
            )
        manifest = json.loads(man_path.read_text())
        entry = next(
            (leaf for leaf in manifest["leaves"] if leaf["name"] == "meta"), None
        )
        if entry is None:
            raise checkpoint.CheckpointError(
                f"snapshot {final} has no 'meta' leaf — not an engine snapshot"
            )
        meta = json.loads(np.load(final / entry["file"]).tobytes().decode("utf-8"))
        if meta.get("format") != _SNAPSHOT_FORMAT:
            raise checkpoint.CheckpointError(
                f"snapshot {final} has format {meta.get('format')!r}; this "
                f"build reads format {_SNAPSHOT_FORMAT}"
            )
        return meta

    @classmethod
    def resume(cls, params, cfg: ModelConfig, ckpt_dir=None, *,
               step: Optional[int] = None, journal=None, mesh=None,
               rules=None, **overrides) -> "Engine":
        """Rebuild a crashed engine: restore the latest committed snapshot
        under ``ckpt_dir`` (if any), then reconcile the write-ahead journal
        on top.  Returns an engine ready for :meth:`run` — restored in-flight
        slots continue decoding and restored queue entries are served first,
        ahead of any new requests passed to ``run``.

        * **Elastic resharding**: pass ``mesh=`` (and optionally ``rules=``)
          to land a snapshot taken on one mesh shape onto another — the pool
          leaves are read on host and re-sharded via ``serve_pool_shardings``
          (1-device → mesh and back both work).
        * **Journal reconciliation**: uids journaled ``finished`` are
          dropped from the restored state (their completion is already
          durable in the journal); ``accepted`` requests with no finished
          record and no presence in the snapshot are replayed from their
          journal fields (counted in the ``journal_replays`` stat).
        * **Overrides**: scheduling knobs (``chunk``, ``detectors``,
          ``max_queue``, ``snapshot_every_chunks``, ...) may be overridden;
          the pool shape (``num_slots`` / ``cache_len`` / ``quantized_kv``)
          is part of the serialized state and cannot change.
        * With no snapshot committed yet, the engine is built fresh from
          ``overrides`` alone and recovery is journal-replay only.

        Do not call :meth:`warmup` on the result (it resets the pool); the
        first chunk compiles on the serving clock instead.
        """
        if step is None and ckpt_dir is not None:
            step = checkpoint.latest_step(ckpt_dir)
        meta = None
        if step is not None:
            meta = cls._read_snapshot_meta(ckpt_dir, step)
            e = meta["engine"]
            kw = {
                "num_slots": e["num_slots"],
                "cache_len": e["cache_len"],
                "quantized_kv": e["quantized_kv"],
                "chunk": e["chunk"],
                "eos_id": e["eos_id"],
                "temperature": e["temperature"],
                "top_k": e["top_k"],
                "seed": e["seed"],
                "max_queue": e.get("max_queue"),
                "shed_policy": e.get("shed_policy", "reject-new"),
            }
            s = e.get("slo")
            if s is not None:
                s = dict(s)
                if s.get("ladder") is not None:
                    s["ladder"] = tuple(s["ladder"])
                kw["slo"] = AccuracySLO(**s)
            sp = e.get("spec")
            if sp is not None:
                kw["spec"] = SpecConfig(**sp)
            for frozen in ("num_slots", "cache_len", "quantized_kv"):
                if frozen in overrides and overrides[frozen] != kw[frozen]:
                    raise ValueError(
                        f"resume cannot change {frozen}: the snapshot pool "
                        f"was shaped with {kw[frozen]!r} (got "
                        f"{overrides[frozen]!r}); the pool shape is part of "
                        f"the serialized state"
                    )
            kw.update(overrides)
        else:
            kw = dict(overrides)
        if journal is not None:
            kw.setdefault("journal", journal)
        if ckpt_dir is not None:
            kw.setdefault("snapshot_dir", ckpt_dir)
        eng = cls(params, cfg, mesh=mesh, rules=rules, **kw)
        if step is not None:
            eng._restore_snapshot(ckpt_dir, step, meta)
        eng._replay_journal()
        return eng

    def _restore_snapshot(self, ckpt_dir, step: int, meta: dict) -> None:
        """Install a committed snapshot: device pool through
        ``checkpoint.restore`` (resharded onto this engine's mesh, if any)
        plus the host-side slot/queue metadata."""
        like = {
            "pool": lm.init_pool_state(
                self.cfg, self.num_slots, self.cache_len,
                quantized=self.quantized_kv, abstract=True,
            )
        }
        shardings = None
        if self.mesh is not None:
            shardings = {"pool": serve_pool_tree(self._pool_sh)}
        restored = checkpoint.restore(ckpt_dir, step, like, shardings=shardings)
        self._set_pool_host(restored["pool"])
        for slot, rec in enumerate(meta["slots"]):
            if rec is None:
                continue
            t = _ticket_from_record(rec)
            self._owner[slot] = t.req
            self._emitted[slot] = [int(x) for x in rec.get("emitted", [])]
            self._admitted_s[slot] = 0.0  # clocks restart at resume
            self._first_s[slot] = 0.0 if self._emitted[slot] else -1.0
            self._trips[slot] = t.trips
        self._queue = deque(_ticket_from_record(r) for r in meta["queue"])
        self._chunks_total = int(meta["chunks_total"])
        self._restored_step = int(step)
        if self.spec is not None:
            # the n-gram history is NOT part of the serialized pool (the
            # snapshot format predates speculation); rebuild it from the
            # slot metadata — hist[p] is the token fed at step p, which is
            # the prompt followed by the emitted (= fed) tokens.  A resumed
            # slot drafts from exactly the history an uninterrupted run
            # would hold, and drafts never affect correctness anyway.
            hist = np.zeros((self.num_slots, self.cache_len), np.int32)
            for slot, rec in enumerate(meta["slots"]):
                if rec is None:
                    continue
                fed = list(rec["prompt"]) + [int(x) for x in
                                             rec.get("emitted", [])]
                fed = fed[: self.cache_len]
                hist[slot, : len(fed)] = fed
            self._hist = jnp.asarray(hist)
        s = meta.get("slo")
        if s is not None and self._ladder is not None:
            top = len(self._ladder) - 1
            clamp = lambda xs: np.asarray(  # noqa: E731
                [min(max(int(x), 0), top) for x in xs], np.int32
            )
            self._unit_levels = clamp(s["unit_levels"])
            self._clean_streak = np.asarray(s["clean_streak"], np.int32)
            self._rung_div = np.asarray(s["rung_div"], np.int32)
            self._slot_canary_checks = np.asarray(s["canary_checks"], np.int64)
            self._slot_canary_div = np.asarray(
                s["canary_divergences"], np.int64
            )
            self._slot_events = [list(e) for e in s["events"]]

    def _set_pool_host(self, pool: dict) -> None:
        """Like ``_set_pool`` but for already-placed restored arrays: the
        non-mesh path keeps ``checkpoint.restore``'s default placement, the
        mesh path got its shardings at restore time."""
        self._cache = pool["cache"]
        self._tok = pool["tok"]
        self._pos = pool["pos"]
        self._active = pool["active"]
        self._remaining = pool["remaining"]
        self._keys = pool["keys"]

    def _replay_journal(self) -> None:
        """Reconcile the write-ahead journal against the restored state:
        finished uids are done exactly once (drop them everywhere); accepted
        uids absent from both the queue and the slots are replayed."""
        if self._journal is None:
            return
        records = read_journal(self._journal.path)
        if not records:
            return
        finished, accepted = replay_plan(records)
        deactivate = [
            slot for slot in range(self.num_slots)
            if self._owner[slot] is not None
            and self._owner[slot].uid in finished
        ]
        if deactivate:
            # free the slot host-side and clear its device liveness (the row
            # decays harmlessly, as in quarantine); done on host so the mesh
            # placement survives
            # np.array (copy): device_get can hand back a read-only view
            active = np.array(jax.device_get(self._active))
            for slot in deactivate:
                self._owner[slot] = None
                self._emitted[slot] = []
                self._first_s[slot] = -1.0
                active[slot] = False
            if self.mesh is not None:
                self._active = jax.device_put(active, self._pool_sh["vec"])
            else:
                self._active = jnp.asarray(active)
        self._queue = deque(
            t for t in self._queue if t.req.uid not in finished
        )
        present = {t.req.uid for t in self._queue} | {
            o.uid for o in self._owner if o is not None
        }
        for uid, rec in accepted.items():
            if uid in present:
                continue
            self._queue.append(_ticket_from_record({**rec, "trips": 0}))
            self._journal_replays += 1
        if self._ladder is not None:
            # ladder trips journaled AFTER the restored snapshot override
            # its rungs (the crash happened mid-degradation); with no
            # snapshot the whole trail reconstructs best-effort, so a crash
            # during degraded mode resumes degraded either way
            recs = records
            restored = getattr(self, "_restored_step", None)
            if restored is not None:
                marks = [
                    i for i, r in enumerate(records)
                    if r.get("kind") == "snapshot" and r.get("step") == restored
                ]
                if marks:
                    recs = records[marks[-1] + 1:]
            top = len(self._ladder) - 1
            for slot, lv in replay_unit_levels(recs).items():
                if 0 <= slot < self.num_slots:
                    self._unit_levels[slot] = min(max(int(lv), 0), top)

    # -- scheduler ----------------------------------------------------------

    def _validate(self, req: Request):
        """Reject a malformed request up front — naming the request id and
        the offending field — before it can touch any slot state."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1:
            raise ValueError(
                f"request {req.uid}: field 'prompt' must be a 1-D token "
                f"array (got shape {prompt.shape})"
            )
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"request {req.uid}: field 'prompt' must hold integer token "
                f"ids (got dtype {prompt.dtype})"
            )
        s = int(prompt.shape[0])
        if s < 1:
            raise ValueError(
                f"request {req.uid}: field 'prompt' needs >= 1 prompt token "
                f"(got {s})"
            )
        if not isinstance(req.max_new_tokens, (int, np.integer)) or req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.uid}: field 'max_new_tokens' needs an integer "
                f"generation budget >= 1 (got {req.max_new_tokens!r})"
            )
        if req.deadline_s is not None and req.deadline_s <= 0:
            raise ValueError(
                f"request {req.uid}: field 'deadline_s' must be positive "
                f"when set (got {req.deadline_s})"
            )
        if not self.cfg.is_subquadratic and s + req.max_new_tokens > self.cache_len:
            # a dense (global-attention) cache is NOT a ring: positions past
            # cache_len would wrap onto the request's own KV and, once
            # pos >= cache_len, the validity mask treats every line as live —
            # silently wrong tokens.  (Pure window/SSM stacks wrap by design.)
            raise ValueError(
                f"request {req.uid}: fields 'prompt' ({s}) + 'max_new_tokens' "
                f"budget ({req.max_new_tokens}) exceeds the dense cache_len "
                f"({self.cache_len}); allocate a larger pool"
            )

    def _dispatch(self, fn, *args):
        """Run a jitted step under the dispatch fault schedule: an injected
        failure raises BEFORE the call (donated pool buffers stay intact), is
        retried with exponential backoff up to ``max_dispatch_retries``, and
        only then escalates as :class:`DispatchFault`."""
        if self._injector is None:
            return fn(*args)
        attempts = 0
        while self._injector.should_fail():
            attempts += 1
            self._dispatch_faults += 1
            if attempts > self.max_dispatch_retries:
                raise DispatchFault(
                    f"dispatch failed {attempts} consecutive times "
                    f"(max_dispatch_retries={self.max_dispatch_retries})"
                )
            self._dispatch_retries += 1
            time.sleep(self.dispatch_backoff_s * (2 ** (attempts - 1)))
        return fn(*args)

    def _admit_jit_for(self, level: int):
        """The jitted admission step for a ladder rung, built lazily: most
        runs never demote, so only rung 0 (built in __init__) ever traces."""
        j = self._admit_jits.get(level)
        if j is None:
            # a non-zero rung prefills on that rung's unit, fault-free and
            # ladder-free (the rung IS the datapath; decode re-selects per
            # row via unit_levels)
            acfg = self.cfg.replace(
                sqrt_unit=self._ladder[level], sqrt_faults=None,
                sqrt_ladder=None,
            )
            j = self._admit_jits[level] = self._make_admit(acfg)
        return j

    def _admit(self, req: Request, slot: int, now: float, trips: int = 0):
        with self._spans.span("admit", len(req.prompt)):
            self._validate(req)
            level = 0 if self._ladder is None else int(self._unit_levels[slot])
            prompt = jnp.asarray(req.prompt, jnp.int32)[None]
            extra_in: tuple = ()
            if self.spec is not None:
                extra_in = (self._hist,)
                if self._draft_model is not None:
                    extra_in = extra_in + (self._dcache,)
            out = self._dispatch(
                self._admit_jit_for(level),
                self.params, self._cache, self._tok, self._pos, self._active,
                self._remaining, self._keys, *extra_in, prompt,
                np.asarray([slot], np.int32),
                np.asarray([req.max_new_tokens], np.int32),
                # sampling stream keyed by uid, not by slot
                np.asarray([req.uid & 0x7FFFFFFF], np.int32),
            )
            (self._cache, self._tok, self._pos, self._active, self._remaining,
             self._keys) = out[:6]
            if self.spec is not None:
                self._hist = out[6]
                if self._draft_model is not None:
                    self._dcache = out[7]
                self._slot_spec_steps[slot] = 0
                self._slot_spec_acc[slot] = 0
            self._owner[slot] = req
            self._emitted[slot] = []
            self._admitted_s[slot] = now
            self._first_s[slot] = -1.0
            self._trips[slot] = trips
            # request-scoped SLO state resets with the new occupant; the rung
            # itself (and its divergence count / streak) is slot-scoped
            self._slot_canary_checks[slot] = 0
            self._slot_canary_div[slot] = 0
            self._slot_events[slot] = []

    def _decode_chunk(self):
        if self.spec is not None:
            return self._decode_chunk_spec()
        args = (self.params, self._cache, self._tok, self._pos, self._active,
                self._remaining, self._keys)
        if self.slo is not None:
            # per-row rung vector + the lifetime step offset that keeps the
            # canary cadence global across chunks/resets/resume (values are
            # plain operands — no retrace as they change)
            args = args + (
                np.asarray(self._unit_levels, np.int32),
                np.int32(self._chunks_total * self.chunk),
            )
        with self._spans.span("decode_dispatch"):
            out = self._dispatch(self._decode_j, *args)
        (toks, emitted, self._tok, self._pos, self._active,
         self._remaining, self._cache) = out[:7]
        i = 7
        if self.detectors:
            bad, mx = out[i], out[i + 1]
            i += 2
        else:
            bad = jnp.zeros((self.num_slots,), bool)
            mx = jnp.zeros((self.num_slots,), jnp.float32)
        if self.slo is not None and self._canary_stride:
            cc, cd, cmr, crs = out[i:i + 4]
        else:
            cc = cd = np.zeros((self.num_slots,), np.int32)
            cmr = crs = np.zeros((self.num_slots,), np.float32)
        # ONE device->host sync per chunk: tokens, emission mask, liveness,
        # the health signals and the canary gauges come back together
        # (separate np.asarray round-trips measurably dominate the
        # smoke-scale serve loop)
        with self._spans.span("decode_sync"):
            return jax.device_get((toks, emitted, self._active, bad, mx,
                                   cc, cd, cmr, crs))

    def _decode_chunk_spec(self):
        """The speculative twin of :meth:`_decode_chunk`: one jitted
        ``lm.decode_slots_spec_scan`` of ``chunk`` draft-and-verify steps
        (each committing 1..k+1 tokens per active slot), returning the same
        9-tuple so the serve loop is speculation-agnostic — ``toks`` /
        ``emitted`` are just wider, ``chunk * (k+1)``.  The per-slot
        acceptance gauges ride the chunk's single host sync and accumulate
        into the occupant counters here."""
        args = [self.params, self._cache, self._tok, self._pos, self._active,
                self._remaining, self._hist]
        if self._draft_model is not None:
            args.append(self._dcache)
        if self.slo is not None:
            args += [np.asarray(self._unit_levels, np.int32),
                     np.int32(self._chunks_total * self.chunk)]
        with self._spans.span("decode_dispatch"):
            out = self._dispatch(self._decode_j, *args)
        (toks, emitted, self._tok, self._pos, self._active,
         self._remaining, self._cache, self._hist) = out[:8]
        accepted, steps = out[8], out[9]
        i = 10
        if self._draft_model is not None:
            self._dcache = out[i]
            i += 1
        if self.detectors:
            bad, mx = out[i], out[i + 1]
            i += 2
        else:
            bad = jnp.zeros((self.num_slots,), bool)
            mx = jnp.zeros((self.num_slots,), jnp.float32)
        if self.slo is not None and self._canary_stride:
            cc, cd, cmr, crs = out[i:i + 4]
        else:
            cc = cd = np.zeros((self.num_slots,), np.int32)
            cmr = crs = np.zeros((self.num_slots,), np.float32)
        with self._spans.span("decode_sync"):
            got = jax.device_get((toks, emitted, self._active, bad, mx,
                                  cc, cd, cmr, crs, accepted, steps))
        acc_h, steps_h = got[9], got[10]
        self._slot_spec_acc += acc_h
        self._slot_spec_steps += steps_h
        self._spec_acc_total += int(acc_h.sum())
        self._spec_steps_total += int(steps_h.sum())
        return got[:9]

    def _slo_update(self, cc, cd, cmr, counters) -> None:
        """Apply one chunk's canary gauges to the per-slot ladder: demote a
        slot one rung when it blew a budget this chunk, promote one rung
        after ``promote_after`` consecutive clean canaries.  Runs BEFORE the
        chunk's finish bookkeeping so a request that ends this chunk sees
        its final rung and full canary trail in its Completion."""
        slo, ladder = self.slo, self._ladder
        top = len(ladder) - 1
        for slot in range(self.num_slots):
            n = int(cc[slot])
            if n == 0:
                continue  # no canary fired for this slot this chunk
            dv = int(cd[slot])
            mr = float(cmr[slot])
            counters["canary_checks"] += n
            counters["canary_divergences"] += dv
            counters["canary_max_rel_err"] = max(
                counters["canary_max_rel_err"], mr
            )
            self._slot_canary_checks[slot] += n
            self._slot_canary_div[slot] += dv
            self._rung_div[slot] += dv
            level = int(self._unit_levels[slot])
            owner = self._owner[slot]
            uid = None if owner is None else owner.uid
            over_div = (slo.divergence_budget is not None
                        and int(self._rung_div[slot]) > slo.divergence_budget)
            over_rel = mr > slo.rel_err_budget
            if over_div or over_rel:
                self._clean_streak[slot] = 0
                if level < top:
                    level += 1
                    self._unit_levels[slot] = level
                    self._rung_div[slot] = 0
                    counters["demotions"] += 1
                    event = {
                        "event": "demoted", "level": level,
                        "unit": ladder[level],
                        "chunk": int(self._chunks_total),
                        "max_rel_err": mr, "divergences": dv,
                    }
                    self._slot_events[slot].append(event)
                    if self._journal is not None:
                        with self._spans.span("journal"):
                            self._journal.demoted(slot, uid, level, ladder[level])
            elif dv:
                # divergent but within budget: hysteresis restarts anyway
                self._clean_streak[slot] = 0
            elif level > 0:
                self._clean_streak[slot] += n
                if (slo.promote_after is not None
                        and int(self._clean_streak[slot]) >= slo.promote_after):
                    level -= 1
                    self._unit_levels[slot] = level
                    self._clean_streak[slot] = 0
                    self._rung_div[slot] = 0
                    counters["promotions"] += 1
                    event = {
                        "event": "promoted", "level": level,
                        "unit": ladder[level],
                        "chunk": int(self._chunks_total),
                    }
                    self._slot_events[slot].append(event)
                    if self._journal is not None:
                        with self._spans.span("journal"):
                            self._journal.promoted(slot, uid, level, ladder[level])

    def _exact_fallback(self, req: Request):
        """The bottom rung of the degradation ladder: serve one request solo
        on the exact, fault-free datapath (greedy), reusing the module-level
        static jit caches.  Returns (tokens, healthy): ``healthy=False`` when
        even the exact path yields non-finite logits (status ``failed``)."""
        ecfg = lm.exact_twin(self.cfg)
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        cache, _ = lm.init_cache(ecfg, 1, self.cache_len, quantized=self.quantized_kv)
        logits, cache = _static_prefill_jit(ecfg)(self.params, cache, prompt)
        last = np.asarray(logits[:, -1].astype(jnp.float32))
        if not np.isfinite(last).all():
            return np.zeros(0, np.int32), False
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        toks, _, _ = _static_gen_jit(ecfg, req.max_new_tokens)(
            self.params, cache, tok, jnp.int32(prompt.shape[1])
        )
        out = np.asarray(toks)[0]
        if self.eos_id is not None:  # slot-path semantics: EOS emitted, then stop
            hits = np.nonzero(out == self.eos_id)[0]
            if hits.size:
                out = out[: hits[0] + 1]
        return out.astype(np.int32), True

    def _shed_victim(self, now: float) -> _Ticket:
        """Pick which queued ticket admission control drops, per
        ``shed_policy`` (see :data:`SHED_POLICIES`)."""
        q = self._queue
        if self.shed_policy == "reject-new":
            return q[-1]
        if self.shed_policy == "evict-latest-deadline":
            def effective_deadline(t):
                r = t.req
                dl = (float("inf") if r.deadline_s is None
                      else r.arrival_s + r.deadline_s)
                return (dl, r.arrival_s, r.uid)
            return max(q, key=effective_deadline)
        # shed-by-slo: smallest deadline slack loses (it is least likely to
        # meet its SLO anyway); deadline-free requests have infinite slack
        # and shed newest-first so old deadline-free work is not starved
        def slack(t):
            r = t.req
            s = (float("inf") if r.deadline_s is None
                 else (r.arrival_s + r.deadline_s) - now)
            return (s, -r.arrival_s, -r.uid)
        return min(q, key=slack)

    def run(self, requests=(), *, deadline_s: float = 600.0,
            max_chunks: Optional[int] = None) -> dict:
        """Serve ``requests`` (admitted no earlier than their ``arrival_s``,
        measured on the wall clock from call start) until all complete.
        Returns {uid: Completion} — one per request, each with a structured
        ``status`` — plus aggregate stats and fault/recovery counters under
        ``self.stats``; nothing raises mid-batch.  On an engine built by
        :meth:`resume`, restored work is served first — ``requests`` may be
        empty.

        Deadlines degrade gracefully rather than raising: when the global
        ``deadline_s`` expires, in-flight requests are evicted with their
        partial tokens and still-queued ones with empty tokens (status
        ``evicted``, ``admitted_s=-1.0`` if never admitted).  A request's own
        ``deadline_s`` (relative to its arrival) evicts just that request.

        With detectors on, a slot whose chunk tripped the health checks
        (non-finite logits, or max |logit| above ``logit_sentinel``) is
        quarantined: its emissions are discarded and the request re-queued
        for up to ``quarantine_retries`` fresh approximate-path attempts,
        after which it is re-served on the exact datapath (status
        ``degraded``; ``failed`` if even that is unhealthy).

        Overload: with ``max_queue=`` set, the due-request queue is bounded —
        once arrivals outrun capacity, the configured ``shed_policy`` picks
        tickets to drop with status ``rejected`` (empty tokens,
        ``admitted_s=-1.0``) instead of letting the queue and tail latency
        grow without bound.  Quarantine re-queues bypass the bound check on
        entry (they already held a slot) but compete like everyone else
        afterwards.

        Crash consistency: with a ``journal``, every request's ``accepted``
        record is fsynced BEFORE any device work and every terminal status
        writes a ``finished`` record (the durable completion); with
        ``snapshot_every_chunks=``, the full serving state autosaves at that
        chunk cadence.  ``max_chunks=`` is the chaos hook: stop dead at that
        decode-chunk boundary — no draining, no terminal records for
        in-flight work — exactly what SIGKILL leaves behind
        (tests/launch/test_engine_snapshot.py, tools/kill_resume_smoke.py).

        Host time: the run is the span ``engine.run``, whose start is the
        clock's zero (``arrival_s``, ``admitted_s``, ``first_token_s`` and
        telemetry ``t`` are seconds after it).  ``self.stats`` carries
        ``host_<span>_s`` for each of :data:`HOST_SPANS`, and
        ``longest_turn_s`` / ``longest_turn_chunk``: the longest host turn
        between a chunk's sync and the next chunk's dispatch with work live,
        and the telemetry ``chunk`` whose boundary began it (0.0 and -1.0
        when no such turn ran).
        """
        requests = list(requests)
        for req in requests:
            # validate the whole trace BEFORE serving starts: a bad request
            # surfacing mid-trace would abandon every in-flight completion
            self._validate(req)
        if self._journal is not None:
            # write-ahead: the intake records are durable before any of
            # these requests can touch a slot
            for req in sorted(requests, key=lambda r: (r.arrival_s, r.uid)):
                self._journal.accepted(req)
        self._arrivals.extend(
            _Ticket(r) for r in sorted(requests, key=lambda r: (r.arrival_s, r.uid))
        )
        queue, arrivals = self._queue, self._arrivals
        done: dict[int, Completion] = {}
        counters = {
            "faults_detected": 0,
            "quarantine_retries": 0,
            "exact_fallbacks": 0,
            "deadline_evictions": 0,
            "shed_rejections": 0,
            "canary_checks": 0,
            "canary_divergences": 0,
            "canary_max_rel_err": 0.0,
            "demotions": 0,
            "promotions": 0,
        }
        spans = self._spans = HostSpans()
        with spans.span("run") as t0, spans.collect_gc():
            decode_chunks = 0
            if self.spec is not None:
                spec_acc0 = self._spec_acc_total
                spec_steps0 = self._spec_steps_total
            peak_queue_depth = len(queue)
            queue_depth_sum = 0
            queue_depth_samples = 0
            telemetry_tokens = 0
            expired = False
            killed = False
            # a host turn: from one chunk's sync to the next chunk's dispatch,
            # while work is live (a turn that idles the pool is not counted)
            turn_from = None
            longest_turn, longest_turn_chunk = 0.0, -1

            def finish(req, tokens, status, now, admitted_s, trips=0, slot=None):
                first_s = -1.0
                if slot is not None and len(tokens):
                    first_s = self._first_s[slot]
                audit = {}
                if slot is not None and self._ladder is not None:
                    audit = dict(
                        unit_final=self._ladder[int(self._unit_levels[slot])],
                        canary_checks=int(self._slot_canary_checks[slot]),
                        canary_divergences=int(self._slot_canary_div[slot]),
                        unit_trips=tuple(self._slot_events[slot]),
                    )
                if slot is not None and self.spec is not None:
                    audit.update(
                        spec_steps=int(self._slot_spec_steps[slot]),
                        spec_accepted=int(self._slot_spec_acc[slot]),
                    )
                done[req.uid] = Completion(
                    uid=req.uid,
                    prompt_len=len(req.prompt),
                    tokens=np.asarray(tokens, np.int32),
                    arrival_s=req.arrival_s,
                    admitted_s=admitted_s,
                    finished_s=now,
                    status=status,
                    trips=trips,
                    first_token_s=first_s,
                    **audit,
                )
                if self._journal is not None:
                    with spans.span("journal"):
                        self._journal.finished(req.uid, status, done[req.uid].tokens)

            def overdue(req, now):
                return req.deadline_s is not None and now > req.arrival_s + req.deadline_s

            while queue or arrivals or any(o is not None for o in self._owner):
                now = time.perf_counter() - t0
                if now > deadline_s:
                    expired = True
                    break
                if max_chunks is not None and decode_chunks >= max_chunks:
                    killed = True  # chaos hook: die at the chunk boundary
                    break
                # accepted arrivals come due; the bound is enforced below, after
                # free slots have drained the queue
                while arrivals and arrivals[0].req.arrival_s <= now:
                    queue.append(arrivals.popleft())
                # evict overdue queued requests before they can take a slot
                if any(overdue(t.req, now) for t in queue):
                    kept = deque()
                    for t in queue:
                        if overdue(t.req, now):
                            counters["deadline_evictions"] += 1
                            finish(t.req, [], "evicted", now, -1.0, t.trips)
                        else:
                            kept.append(t)
                    queue.clear()
                    queue.extend(kept)
                # admit queued arrivals into free slots
                for slot in range(self.num_slots):
                    if self._owner[slot] is None and queue:
                        t = queue.popleft()
                        self._admit(t.req, slot, now, trips=t.trips)
                        if self._journal is not None:
                            with spans.span("journal"):
                                self._journal.admitted(t.req.uid, slot)
                # overload admission control: requests that could not get a slot
                # wait in a BOUNDED queue; beyond the bound the shed policy picks
                # who is turned away (status "rejected")
                while self.max_queue is not None and len(queue) > self.max_queue:
                    victim = self._shed_victim(now)
                    queue.remove(victim)
                    counters["shed_rejections"] += 1
                    finish(victim.req, [], "rejected", now, -1.0, victim.trips)
                depth = len(queue)
                peak_queue_depth = max(peak_queue_depth, depth)
                queue_depth_sum += depth
                queue_depth_samples += 1
                if not any(o is not None for o in self._owner):
                    # pool idle: sleep until the next arrival
                    turn_from = None
                    if arrivals:
                        with spans.span("wait_arrival"):
                            time.sleep(max(0.0, arrivals[0].req.arrival_s - now))
                    continue
                if turn_from is not None:
                    turn = time.perf_counter() - turn_from
                    if turn > longest_turn:
                        longest_turn, longest_turn_chunk = turn, self._chunks_total
                toks, emitted, active, bad, mx, cc, cd, cmr, _crs = (
                    self._decode_chunk()
                )
                turn_from = time.perf_counter()
                decode_chunks += 1
                self._chunks_total += 1
                now = chunk_t = turn_from - t0
                with spans.span("bookkeeping"):
                    if self.slo is not None and self._canary_stride:
                        # ladder bookkeeping first, so requests finishing this chunk
                        # carry their final rung + canary trail in the Completion
                        self._slo_update(cc, cd, cmr, counters)
                    for slot in range(self.num_slots):
                        req = self._owner[slot]
                        if req is None:
                            continue
                        # NaN mx compares False, but `bad` has latched in that case
                        tripped = self.detectors and (
                            bool(bad[slot]) or float(mx[slot]) > self.logit_sentinel
                        )
                        if tripped:
                            # quarantine: drop the slot (its device row decays
                            # harmlessly — row isolation + budget exhaustion) and
                            # discard every emission; the retry starts clean
                            counters["faults_detected"] += 1
                            trips = self._trips[slot] + 1
                            self._owner[slot] = None
                            if trips <= self.quarantine_retries:
                                counters["quarantine_retries"] += 1
                                queue.appendleft(_Ticket(req, trips))
                            else:
                                counters["exact_fallbacks"] += 1
                                tokens, healthy = self._exact_fallback(req)
                                now = time.perf_counter() - t0
                                # the fallback delivers all its tokens at once
                                self._first_s[slot] = now
                                finish(req, tokens, "degraded" if healthy else "failed",
                                       now, self._admitted_s[slot], trips, slot=slot)
                            continue
                        new = toks[slot][emitted[slot]].tolist()
                        if new and not self._emitted[slot]:
                            self._first_s[slot] = chunk_t
                        self._emitted[slot].extend(new)
                        if not active[slot]:  # finished: free the slot for reuse
                            finish(req, self._emitted[slot], "ok", now,
                                   self._admitted_s[slot], self._trips[slot], slot=slot)
                            self._owner[slot] = None
                        elif overdue(req, now):  # per-request deadline: partial out
                            counters["deadline_evictions"] += 1
                            finish(req, self._emitted[slot], "evicted", now,
                                   self._admitted_s[slot], self._trips[slot], slot=slot)
                            self._owner[slot] = None
                if self._journal is not None:
                    live = [
                        (o.uid, len(self._emitted[s]))
                        for s, o in enumerate(self._owner)
                        if o is not None
                    ]
                    if live:
                        with spans.span("journal"):
                            self._journal.progress(live)
                if self._telemetry is not None:
                    with spans.span("telemetry"):
                        n_active = sum(o is not None for o in self._owner)
                        if self._ladder is not None:
                            hist: dict = {}
                            for lv in self._unit_levels:
                                name = self._ladder[int(lv)]
                                hist[name] = hist.get(name, 0) + 1
                        else:
                            hist = {self.cfg.sqrt_unit: self.num_slots}
                        chunk_tokens = int(np.sum(emitted))
                        telemetry_tokens += chunk_tokens
                        self._telemetry.emit({
                            "kind": "chunk",
                            "t": now,
                            "chunk": int(self._chunks_total),
                            "active_slots": n_active,
                            "slot_occupancy": n_active / self.num_slots,
                            "queue_depth": depth,
                            "tokens": chunk_tokens,
                            "tok_s": telemetry_tokens / max(now, 1e-9),
                            "canary_checks": int(np.sum(cc)),
                            "canary_divergences": int(np.sum(cd)),
                            "canary_max_rel": float(np.max(cmr)) if len(cmr) else 0.0,
                            "unit_levels": hist,
                        })
                # autosave at the chunk boundary, after the host bookkeeping
                # above — the durable cut the kill-and-resume chaos suite
                # proves exactly-once recovery against
                if (self.snapshot_every_chunks is not None
                        and decode_chunks % self.snapshot_every_chunks == 0):
                    with spans.span("snapshot"):
                        self.snapshot()
            if expired:
                now = time.perf_counter() - t0
                for slot in range(self.num_slots):
                    req = self._owner[slot]
                    if req is None:
                        continue
                    counters["deadline_evictions"] += 1
                    finish(req, self._emitted[slot], "evicted", now,
                           self._admitted_s[slot], self._trips[slot], slot=slot)
                    self._owner[slot] = None
                for t in list(queue) + list(arrivals):
                    counters["deadline_evictions"] += 1
                    finish(t.req, [], "evicted", now, -1.0, t.trips)
                queue.clear()
                arrivals.clear()
        # after the run span closes, so the host_*_s stats add up to at most it
        makespan = time.perf_counter() - t0
        total_tokens = sum(len(c.tokens) for c in done.values())
        by_status = {s: 0 for s in STATUSES}
        for c in done.values():
            by_status[c.status] += 1
        self.stats = {
            "makespan_s": makespan,
            "total_tokens": total_tokens,
            "tok_s": total_tokens / max(makespan, 1e-9),
            "decode_chunks": decode_chunks,
            "n_requests": len(done),
            "deadline_expired": expired,
            "killed": killed,
            "dispatch_faults": self._dispatch_faults,
            "dispatch_retries": self._dispatch_retries,
            "peak_queue_depth": peak_queue_depth,
            "mean_queue_depth": (
                queue_depth_sum / queue_depth_samples
                if queue_depth_samples else 0.0
            ),
            "snapshots_written": self._snapshots_written,
            "journal_replays": self._journal_replays,
            "telemetry": (None if self._telemetry is None
                          else str(self._telemetry.path)),
            **counters,
            **{f"n_{s}": by_status[s] for s in STATUSES},
            **{f"host_{k}_s": spans.seconds.get(k, 0.0) for k in HOST_SPANS},
            "longest_turn_s": longest_turn,
            "longest_turn_chunk": float(longest_turn_chunk),
        }
        if self.spec is not None:
            acc = self._spec_acc_total - spec_acc0
            steps = self._spec_steps_total - spec_steps0
            self.stats.update(
                spec_steps=steps,
                spec_accepted=acc,
                # drafts accepted per speculative step (0..k) and the same
                # as a fraction of drafts proposed (0..1)
                accepted_per_step=acc / max(steps, 1),
                acceptance_rate=acc / max(steps * self.spec.k, 1),
            )
        return done


# jitted lock-step solvers shared across run_static_baseline calls (keyed by
# the frozen ModelConfig; jax's own cache then specializes per shape) — a
# fresh jax.jit per call would re-trace inside the timed region on replays
_STATIC_PREFILL_JITS: dict = {}
_STATIC_GEN_JITS: dict = {}


def _static_prefill_jit(cfg):
    if cfg not in _STATIC_PREFILL_JITS:
        _STATIC_PREFILL_JITS[cfg] = jax.jit(
            lambda p, c, t: lm.prefill(p, cfg, c, t, last_logit_only=True),
            donate_argnums=(1,),
        )
    return _STATIC_PREFILL_JITS[cfg]


def _static_gen_jit(cfg, g_len):
    key = (cfg, g_len)
    if key not in _STATIC_GEN_JITS:
        _STATIC_GEN_JITS[key] = jax.jit(
            lambda p, c, t, sp: lm.generate_scan(p, cfg, c, t, sp, g_len),
            donate_argnums=(1, 2),
        )
    return _STATIC_GEN_JITS[key]


def run_static_baseline(params, cfg: ModelConfig, requests, *,
                        num_slots: int = 4, quantized_kv: bool = False,
                        warmed: Optional[set] = None) -> tuple[dict, dict]:
    """The PR-3 lock-step scheduler as a baseline: requests are served in
    arrival-order groups of ``num_slots``; each group waits for its last
    arrival, right-pads every prompt to the group max and decodes the group
    max ``max_new_tokens`` for every slot — the padding / idle-slot waste
    continuous batching removes.  Only each request's own ``max_new_tokens``
    emissions count as useful tokens.  Returns ({uid: Completion}, stats).

    This is a throughput yardstick, not an output-correct server: a request
    shorter than its group's max prompt decodes from the right-padded
    prompt, so its ``Completion.tokens`` are the padded continuation and do
    NOT match a solo run of that request (the engine side does — that is
    the point of the comparison).

    ``warmed`` (a set) makes the jitted prefill/decode shapes compile off the
    clock on first sight across calls; the jit wrappers themselves are cached
    module-wide per config, so replays never re-trace on the clock.
    """
    reqs = sorted(requests, key=lambda r: (r.arrival_s, r.uid))
    groups = [reqs[i : i + num_slots] for i in range(0, len(reqs), num_slots)]
    done: dict[int, Completion] = {}
    warmed = warmed if warmed is not None else set()
    prefill_j = _static_prefill_jit(cfg)

    def solve(group, g_len):
        b = len(group)
        s_max = max(len(r.prompt) for r in group)
        prompts = np.zeros((b, s_max), np.int32)
        for i, r in enumerate(group):
            prompts[i, : len(r.prompt)] = r.prompt  # lock-step: pad to batch max
        cache, _ = lm.init_cache(cfg, b, s_max + g_len, quantized=quantized_kv)
        cache = jax.block_until_ready(cache)
        logits, cache = prefill_j(params, cache, jnp.asarray(prompts))
        tok = jnp.argmax(logits[:, -1:], axis=-1)
        toks, _, _ = _static_gen_jit(cfg, g_len)(params, cache, tok, jnp.int32(s_max))
        return np.asarray(jax.block_until_ready(toks))

    t0 = time.perf_counter()
    prev_end = 0.0
    for group in groups:
        g_len = max(r.max_new_tokens for r in group)
        shape = (len(group), max(len(r.prompt) for r in group), g_len)
        if shape not in warmed:  # compile off the clock
            t_saved = time.perf_counter()
            solve(group, g_len)
            warmed.add(shape)
            t0 += time.perf_counter() - t_saved
        start = max(prev_end, max(r.arrival_s for r in group))
        # the batch cannot form before its last member arrives
        now = time.perf_counter() - t0
        if now < start:
            time.sleep(start - now)
        toks = solve(group, g_len)
        end = time.perf_counter() - t0
        prev_end = end
        for i, r in enumerate(group):
            done[r.uid] = Completion(
                uid=r.uid,
                prompt_len=len(r.prompt),
                tokens=toks[i, : r.max_new_tokens],
                arrival_s=r.arrival_s,
                admitted_s=start,
                finished_s=end,  # lock-step: the whole group finishes together
            )
    makespan = time.perf_counter() - t0
    total_tokens = sum(len(c.tokens) for c in done.values())
    stats = {
        "makespan_s": makespan,
        "total_tokens": total_tokens,
        "tok_s": total_tokens / max(makespan, 1e-9),
        "n_groups": len(groups),
        "n_requests": len(done),
    }
    return done, stats

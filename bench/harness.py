"""One run of one cell: build, warm up, serve the open-loop trace, measure,
check against the reference.

The system under test is the program's ``Engine`` (src/repro/launch/
engine.py), fed weights that bench/weights.py draws from the seed and
requests that bench/traffic/generator.py draws from the mix.  The run:

1. draws the weights on the device in one jitted call: on the mesh that
   the configuration's ``engine.mesh`` names, straight onto the shardings
   the engine serves them from;
2. builds one ``Engine`` (on that mesh, if any) and warms up the
   prompt-length buckets this trace uses and one decode chunk (compiled
   programs come from JAX's persistent cache after a checkout's first run);
3. serves the trace through ``Engine.run``; a ``Telemetry`` that keeps each
   chunk boundary's time in memory is the only thing the engine is handed
   besides the requests;
4. reads the peak memory of the fullest device, frees the program's state
   on every device, and runs the plain reference, on the first device, over
   a sample of the served requests (bench/check.py).

With ``trace=True`` the profiler records a few seconds in mid-window, and
the benchmark's own ``TraceAnnotation`` spans mark what the host was doing:
``bench.run`` around ``Engine.run``, ``bench.admit#<prompt len>`` around
each admission, ``bench.decode_chunk`` around each decode chunk (dispatch
and the one host sync), ``bench.chunk_end#<chunk>`` at each chunk boundary.
"""
from __future__ import annotations

import gc
import math
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import spec
from bench.traffic.generator import make_trace

__all__ = ["chunk_clock", "program_config", "program_mesh", "program_weights",
           "serve", "first_token_times", "end_to_end", "TRACE_DIR"]

TRACE_DIR = spec.ROOT / ".bench_traces"

def _src_on_path(root: Path) -> None:
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def program_config(config: dict, arch=None):
    """The program's ModelConfig for a configuration file, checked against
    the file's model block by the architecture module's ``check_program``
    (``arch``; without it the dense default, bench/archs/dense.py): a width,
    block type or size that differs is an error."""
    from bench.archs import dense
    from repro.configs import get_config

    prog = config["program"]
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in prog.get("overrides", {}).items()}
    cfg = get_config(prog["arch"], **over).validate()
    m = config["model"]
    try:
        (arch or dense).check_program(m, cfg)
    except ValueError as e:
        raise ValueError(f"{config['name']}: {e}") from None
    if cfg.act_dtype != m["torch_dtype"]:
        raise ValueError(f"{config['name']}: dtype {cfg.act_dtype} != {m['torch_dtype']}")
    return cfg


def program_mesh(config: dict):
    """The device mesh that the configuration's ``engine.mesh`` names (axis
    name -> size, in the program's axis names), over the first devices; None
    without one, and the program then runs on one device."""
    axes = config["engine"].get("mesh")
    if not axes:
        return None
    from repro.launch.mesh import make_mesh_for

    return make_mesh_for(tuple(axes.values()), tuple(axes))


def program_weights(cfg, seed: int, mesh=None, rules=None):
    """The program's weights drawn from the seed in one jitted call
    (bench/weights.py), with the architecture module's weight ``rules``
    before the defaults.  On a mesh each leaf lands on the sharding that
    ``Engine`` commits it to (its default rules, ``serve_rules``), so the
    engine's own placement moves nothing and no second copy is made."""
    import jax

    from bench import weights
    from repro.models import lm

    abstract, specs = lm.init(cfg, jax.random.PRNGKey(0), abstract=True)
    shardings = None
    if mesh is not None:
        from repro.distributed.sharding import serve_rules, shardings_for

        shardings = shardings_for(specs, mesh, serve_rules(cfg, mesh), abstract)
    return weights.program_params(seed, abstract, cfg.n_layers, shardings, rules)


def chunk_clock(on_chunk=None):
    """The in-memory telemetry the engine is handed: it keeps
    ``(t, chunk, tokens)`` of every chunk boundary, on the engine's own
    clock, and calls ``on_chunk(record)`` if given."""
    from repro.launch.telemetry import Telemetry

    class ChunkClock(Telemetry):
        def __init__(self):
            self.path = "<memory>"
            self.records: list = []

        def emit(self, record):
            self.records.append((record["t"], record["chunk"], record["tokens"]))
            if on_chunk is not None:
                on_chunk(record)
            return record

        def close(self):
            pass

    return ChunkClock()


def first_token_times(completions: dict, records: list) -> dict:
    """uid -> (first-token time, chunk id).  A request admitted at a loop
    turn emits its first token in the decode chunk of that same turn, so its
    first token reaches the host at the first chunk boundary after
    ``admitted_s`` (tests/bench/test_bench_clock.py checks this against
    ``Completion.finished_s``)."""
    times = np.asarray([r[0] for r in records])
    out = {}
    for uid, c in completions.items():
        if c.admitted_s < 0 or not len(c.tokens):
            continue
        i = int(np.searchsorted(times, c.admitted_s, side="right"))
        if i < len(records):
            out[uid] = (records[i][0], records[i][1])
    return out


def _pct(x, q) -> float:
    return float(np.percentile(np.asarray(x, np.float64), q)) if len(x) else math.inf


def latencies(completions: dict, first: dict) -> tuple[list, list]:
    """Per-request TTFT (ms; a request never served counts as infinite) and
    TPOT (ms, requests with two or more tokens)."""
    ttft, tpot = [], []
    for uid, c in completions.items():
        if uid not in first:
            ttft.append(math.inf)
            continue
        ft = first[uid][0]
        ttft.append((ft - c.arrival_s) * 1e3)
        if len(c.tokens) >= 2:
            tpot.append((c.finished_s - ft) / (len(c.tokens) - 1) * 1e3)
    return ttft, tpot


def end_to_end(completions: dict, first: dict, records: list, seconds: float) -> dict:
    """The end-to-end metrics of one run, in their units (setup_s apart).

    ``tok_s`` counts the tokens that reached the host at chunk boundaries
    inside the window ``[0, seconds]`` of due times, over the window: the
    rate the system served while the load was offered.  (Over the drain
    as well it would mostly measure how long the last few requests were.)"""
    tokens = sum(n for t, _, n in records if t <= seconds)
    ttft, tpot = latencies(completions, first)
    return {"tok_s": tokens / seconds, "ttft_p90_ms": _pct(ttft, 90),
            "tpot_p90_ms": _pct(tpot, 90)}


def enable_compile_cache(root: Path) -> None:
    """JAX's persistent cache, where the program keeps it: the checkout's
    ``.cache/jax``, or ``JAX_COMPILATION_CACHE_DIR`` when set."""
    _src_on_path(root)
    import jax
    from repro.local_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class _CompileCounter:
    """Counts XLA compilations while ``on`` (there should be none inside
    the measured window).  One listener per process."""

    n = 0
    on = False
    _installed = False

    @classmethod
    def start(cls):
        import jax

        if not cls._installed:
            def listen(event, *_a, **_k):
                if cls.on and event == "/jax/core/compile/backend_compile_duration":
                    cls.n += 1

            jax.monitoring.register_event_duration_secs_listener(listen)
            cls._installed = True
        cls.n, cls.on = 0, True

    @classmethod
    def stop(cls) -> int:
        cls.on = False
        return cls.n


def serve(cell: dict, seed: int, seconds: float, *, trace: bool, t_start: float,
          log=print) -> dict:
    """Everything a run measures, before the reference check.  Returns
    {"completions", "records", "trace_data", "trace_opened_s", "setup_s",
     "memory_peak_bytes", "requests", "compiles_in_window", "engine_stats"}."""
    _src_on_path(cell["root"])
    import jax
    from jax.profiler import TraceAnnotation

    from repro.launch.engine import Engine, Request

    # op metadata (the named scopes the trace readers look for) goes into a
    # program's cache key, so a cached program carries this build's scopes
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    config, mix = cell["config"], cell["traffic"]
    cfg = program_config(config, cell["arch"])
    mesh = program_mesh(config)
    params = program_weights(cfg, seed, mesh, cell["arch"].RULES)
    jax.block_until_ready(params)
    log(f"weights drawn: {time.perf_counter() - t_start:.2f} s since start")

    state = {"open": False, "closed": False, "admits": 0}
    trace_dir = TRACE_DIR / f"{cell['name']}-{seed}"
    # a few seconds in mid-window, held open until an admission has run in
    # it (so every per-layer metric finds work to read), at most 15 s
    t_open = 0.4 * seconds
    t_close = t_open + min(4.0, 0.2 * seconds)

    def on_chunk(rec):
        if not trace:
            return
        if state["open"] and not state["closed"]:
            with TraceAnnotation(f"bench.chunk_end#{rec['chunk']}"):
                pass
            if (rec["t"] >= t_close and state["admits"]) or rec["t"] >= t_open + 15.0:
                with TraceAnnotation("bench.trace_close"):
                    pass
                jax.profiler.stop_trace()
                state["closed"] = True
        elif not state["open"] and rec["t"] >= t_open:
            state["opened_s"] = rec["t"]
            jax.profiler.start_trace(str(trace_dir))
            state["open"] = True
            with TraceAnnotation("bench.trace_open"):
                pass

    clock = chunk_clock(on_chunk)
    eng_cfg = config["engine"]
    eng = Engine(params, cfg, num_slots=eng_cfg["num_slots"],
                 cache_len=eng_cfg["cache_len"], chunk=eng_cfg["chunk"],
                 telemetry=clock, mesh=mesh)
    reqs = make_trace(mix, seconds, seed, cfg.vocab)
    eng.warmup(sorted({len(r.prompt) for r in reqs}))
    jax.block_until_ready(eng._cache)
    clock.records.clear()
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        _annotate(eng, TraceAnnotation, state)
    requests = [Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                        arrival_s=r.arrival_s) for r in reqs]
    _CompileCounter.start()
    setup_s = time.perf_counter() - t_start
    if trace:
        with TraceAnnotation("bench.run"):
            done = eng.run(requests, deadline_s=seconds + mix["drain_s"])
    else:
        done = eng.run(requests, deadline_s=seconds + mix["drain_s"])
    compiles = _CompileCounter.stop()
    if trace and state["open"] and not state["closed"]:
        with TraceAnnotation("bench.trace_close"):
            pass
        jax.profiler.stop_trace()
        state["closed"] = True
    devices = jax.devices()[:1] if mesh is None else list(mesh.devices.flat)
    out = {
        "completions": done,
        "records": list(clock.records),
        "setup_s": setup_s,
        "memory_peak_bytes": max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                                 for d in devices),
        "requests": reqs,
        "compiles_in_window": compiles,
        "engine_stats": dict(eng.stats),
        "trace_data": None,
        # engine clock at which the profiler was started: starting and
        # stopping it stall the host, so later waits measure the profiler
        "trace_opened_s": state.get("opened_s"),
    }
    if trace and state["open"]:
        from bench.trace import read_xplane

        out["trace_data"] = read_xplane(str(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
    del eng, params
    gc.collect()
    log("bytes in use after freeing the program's state, by device: "
        + " ".join(str((d.memory_stats() or {}).get("bytes_in_use")) for d in devices))
    return out


def _annotate(eng, annotation, state: dict) -> None:
    """Wrap the engine's admission and decode-chunk calls in host spans, on
    this instance only, counting admissions made while the trace is open."""
    admit, chunk = eng._admit, eng._decode_chunk

    def traced_admit(req, slot, now, trips=0):
        if state["open"] and not state["closed"]:
            state["admits"] += 1
        with annotation(f"bench.admit#{len(req.prompt)}"):
            return admit(req, slot, now, trips)

    def traced_chunk():
        with annotation("bench.decode_chunk"):
            return chunk()

    eng._admit = traced_admit
    eng._decode_chunk = traced_chunk

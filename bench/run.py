"""The benchmark's command: one run of one cell.

    python3 -m bench.run --workload qwen3-4b.chat-steady --seed 7 \\
        --seconds 45 --trace 0

Runs from a checkout's root, on the chip that JAX finds there.  It fails,
and prints no result, when JAX finds no TPU, fewer chips than the cell asks
for, or a chip missing from ``bench/peaks.json``.  The last line of
standard output is the result: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics with ``--trace 0``, its per-layer
ones with ``--trace 1``), ``device``, with ``--trace 1`` a ``breakdown``,
and last ``checked``: each number compared, beside its limit.  The same
numbers end standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec  # noqa: E402


class NoChip(RuntimeError):
    """The machine lacks the chip the cell needs."""


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def peaks_for(kind: str, root: Path = ROOT) -> dict:
    with open(Path(root) / "bench" / "peaks.json", encoding="utf-8") as f:
        table = json.load(f)["chips"]
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


def check_devices(devices, chips: int, root: Path = ROOT) -> tuple[dict, dict]:
    """The result's device record and the chip's peaks; raises NoChip."""
    d = devices[0]
    if d.platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {d.platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devices)}")
    record = {"platform": d.platform, "kind": d.device_kind, "count": len(devices)}
    return record, peaks_for(d.device_kind, root)


def read_per_layer(cell: dict, served: dict, first: dict, peaks: dict) -> dict:
    """Each per-layer metric of the cell, by its reader
    ``bench/metrics/<name>.py``; a reader that finds nothing to read returns
    None and its metric is left out.  A reader is handed the run's
    completions and first-token times, the reduced trace (None without
    one), the engine-clock time the profiler started (None without a
    trace), the configuration's model and engine blocks, the work counts of
    its architecture (``work``: ``cell["arch"].work(model)``), the number
    of chips the program runs on, and their peaks: one chip's times that
    number, so a share of a roofline or of the peak is of all of them."""
    n = spec.chips(cell["config"])
    ctx = {
        "completions": served["completions"],
        "first": first,
        "records": served["records"],
        "trace": served["trace_data"],
        "trace_opened_s": served.get("trace_opened_s"),
        "model": cell["config"]["model"],
        "engine": cell["config"]["engine"],
        "work": cell["arch"].work(cell["config"]["model"]),
        "chips": n,
        "peaks": {k: v * n for k, v in peaks.items()},
    }
    out = {}
    for m in cell["per_layer"]:
        v = spec.metric_reader(m["name"], cell["root"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def _log_program_trace(trace: dict, engine: dict, log) -> None:
    """The traced window's device idle time by the engine span the host was
    in, and the decode step by named scope with its unscoped share."""
    from bench import program_trace

    idle = program_trace.idle_by_span(trace)
    if idle is not None:
        log("device idle by engine span (s): "
            + " ".join(f"{k} {v}" for k, v in sorted(idle.items(), key=lambda kv: -kv[1])))
    split = program_trace.decode_split({"trace": trace, "engine": engine})
    if split is not None:
        log("decode step by scope (ms): "
            + " ".join(f"{k or 'unscoped'} {v}" for k, v in split.items())
            + f"; unscoped share {split[None] / sum(split.values())}")


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, device: dict,
             peaks: dict, t_start: float, *, log=_log) -> dict:
    """One run, from the weights to the result dict (without printing)."""
    from bench import check, harness

    served = harness.serve(cell, seed, seconds, trace=trace, t_start=t_start, log=log)
    done = served["completions"]
    first = harness.first_token_times(done, served["records"])
    failed = check.not_served(served["requests"], done)
    ttft, tpot = harness.latencies(done, first)
    log("latency percentiles (p50 p75 p90): ttft_ms "
        + " ".join(f"{harness._pct(ttft, q):.1f}" for q in (50, 75, 90)) + " tpot_ms "
        + " ".join(f"{harness._pct(tpot, q):.2f}" for q in (50, 75, 90)))
    log(f"served {len(done)} requests, {failed} not in full; setup "
        f"{served['setup_s']:.2f} s; compiles in window "
        f"{served['compiles_in_window']}; engine stats "
        f"{json.dumps({k: v for k, v in served['engine_stats'].items() if isinstance(v, (int, float))})}")
    if trace:
        metrics = read_per_layer(cell, served, first, peaks)
        if served["trace_data"] is not None:
            _log_program_trace(served["trace_data"], cell["config"]["engine"], log)
    else:
        e2e = harness.end_to_end(done, first, served["records"], seconds)
        e2e["setup_s"] = served["setup_s"]
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device = dict(device, memory_peak_bytes=served["memory_peak_bytes"])
    traced = trace and served["trace_data"] is not None and served["trace_data"]["devices"]
    if traced:
        from bench import trace as tr

        device["busy_s"] = tr.busy_s(served["trace_data"])
        device["window_s"] = tr.window_s(served["trace_data"])

    t_ref = time.perf_counter()
    prompts = {r.uid: r.prompt for r in served["requests"]}
    ref = cell["arch"].Reference(cell["config"]["model"], seed)
    readings = check.run_check(ref, done, prompts, seed, cell["traffic"], ref.Q_BLOCK)
    readings["not_served"] = failed
    log(f"reference over {readings['checked_requests']} requests, "
        f"{readings['checked_tokens']} tokens: {time.perf_counter() - t_ref:.2f} s")
    correct, compared = check.verdict(readings, cell["limits"])
    result = {
        "correct": correct,
        "attempted": len(served["requests"]),
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if traced:
        result["breakdown"] = tr.breakdown(served["trace_data"])
    result["checked"] = compared
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.cell(args.workload)
    import jax

    try:
        device, peaks = check_devices(jax.devices(), cell["entry"]["chips"])
    except NoChip as e:
        _log(f"refused: {e}")
        return 3
    from bench import harness

    harness.enable_compile_cache(cell["root"])
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), device,
                      peaks, T_START)
    for name, v in result["checked"].items():
        _log(f"{name} {v['value']} limit {v['limit']}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

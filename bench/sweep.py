"""Find a cell's knee: serve its mix at several offered rates, on the chip.

    python3 -m bench.sweep --workload qwen3-4b.chat-steady --seed 11 \\
        --seconds 30 --rates 2.5,3,3.5,4

One process, one engine, one set of weights; each rate serves a fresh trace
of the cell's mix with only ``rate_rps`` changed.  The knee is the highest
rate at which the queue does not grow over the window (requests due in the
last quarter wait for a slot about as long as those in the first) and every
request drains within the mix's cap.  The cell's mix file then fixes 0.8 of
it.  Prints one JSON line per rate.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from bench import harness, spec
from bench.run import NoChip, check_devices


def quarter_waits(done: dict, seconds: float) -> tuple[float, float]:
    """Mean admission wait (s) of requests due in the first and last
    quarter of the window."""
    first, last = [], []
    for c in done.values():
        if c.admitted_s < 0:
            continue
        w = c.admitted_s - c.arrival_s
        if c.arrival_s < seconds / 4:
            first.append(w)
        elif c.arrival_s >= 3 * seconds / 4:
            last.append(w)
    return (float(np.mean(first)) if first else 0.0,
            float(np.mean(last)) if last else 0.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    cell = spec.cell(args.workload)
    harness._src_on_path(cell["root"])
    import jax

    try:
        check_devices(jax.devices(), cell["entry"]["chips"])
    except NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 3
    harness.enable_compile_cache(cell["root"])
    from bench.traffic.generator import make_trace
    from repro.launch.engine import Engine, Request

    cfg = harness.program_config(cell["config"], cell["arch"])
    mesh = harness.program_mesh(cell["config"])
    params = harness.program_weights(cfg, args.seed, mesh, cell["arch"].RULES)
    clock = harness.chunk_clock()
    e = cell["config"]["engine"]
    eng = Engine(params, cfg, num_slots=e["num_slots"], cache_len=e["cache_len"],
                 chunk=e["chunk"], telemetry=clock, mesh=mesh)
    mix = cell["traffic"]
    for rate in (float(r) for r in args.rates.split(",")):
        m = dict(mix, arrival=dict(mix["arrival"], rate_rps=rate))
        reqs = make_trace(m, args.seconds, args.seed, cfg.vocab)
        eng.warmup(sorted({len(r.prompt) for r in reqs}))
        clock.records.clear()
        t = time.perf_counter()
        done = eng.run([Request(uid=r.uid, prompt=r.prompt, max_new_tokens=r.max_new_tokens,
                                arrival_s=r.arrival_s) for r in reqs],
                       deadline_s=args.seconds + mix["drain_s"])
        wall = time.perf_counter() - t
        first = harness.first_token_times(done, clock.records)
        e2e = harness.end_to_end(done, first, clock.records, args.seconds)
        w0, w1 = quarter_waits(done, args.seconds)
        cut = sum(c.status != "ok" for c in done.values())
        print(json.dumps({"rate_rps": rate, "requests": len(reqs), "cut": cut,
                          "wall_s": wall, **e2e, "wait_first_q_s": w0,
                          "wait_last_q_s": w1,
                          "mean_queue": eng.stats["mean_queue_depth"],
                          "peak_queue": eng.stats["peak_queue_depth"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Scheduler (``Engine.run``): how long requests wait for a slot.

p90 of ``admitted_s - arrival_s``, in ms, from the run's completions.  In
a traced run, only over requests admitted before the profiler started:
starting and stopping it stall the host for seconds, and waits through
those stalls measure the profiler, not the scheduler.  Moves
``ttft_p90_ms``."""
import numpy as np


def read(ctx):
    until = ctx.get("trace_opened_s")
    waits = [(c.admitted_s - c.arrival_s) * 1e3
             for c in ctx["completions"].values()
             if c.admitted_s >= 0 and (until is None or c.admitted_s < until)]
    return float(np.percentile(waits, 90)) if waits else None

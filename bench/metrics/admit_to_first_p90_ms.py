"""Admit/prefill: time from a request's admission into a slot to its first
token on the host (``Completion.first_token_s``, the chunk boundary that
delivered it), p90 in ms, from the run's completions.  The same requests as
``admit_wait_p90_ms``: in a traced run, those admitted before the profiler
started.  A program whose completions carry no first-token time reads
nothing.  Moves ``ttft_p90_ms``."""
import numpy as np


def read(ctx):
    until = ctx.get("trace_opened_s")
    gaps = [(c.first_token_s - c.admitted_s) * 1e3
            for c in ctx["completions"].values()
            if getattr(c, "first_token_s", -1.0) >= 0 and c.admitted_s >= 0
            and (until is None or c.admitted_s < until)]
    return float(np.percentile(gaps, 90)) if gaps else None

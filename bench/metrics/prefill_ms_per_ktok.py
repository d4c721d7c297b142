"""Admit/prefill (``Engine._admit`` -> ``lm.prefill_into_slots``): device
time of the admit program per thousand prompt tokens admitted, in ms, over
the traced window.  Moves ``ttft_p90_ms``."""
from bench import work

MODULES = ("jit_admit_fn",)


def read(ctx):
    a = work.admits(ctx, MODULES)
    if not a:
        return None
    return sum(s for _, s in a) * 1e3 / (sum(n for n, _ in a) / 1e3)

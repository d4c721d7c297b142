"""Decode chunk (``lm.decode_slots_scan``): device time of the decode
program per decode step, in ms, over the traced window.  Moves
``tpot_p90_ms``."""
from bench import work

MODULES = ("jit_decode_fn",)


def read(ctx):
    c = work.chunks(ctx, MODULES)
    if not c:
        return None
    return sum(s for _, s in c) * 1e3 / (len(c) * ctx["engine"]["chunk"])

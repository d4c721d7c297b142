"""Device: share of the traced window in which no operation ran on the
chip, in %.  Moves ``tpot_p90_ms``."""
from bench import trace


def read(ctx):
    t = ctx.get("trace")
    if t is None or not t["devices"]:
        return None
    return 100.0 * (1.0 - trace.busy_s(t) / trace.window_s(t))

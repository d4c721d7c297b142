"""Admit/prefill: share of the admit program's device time that the chip's
roofline needs for the prefills it ran (the architecture's
``work(model).prefill``), in %.
Moves ``ttft_p90_ms``."""
from bench import flops, work

MODULES = ("jit_admit_fn",)


def read(ctx):
    a = work.admits(ctx, MODULES)
    if not a:
        return None
    counts = ctx["work"]
    need = sum(flops.min_time(*counts.prefill(n), ctx["peaks"])[0] for n, _ in a)
    return 100.0 * need / sum(s for _, s in a)

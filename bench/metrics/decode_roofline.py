"""Decode chunk: share of the decode program's device time that the chip's
roofline needs for the needed rows of its steps (the architecture's
``work(model).decode_step``, bench/flops.py's rules: weights once a step,
the lines each row attends), in %.
Moves ``tpot_p90_ms``."""
from bench import flops, work

MODULES = ("jit_decode_fn",)


def read(ctx):
    c = work.chunks(ctx, MODULES)
    if not c:
        return None
    counts = ctx["work"]
    pos = work.chunk_positions(ctx)
    need = sum(flops.min_time(*counts.decode_step(p), ctx["peaks"])[0]
               for cid, _ in c for p in pos.get(cid, {}).values())
    return 100.0 * need / sum(s for _, s in c)

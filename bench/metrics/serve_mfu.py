"""Whole step (every device program): the model FLOPs of the prefills and
needed decode rows run in the traced window, over the window times the
chip's peak, in %.  Moves ``tok_s``."""
from bench import flops, trace, work


def read(ctx):
    t = ctx.get("trace")
    a = work.admits(ctx, ("jit_admit_fn",))
    c = work.chunks(ctx, ("jit_decode_fn",))
    if t is None or a is None or c is None:
        return None
    m = flops.dims(ctx["model"])
    pos = work.chunk_positions(ctx)
    total = sum(flops.prefill(m, n)[0] for n, _ in a)
    total += sum(flops.decode_step(m, p)[0]
                 for cid, _ in c for p in pos.get(cid, {}).values())
    return 100.0 * total / (trace.window_s(t) * ctx["peaks"]["bf16_flops"])

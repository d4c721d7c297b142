"""Whole step (every device program): the model FLOPs of the prefills and
needed decode rows run in the traced window (the architecture's
``work(model)``), over the window times the chip's peak, in %.  Moves
``tok_s``."""
from bench import trace, work


def read(ctx):
    t = ctx.get("trace")
    a = work.admits(ctx, ("jit_admit_fn",))
    c = work.chunks(ctx, ("jit_decode_fn",))
    if t is None or a is None or c is None:
        return None
    counts = ctx["work"]
    pos = work.chunk_positions(ctx)
    total = sum(counts.prefill(n)[0] for n, _ in a)
    total += sum(counts.decode_step(p)[0]
                 for cid, _ in c for p in pos.get(cid, {}).values())
    return 100.0 * total / (trace.window_s(t) * ctx["peaks"]["bf16_flops"])

"""Norm datapath (``layers/norms.py`` under ``jax.named_scope("norm")``:
mean-square reduce, the E2AFS rsqrt and the scale, for the layer, final
and q/k norms): device time of its leaf operations in the decode program
per decode step, in ms (bench/program_trace.py).  Reads the trace's
``scopes``.  Moves ``tpot_p90_ms``."""
from bench import program_trace


def read(ctx):
    split = program_trace.decode_split(ctx)
    return None if split is None else split["norm"]

"""Decode attention (``layers/attention.py`` ``attention_decode`` under
``jax.named_scope("decode_attention")``: the cache write and read, each KV
head's scores against its own query group, softmax and weighted sum, with
no copy of the cache per query head): device time of its leaf operations in
the decode program per decode step, in ms (bench/program_trace.py).  Reads
the trace's ``scopes``.  Moves ``tpot_p90_ms``."""
from bench import program_trace


def read(ctx):
    split = program_trace.decode_split(ctx)
    return None if split is None else split["decode_attention"]

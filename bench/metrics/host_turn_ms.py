"""Scheduler (``Engine.run``): mean host turn in the traced window, in ms:
from the end of one chunk's ``engine.decode_sync`` span to the start of
the next ``engine.decode_dispatch``, the time the device waits on the
host's admissions and bookkeeping (bench/program_trace.py
``host_turns``).  Reads the trace's ``program`` spans.  Moves
``tpot_p90_ms``."""
from bench import program_trace


def read(ctx):
    t = ctx.get("trace")
    turns = None if t is None else program_trace.host_turns(t)
    return 1e3 * sum(turns) / len(turns) if turns else None

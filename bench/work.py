"""Which work each device program in the traced window did.

The trace opens and closes at chunk boundaries, right after the engine's
one host sync, when everything dispatched has run.  So inside the window
the k-th admission the host dispatched (span ``bench.admit#<prompt len>``)
is the k-th execution of an admit program, and the k-th chunk boundary
(``bench.chunk_end#<chunk>``) closes the k-th execution of the decode
program.  Where the counts disagree, nothing is attributed and the
metrics that need it are left out.

A request emits one token per decode step from the first step of the
chunk after its admission (bench/harness.py ``first_token_times``), so its
``j``-th served token is fed at position ``prompt_len + j`` in step
``j % chunk`` of chunk ``first_chunk + j // chunk``.  Steps feeding the last
served token are not needed (their logits are never used) and are not
counted.
"""
from __future__ import annotations

from collections import defaultdict

from bench import trace as tr

__all__ = ["admits", "chunks", "chunk_positions"]


def admits(ctx: dict, patterns) -> list | None:
    """[(prompt_len, device seconds)] of the admissions in the window."""
    t = ctx.get("trace")
    if t is None:
        return None
    dev = tr.module_events(t, patterns)
    host = tr.host_spans(t, "bench.admit#")
    if not dev or len(dev) != len(host):
        return None
    return [(int(h[0].split("#")[1]), (e[2] - e[1]) / 1e9) for h, e in zip(host, dev)]


def chunks(ctx: dict, patterns) -> list | None:
    """[(chunk id, device seconds)] of the decode chunks in the window."""
    t = ctx.get("trace")
    if t is None:
        return None
    dev = tr.module_events(t, patterns)
    marks = tr.host_spans(t, "bench.chunk_end#")
    if not dev or len(dev) != len(marks):
        return None
    return [(int(m[0].split("#")[1]), (e[2] - e[1]) / 1e9) for m, e in zip(marks, dev)]


def chunk_positions(ctx: dict) -> dict:
    """chunk id -> {step: [positions fed by needed rows]}."""
    n = ctx["engine"]["chunk"]
    out: dict = defaultdict(lambda: defaultdict(list))
    for uid, c in ctx["completions"].items():
        if uid not in ctx["first"]:
            continue
        c0 = ctx["first"][uid][1]
        for j in range(len(c.tokens) - 1):
            out[c0 + j // n][j % n].append(c.prompt_len + j)
    return out

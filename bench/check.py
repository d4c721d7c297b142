"""How ``correct`` is decided: served tokens against the plain reference.

Once the window has closed and the program's state is freed, a sample of
the requests the run served, drawn from the seed, is run through the
reference (bench/reference.py) teacher-forced on the tokens the program
served.  The sample always holds the request with the most served tokens,
and others join until it holds ``check_tokens`` served tokens (the mix file
sets it).  At every served position the gap is the reference's best logit
minus its logit of the token the program served; the widest gap over the
sample is compared with the cell's limit (``bench/limits/<cell>.json``).

Greedy decoding serves the argmax of the program's logits, so a gap is 0
where program and reference agree on the best token and small where they
part at a near-tie; a program that served a wrong token, or computes in a
lower precision than the configuration states, leaves a wider one.  Every
request of the trace must also have been served in full.
"""
from __future__ import annotations

import numpy as np

__all__ = ["sample", "pad_len", "gaps", "not_served", "verdict", "run_check"]


def sample(completions: dict, seed: int, check_tokens: int) -> list:
    """uids of the requests checked: the longest served, then a seeded
    order of the rest until ``check_tokens`` served tokens are covered."""
    ok = [c for c in completions.values() if c.status == "ok" and len(c.tokens)]
    if not ok:
        return []
    longest = max(ok, key=lambda c: (len(c.tokens), c.prompt_len, -c.uid))
    rest = [c for c in ok if c.uid != longest.uid]
    rng = np.random.default_rng([int(seed) % (1 << 63), 7])
    order = [rest[i] for i in rng.permutation(len(rest))]
    chosen, n = [longest], len(longest.tokens)
    for c in order:
        if n >= check_tokens:
            break
        chosen.append(c)
        n += len(c.tokens)
    return [c.uid for c in chosen]


def pad_len(prompt_len: int, max_new: int, block: int) -> int:
    """A fixed padded length per prompt bucket: the bucket plus the longest
    output the mix allows, rounded up to whole blocks."""
    n = prompt_len + max_new
    return -(-n // block) * block


def teacher_forced(prompt, tokens):
    """The reference input: the prompt, then every served token but the
    last; positions ``len(prompt) - 1 ...`` predict the served tokens."""
    return np.concatenate([np.asarray(prompt, np.int32),
                           np.asarray(tokens[:-1], np.int32)]), len(prompt) - 1


def gaps(logits: np.ndarray, served) -> np.ndarray:
    """Per position: best logit minus the logit of the served token."""
    served = np.asarray(served, np.int64)
    return logits.max(axis=-1) - logits[np.arange(len(served)), served]


def not_served(requests, completions: dict) -> int:
    """Requests of the trace that were cut, never finished, or served fewer
    tokens than they asked for."""
    return sum(1 for r in requests
               if r.uid not in completions or completions[r.uid].status != "ok"
               or len(completions[r.uid].tokens) != r.max_new_tokens)


def verdict(readings: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and the numbers compared, each beside its limit."""
    compared = {k: {"value": readings[k], "limit": limits[k]} for k in limits}
    ok = all(v["value"] is not None and v["value"] <= v["limit"]
             for v in compared.values())
    return ok, compared


def run_check(ref, completions: dict, prompts: dict, seed: int, mix: dict,
              block: int, *, control: bool = False) -> dict:
    """Readings of one run: the widest gap of the served tokens over the
    sample (and with ``control``, of the tokens the control's logits put
    first at the same positions)."""
    uids = sample(completions, seed, mix["check_tokens"])
    seqs, starts, pads, served = [], [], [], []
    max_new = mix["output"]["max"]
    for u in uids:
        c = completions[u]
        seq, st = teacher_forced(prompts[u], c.tokens)
        seqs.append(seq)
        starts.append(st)
        pads.append(pad_len(len(prompts[u]), max_new, block))
        served.append(c.tokens)
    out = {"checked_requests": len(uids),
           "checked_tokens": int(sum(len(s) for s in served))}
    if not uids:
        out["logit_gap"] = None
        return out
    hi = ref.logits(seqs, starts, pads)
    out["logit_gap"] = float(max(gaps(lg, s).max() for lg, s in zip(hi, served)))
    if control:
        lo = ref.logits(seqs, starts, pads, low=True)
        out["control_gap"] = float(max(
            gaps(h, np.argmax(lo_i, axis=-1)).max() for h, lo_i in zip(hi, lo)))
    return out

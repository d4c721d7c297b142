#!/usr/bin/env python3
"""Kill-and-resume smoke: a REAL ``SIGKILL`` mid-serve, then recovery.

The in-process chaos suite (tests/launch/test_engine_snapshot.py) simulates
the kill with ``run(max_chunks=k)``; this smoke closes the remaining gap by
actually killing a serving *process* — no atexit, no flush, no interpreter
teardown — and proving the snapshot + write-ahead journal recover it:

1. a child process serves the trace with ``snapshot_every_chunks=1`` and a
   journal, and is ``SIGKILL``ed as soon as the journal shows decode
   progress;
2. the parent computes the uninterrupted reference (solo greedy tokens per
   request — the slot-parity anchor) in-process;
3. the parent resumes from whatever the dead child left on disk, drains,
   and audits the journal: every request finished EXACTLY once, tokens
   bit-equal the reference.

One process holds the device at a time: the parent does not touch JAX
until its serving child is dead (on an accelerator, a child started by a
parent that holds the chip fails or hangs).

If the child finishes before the kill lands (fast machine), the run is
still a valid — if weaker — recovery check and the audit must still pass.

Usage:
    PYTHONPATH=src python tools/kill_resume_smoke.py           # the smoke
    PYTHONPATH=src python tools/kill_resume_smoke.py --serve --dir D  # child
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from pathlib import Path

ARCH = os.environ.get("REPRO_KILL_SMOKE_ARCH", "qwen3-4b")
N_REQUESTS = int(os.environ.get("REPRO_KILL_SMOKE_REQUESTS", 10))
NUM_SLOTS = 2
CACHE_LEN = 24
CHUNK = 3
KILL_TIMEOUT_S = float(os.environ.get("REPRO_KILL_SMOKE_TIMEOUT", 300))


def _setup():
    import jax
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.launch.engine import Request
    from repro.models import lm

    cfg = get_smoke_config(ARCH, sqrt_unit="e2afs")
    params, _ = lm.init(cfg, jax.random.key(0))
    rng = np.random.RandomState(0)
    reqs = [
        Request(
            uid=i,
            prompt=rng.randint(0, cfg.vocab, size=int(rng.choice([3, 5]))).astype(
                np.int32
            ),
            max_new_tokens=int(rng.choice([7, 12])),
        )
        for i in range(N_REQUESTS)
    ]
    return cfg, params, reqs


def serve(workdir: Path) -> None:
    """Child: serve the trace with autosave + journal, then exit.  The
    parent SIGKILLs this process mid-serve; nothing here may rely on clean
    shutdown."""
    from repro.launch.engine import Engine

    cfg, params, reqs = _setup()
    eng = Engine(
        params, cfg, num_slots=NUM_SLOTS, cache_len=CACHE_LEN, chunk=CHUNK,
        snapshot_dir=workdir / "snap", snapshot_every_chunks=1,
        journal=workdir / "journal.jsonl",
    )
    eng.run(reqs)


def _journal_has_progress(jpath: Path) -> bool:
    """True once the child has journaled decode-chunk progress — the window
    where a kill lands mid-flight."""
    if not jpath.exists():
        return False
    try:
        text = jpath.read_text(encoding="utf-8")
    except OSError:
        return False
    return '"kind":"progress"' in text or '"kind":"snapshot"' in text


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--serve", action="store_true", help="child mode")
    ap.add_argument("--dir", type=Path, default=None)
    args = ap.parse_args()
    if args.serve:
        serve(args.dir)
        return 0

    import tempfile

    import numpy as np

    workdir = Path(args.dir or tempfile.mkdtemp(prefix="kill-resume-"))
    workdir.mkdir(parents=True, exist_ok=True)
    jpath = workdir / "journal.jsonl"

    env = dict(os.environ)
    src = str(Path(__file__).resolve().parents[1] / "src")
    env["PYTHONPATH"] = src + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    child = subprocess.Popen(
        [sys.executable, __file__, "--serve", "--dir", str(workdir)], env=env
    )
    print(f"[parent] child serving (pid {child.pid}); waiting for progress")
    t0 = time.time()
    killed = False
    while time.time() - t0 < KILL_TIMEOUT_S:
        if child.poll() is not None:
            break  # finished before we could kill it — still audit below
        if _journal_has_progress(jpath):
            os.kill(child.pid, signal.SIGKILL)
            child.wait()
            killed = True
            break
        time.sleep(0.005)
    else:
        child.kill()
        child.wait()
        print("[parent] FAIL: child made no journaled progress before timeout")
        return 1
    print(f"[parent] child {'SIGKILLed mid-serve' if killed else 'finished before kill'}")

    # the child is dead: the device is free for the parent from here on
    from repro.launch.engine import Engine, solo_generate
    from repro.launch.journal import read_journal, replay_plan

    cfg, params, reqs = _setup()
    print(f"[parent] reference: {len(reqs)} solo runs ({ARCH})")
    ref = {
        r.uid: solo_generate(params, cfg, r.prompt, r.max_new_tokens,
                             cache_len=CACHE_LEN)
        for r in reqs
    }

    pre_kill = sum(
        1 for r in read_journal(jpath) if r["kind"] == "finished"
    )
    eng = Engine.resume(params, cfg, workdir / "snap", journal=jpath,
                        chunk=CHUNK)
    done = eng.run([])
    print(f"[parent] child had finished {pre_kill}/{len(reqs)} pre-kill; "
          f"resume served {len(done)} more "
          f"({eng.stats['journal_replays']} journal replays)")

    records = read_journal(jpath)
    finished, accepted_unfinished = replay_plan(records)
    counts: dict = {}
    for rec in records:
        if rec["kind"] == "finished":
            counts[rec["uid"]] = counts.get(rec["uid"], 0) + 1
    failures = []
    if accepted_unfinished:
        failures.append(f"accepted but never finished: {sorted(accepted_unfinished)}")
    if set(counts) != {r.uid for r in reqs}:
        failures.append(f"finished uids {sorted(counts)} != accepted {[r.uid for r in reqs]}")
    dupes = {u: n for u, n in counts.items() if n != 1}
    if dupes:
        failures.append(f"not exactly-once: {dupes}")
    for r in reqs:
        if r.uid in finished and not np.array_equal(
            np.asarray(finished[r.uid]["tokens"], np.int32), ref[r.uid]
        ):
            failures.append(f"uid {r.uid}: tokens diverged from uninterrupted run")
    if failures:
        for f in failures:
            print(f"[parent] FAIL: {f}")
        return 1
    print(f"[parent] OK: exactly-once completion, {len(reqs)}/{len(reqs)} "
          f"bit-exact vs uninterrupted reference (killed={killed})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

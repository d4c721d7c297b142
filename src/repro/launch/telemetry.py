"""Serving telemetry: an append-only JSONL stream of per-chunk gauges.

The engine emits one record per decode chunk on the loop's existing
one-host-sync-per-chunk boundary — telemetry adds **zero** device syncs; it
only serializes numbers the scheduler already pulled.  Records are flushed
per emit but never fsynced (telemetry is observability, not recovery — the
journal and snapshots own durability, docs/robustness.md).

Record schema (kind="chunk"; see docs/robustness.md §Accuracy SLO for the
full field table):

    t                   wall-clock seconds at emission
    chunk               lifetime chunk counter (monotonic across resets)
    active_slots        live slots at the end of the chunk
    slot_occupancy      active_slots / num_slots
    queue_depth         due-request queue depth at the chunk boundary
    tokens              tokens emitted this chunk
    tok_s               running decode throughput (emitted / elapsed)
    canary_checks       shadow-exact canaries run this chunk (0 w/o SLO)
    canary_divergences  canary argmax disagreements this chunk
    canary_max_rel      max relative logit error over this chunk's canaries
    unit_levels         histogram {unit name: #slots at that rung}

Unknown fields must be tolerated by readers (same forward-compat contract
as the journal).  ``read_telemetry`` skips a torn final line.

:class:`HostSpans` accounts one serve loop's host time by what the host was
doing, and names each part in a profiler trace (docs/serving.md, "Taking a
profile").
"""
from __future__ import annotations

import contextlib
import gc
import json
import time
from collections import defaultdict
from pathlib import Path

from jax.profiler import TraceAnnotation

__all__ = ["Telemetry", "HostSpans", "read_telemetry"]


class Telemetry:
    """JSONL gauge emitter.  ``mode="a"`` (default) extends one continuous
    history across run segments; ``mode="w"`` truncates (bench lanes)."""

    def __init__(self, path, *, mode: str = "a"):
        if mode not in ("a", "w"):
            raise ValueError(f"mode must be 'a' or 'w', got {mode!r}")
        self.path = Path(path)
        self._mode = mode
        self._f = None

    def _file(self):
        if self._f is None or self._f.closed:
            self.path.parent.mkdir(parents=True, exist_ok=True)
            self._f = open(self.path, self._mode, encoding="utf-8")
            self._mode = "a"  # reopen after close() must not wipe history
        return self._f

    def emit(self, record: dict) -> dict:
        f = self._file()
        f.write(json.dumps(record, separators=(",", ":"), default=float) + "\n")
        f.flush()
        return record

    def close(self) -> None:
        if self._f is not None and not self._f.closed:
            self._f.close()


class HostSpans:
    """Host time of one serve loop, by span.

    ``span(name, tag)`` opens ``jax.profiler.TraceAnnotation("engine.<name>")``
    (``"engine.<name>#<tag>"`` with a tag), which costs next to nothing
    unless the profiler is recording, and adds the span's own
    ``perf_counter`` seconds to ``seconds[name]``: its length less that of
    the spans nested in it.  While :meth:`collect_gc` is active, Python's
    garbage collections count under ``"gc"`` and come off the span they
    interrupted.  So every second inside the outermost span is counted
    exactly once, and ``seconds`` adds up to that span's length.
    """

    def __init__(self):
        self.seconds: dict = defaultdict(float)
        self._open: list = []  # [start, seconds of nested spans] per open span
        self._gc_start = None

    @contextlib.contextmanager
    def span(self, name: str, tag=None):
        """Yields the span's start on the ``perf_counter`` clock."""
        with TraceAnnotation(f"engine.{name}" if tag is None else f"engine.{name}#{tag}"):
            frame = [time.perf_counter(), 0.0]
            self._open.append(frame)
            try:
                yield frame[0]
            finally:
                self._open.pop()
                d = time.perf_counter() - frame[0]
                self.seconds[name] += d - frame[1]
                if self._open:
                    self._open[-1][1] += d

    def _on_gc(self, phase, _info):
        if phase == "start":
            self._gc_start = time.perf_counter()
        elif self._gc_start is not None:
            d = time.perf_counter() - self._gc_start
            self._gc_start = None
            self.seconds["gc"] += d
            if self._open:
                self._open[-1][1] += d

    @contextlib.contextmanager
    def collect_gc(self):
        gc.callbacks.append(self._on_gc)
        try:
            yield
        finally:
            gc.callbacks.remove(self._on_gc)


def read_telemetry(path) -> list[dict]:
    """Parse a telemetry stream; a torn final line (emitter killed
    mid-append) is dropped, corruption elsewhere raises ValueError."""
    p = Path(path)
    if not p.exists():
        return []
    lines = p.read_text(encoding="utf-8").splitlines()
    records = []
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError as e:
            if i == len(lines) - 1:
                break
            raise ValueError(f"telemetry {p} line {i + 1} is corrupt: {e}") from e
    return records

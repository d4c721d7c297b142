"""The trace reduction: busy union, idle share, per-program device time,
attribution of programs to admissions and chunks, and gap tagging, on a
hand-made trace with known answers and on a trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from bench_tiny import REPO  # noqa: F401

from bench import trace as tr
from bench import work

FIXTURE = Path(__file__).resolve().parent / "fixtures" / "trace_chat.json"

# ns; window 0..100.  Device: admit 10-20 (ops 10-14, 12-18), decode 30-60
# (ops 30-50, 55-60), decode 70-90.  Host: run 0-100, admit 5-12,
# decode_chunk 25-62 and 65-92, chunk ends at 62 and 92.
HAND = {
    "window": [0, 100],
    "devices": [{
        "ops": [["fusion.1", 10, 14], ["fusion.2", 12, 18], ["dot.3", 30, 50],
                ["fusion.1", 55, 60], ["dot.3", 70, 90]],
        "modules": [["jit_admit_fn(7)", 10, 20], ["jit_decode_fn(8)", 30, 60],
                    ["jit_decode_fn(8)", 70, 90]],
    }],
    "host": [["bench.run", 0, 100], ["bench.admit#16", 5, 12],
             ["bench.decode_chunk", 25, 62], ["bench.chunk_end#4", 62, 62],
             ["bench.decode_chunk", 65, 92], ["bench.chunk_end#5", 92, 92]],
}


def test_busy_union_and_idle_by_hand():
    # ops cover 10-18, 30-50, 55-60, 70-90: 8 + 20 + 5 + 20 = 53 ns
    assert tr.busy_s(HAND) == pytest.approx(53e-9)
    assert tr.window_s(HAND) == pytest.approx(100e-9)
    assert tr.idle_gaps(HAND) == [[0, 10], [18, 30], [50, 55], [60, 70], [90, 100]]


def test_gap_tagging_by_hand():
    tags = tr.tag_gaps(tr.idle_gaps(HAND), HAND["host"])
    # 0-5 run, 5-10 admit, 18-25 run, 25-30 decode_chunk, 50-55 decode_chunk,
    # 60-62 decode_chunk, 62-65 run, 65-70 decode_chunk, 90-92 decode_chunk,
    # 92-100 run
    assert tags == pytest.approx({"bench.run": 23e-9, "bench.admit": 5e-9,
                                  "bench.decode_chunk": 19e-9})
    assert sum(tags.values()) == pytest.approx(100e-9 - tr.busy_s(HAND))


def test_programs_attributed_by_hand():
    ctx = {"trace": HAND}
    assert work.admits(ctx, ("jit_admit_fn",)) == [(16, pytest.approx(10e-9))]
    assert work.chunks(ctx, ("jit_decode_fn",)) == [(4, pytest.approx(30e-9)),
                                                    (5, pytest.approx(20e-9))]
    assert [o[0] for o in tr.top_ops(HAND)] == ["dot.3", "fusion.1", "fusion.2"]


def test_clip_cuts_events_at_the_window():
    assert tr.clip([["a", -5, 5], ["b", 95, 120], ["c", 200, 300]], [0, 100]) == [
        ["a", 0, 5], ["b", 95, 100]]


def _counted_busy(dev, window):
    """Busy time by counting running operations across every event edge:
    a second algorithm beside the interval merge."""
    t0, t1 = window
    edges = []
    for _, a, b in dev["ops"]:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, running, last = 0.0, 0, None
    for t, d in edges:
        if running > 0:
            busy += t - last
        running += d
        last = t
    return busy


def test_recorded_trace():
    """Two decode chunks and two admissions of qwen3-4b.chat-steady, cut from
    a trace recorded on a TPU v5e."""
    t = json.loads(FIXTURE.read_text())
    busy = tr.busy_s(t)
    assert busy * 1e9 == pytest.approx(_counted_busy(t["devices"][0], t["window"]))
    assert 0 < busy < tr.window_s(t)
    gaps = tr.tag_gaps(tr.idle_gaps(t), t["host"])
    assert sum(gaps.values()) == pytest.approx(tr.window_s(t) - busy, rel=1e-9)
    a = work.admits({"trace": t}, ("jit_admit_fn",))
    c = work.chunks({"trace": t}, ("jit_decode_fn",))
    assert [n for n, _ in a] == [128, 128] and [i for i, _ in c] == [17, 18]
    dev_s = sum(s for _, s in a) + sum(s for _, s in c)
    assert dev_s <= tr.window_s(t)
    b = tr.breakdown(t)
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_admit_wait_stops_at_the_profiler_start():
    from types import SimpleNamespace

    from bench.spec import metric_reader

    read = metric_reader("admit_wait_p90_ms", REPO)
    done = {i: SimpleNamespace(arrival_s=float(i), admitted_s=i + (0.1 if i < 10 else 3.0))
            for i in range(20)}
    done[20] = SimpleNamespace(arrival_s=20.0, admitted_s=-1.0)
    assert read({"completions": done}) == pytest.approx(3000.0)
    # a traced run: the waits through the profiler's stalls are left out
    assert read({"completions": done, "trace_opened_s": 9.5}) == pytest.approx(100.0)
    assert read({"completions": {}, "trace_opened_s": 1.0}) is None

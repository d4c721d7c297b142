"""From a profiler trace to the numbers the per-layer metrics read.

``read_xplane`` turns the profiler's ``.xplane.pb`` into a small JSON-able
record, and everything else works on that record, so a recorded trace kept
as a test fixture exercises the same code as a run on the chip::

    {"window": [t0, t1],                    # ns, from the benchmark's marks
     "devices": [{"ops": [[name, t0, t1], ...],
                  "modules": [[name, t0, t1], ...]}, ...],
     "host": [[name, t0, t1], ...],         # the benchmark's own spans
     "program": [[name, t0, t1], ...],      # the program's engine.* spans
     "scopes": [{module: {op: scope}}, ...]}  # per device, named scopes

Device events come from the planes ``/device:TPU:<n>``: the line
``XLA Ops`` holds one event per operation and ``XLA Modules`` one per
executed program.  Host spans are the ``jax.profiler.TraceAnnotation``
spans whose names start with ``bench.``: ``bench.trace_open`` and
``bench.trace_close`` bound the window, and the rest say what the host was
doing (see bench/harness.py).  ``program`` and ``scopes`` are
bench/program_trace.py's reading of the same file.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

__all__ = ["op_name", "read_xplane", "clip", "union", "busy_s", "module_events",
           "idle_gaps", "tag_gaps", "top_ops", "breakdown"]

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = bf16[..]
    fusion(..)`` becomes ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_xplane(trace_dir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices, host = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[op_name(e.name), e.start_ns, e.end_ns]
                                for e in line.events]
            devices.append(dev)
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append([e.name, e.start_ns, e.end_ns])
    host.sort(key=lambda e: e[1])
    opens = [e for e in host if e[0] == "bench.trace_open"]
    closes = [e for e in host if e[0] == "bench.trace_close"]
    if not opens or not closes:
        raise ValueError("the trace lacks the bench.trace_open/close marks")
    # the device is idle at both marks (each follows the engine's host sync),
    # so every device event recorded lies between them; the device clock
    # can sit a fraction of a millisecond off the host's, so the window
    # widens to hold them
    t0, t1 = opens[0][2], closes[-1][1]
    for dev in devices:
        for e in dev["modules"] + dev["ops"]:
            t0, t1 = min(t0, e[1]), max(t1, e[2])
    from bench import program_trace

    out = {"window": [t0, t1], "devices": devices, "host": host}
    out.update(program_trace.read(paths[-1]))
    return out


def clip(events, window):
    """Events inside ``window``; an event that crosses its edge is cut."""
    t0, t1 = window
    out = []
    for name, a, b in events:
        a, b = max(a, t0), min(b, t1)
        if b > a:
            out.append([name, a, b])
    return out


def union(events) -> list:
    """Merged [start, end] intervals covered by any event."""
    merged: list = []
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _busy_events(dev):
    return dev["ops"] if dev["ops"] else dev["modules"]


def busy_s(trace: dict) -> float:
    """Seconds in the window in which some operation ran, averaged over the
    devices traced."""
    devs = trace["devices"]
    if not devs:
        return 0.0
    tot = 0.0
    for dev in devs:
        tot += sum(b - a for a, b in union(clip(_busy_events(dev), trace["window"])))
    return tot / len(devs) / 1e9


def window_s(trace: dict) -> float:
    a, b = trace["window"]
    return (b - a) / 1e9


def module_events(trace: dict, patterns, device: int = 0) -> list:
    """Executions of the programs whose names contain any of ``patterns``,
    wholly inside the window, in order."""
    if device >= len(trace["devices"]):
        return []
    t0, t1 = trace["window"]
    return [e for e in trace["devices"][device]["modules"]
            if any(p in e[0] for p in patterns) and e[1] >= t0 and e[2] <= t1]


def host_spans(trace: dict, prefix: str) -> list:
    t0, t1 = trace["window"]
    return [e for e in trace["host"]
            if e[0].startswith(prefix) and e[1] >= t0 and e[2] <= t1]


def idle_gaps(trace: dict, device: int = 0) -> list:
    """[start, end] of each stretch of the window with nothing running."""
    if device >= len(trace["devices"]):
        return []
    t0, t1 = trace["window"]
    busy = union(clip(_busy_events(trace["devices"][device]), trace["window"]))
    gaps, cur = [], t0
    for a, b in busy:
        if a > cur:
            gaps.append([cur, a])
        cur = max(cur, b)
    if t1 > cur:
        gaps.append([cur, t1])
    return gaps


# host spans that mark a point or bound the window say nothing of the work
_MARKS = ("bench.trace_open", "bench.trace_close", "bench.chunk_end")


def tag_gaps(gaps, host) -> dict:
    """Idle seconds by what the host was doing: each part of a gap goes to
    the innermost (shortest) benchmark span covering it, and to ``host.other``
    where none does."""
    spans = sorted((e for e in host if not e[0].startswith(_MARKS)),
                   key=lambda e: e[2] - e[1])
    out: dict = defaultdict(float)
    for a, b in gaps:
        # cut the gap at every span edge inside it, then tag each piece
        cuts = sorted({a, b, *[x for s in spans for x in (s[1], s[2]) if a < x < b]})
        for lo, hi in zip(cuts, cuts[1:]):
            mid = (lo + hi) / 2
            tag = next((s[0].split("#")[0] for s in spans if s[1] <= mid < s[2]),
                       "host.other")
            out[tag] += (hi - lo) / 1e9
    return dict(out)


def top_ops(trace: dict, n: int = 10, device: int = 0) -> list:
    """The ``n`` device operations that took most time in the window."""
    if device >= len(trace["devices"]):
        return []
    tot: dict = defaultdict(float)
    for name, a, b in clip(trace["devices"][device]["ops"], trace["window"]):
        tot[name] += (b - a) / 1e9
    return sorted(([k, v] for k, v in tot.items()), key=lambda kv: -kv[1])[:n]


def breakdown(trace: dict, n: int = 10) -> dict:
    gaps = tag_gaps(idle_gaps(trace), trace["host"])
    return {"device_ops": top_ops(trace, n),
            "idle_gaps": sorted(([k, v] for k, v in gaps.items()),
                                key=lambda kv: -kv[1])[:n]}

"""The program's own spans and named scopes in a profiler trace, and the
numbers read from them.

``read(path)`` takes the ``.xplane.pb`` that bench/trace.py ``read_xplane``
reads and returns two keys to add to its record::

    {"program": [[name, t0, t1], ...],       # the engine's host spans, ns
     "scopes": [{module: {op: scope}}, ...]} # per device, ops with a scope

``program`` holds the ``engine.*`` spans that ``Engine.run`` opens
(src/repro/launch/telemetry.py ``HostSpans``; docs/serving.md, "Taking a
profile").  ``scopes`` maps each operation of each program (keyed by its
``XLA Modules`` event name, such as ``jit_decode_fn(<program id>)``) to the
innermost of :data:`SCOPES` on its op-name path, which the program sets with
``jax.named_scope``: an operation of the qk-norm inside attention has the
path ``jit(decode_fn)/.../decode_attention/norm/...`` and counts as
``norm``.  Operations under no scope are left out.

The op-name path is the ``tf_op`` stat, and the program the ``program_id``
stat, of each operation's event metadata on the device plane.
``jax.profiler.ProfileData`` does not show event metadata, so ``read``
decodes just that part of the file's ``XSpace`` protobuf itself.

A scope's device time is the union of the intervals of its leaf operations
inside the program's executions: container operations (``while``,
``conditional``, ``call``) hold other operations and are never counted.
"""
from __future__ import annotations

import bisect
import re

from bench import trace as tr
from bench import work

__all__ = ["SCOPES", "read", "op_scopes", "innermost_scope", "scope_seconds",
           "decode_split", "host_turns", "idle_by_span"]

SCOPES = ("decode_attention", "norm")
PROGRAM_PREFIX = "engine."
_CONTAINERS = ("while", "conditional", "call")
_MODULE = re.compile(r"^[\w.\-]+\((\d+)\)$")  # an XLA Modules event name


def innermost_scope(path: str):
    """The last of :data:`SCOPES` on an op-name path, or None."""
    inner = None
    for part in path.split("/"):
        if part in SCOPES:
            inner = part
    return inner


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, i, end):
    """(field number, value) of each field of the protobuf message in
    ``buf[i:end]``; a length-delimited value is its (start, end)."""
    while i < end:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = bytes(buf[i:i + n]), i + n
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_value(buf, span):
    """(key, value span) of one protobuf map entry."""
    key = value = None
    for f, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def op_scopes(raw: bytes) -> list:
    """Per device plane of a serialized ``XSpace``: {module: {op: scope}}.

    Reads XPlane.name (2), event_metadata (4: id -> XEventMetadata name 2,
    stats 5) and stat_metadata (5: id -> XStatMetadata name 2); an XStat
    holds its stat's id (1) and an int (3, 4), string (5) or interned
    string (7, the id of a stat_metadata entry) value."""
    buf = memoryview(raw)
    out = []
    for f, plane in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for f2, v in _fields(buf, *plane):
            if f2 == 2:
                name = _text(buf, v)
            elif f2 == 4:
                events.append(_map_value(buf, v)[1])
            elif f2 == 5:
                key, md = _map_value(buf, v)
                stat_names[key] = next((_text(buf, x) for g, x in _fields(buf, *md)
                                        if g == 2), "")
        if not name.startswith(tr.DEVICE_PREFIX):
            continue
        modules, ops = {}, []
        for md in events:
            ev_name, stats = "", {}
            for g, x in _fields(buf, *md):
                if g == 2:
                    ev_name = _text(buf, x)
                elif g == 5:
                    sid = val = None
                    for h, y in _fields(buf, *x):
                        if h == 1:
                            sid = y
                        elif h in (3, 4):
                            val = y
                        elif h == 5:
                            val = _text(buf, y)
                        elif h == 7:
                            val = stat_names.get(y)
                    stats[stat_names.get(sid)] = val
            m = _MODULE.match(ev_name)
            if m:
                modules[int(m.group(1))] = ev_name
            elif "tf_op" in stats and "program_id" in stats:
                ops.append((stats["program_id"], tr.op_name(ev_name), str(stats["tf_op"])))
        dev: dict = {}
        for pid, op, path in ops:
            scope = innermost_scope(path)
            if scope and pid in modules:
                dev.setdefault(modules[pid], {})[op] = scope
        out.append(dev)
    return out


def read(path: str) -> dict:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    program = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not plane.name.startswith(tr.DEVICE_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PROGRAM_PREFIX):
                        program.append([e.name, e.start_ns, e.end_ns])
    program.sort(key=lambda e: e[1])
    return {"program": program, "scopes": op_scopes(raw)}


def _is_container(op: str) -> bool:
    return op.split(".")[0] in _CONTAINERS


def scope_seconds(trace: dict, patterns, device: int = 0) -> dict | None:
    """{scope or None: seconds} of the leaf operations inside the window's
    executions of the programs matching ``patterns``; None where the trace
    carries no scoped operation (a program built without named scopes)."""
    if device >= len(trace.get("scopes") or []) or not trace["scopes"][device]:
        return None
    runs = tr.module_events(trace, patterns, device)
    if not runs:
        return None
    maps = trace["scopes"][device]
    starts = [r[1] for r in runs]
    by_scope: dict = {}
    for name, a, b in trace["devices"][device]["ops"]:
        if _is_container(name):
            continue
        i = bisect.bisect_right(starts, a) - 1
        if i < 0 or b > runs[i][2]:
            continue
        scope = maps.get(runs[i][0], {}).get(name)
        by_scope.setdefault(scope, []).append([name, a, b])
    return {k: sum(b - a for a, b in tr.union(v)) / 1e9 for k, v in by_scope.items()}


def decode_split(ctx: dict) -> dict | None:
    """Decode-program device time per decode step, ms, by scope
    (``decode_attention``, ``norm`` and ``None`` for the unscoped rest),
    over the chunks that bench/work.py attributes in the window."""
    c = work.chunks(ctx, ("jit_decode_fn",))
    secs = scope_seconds(ctx.get("trace") or {}, ("jit_decode_fn",))
    if not c or secs is None:
        return None
    steps = len(c) * ctx["engine"]["chunk"]
    split = {s: secs.get(s, 0.0) * 1e3 / steps for s in SCOPES}
    split[None] = secs.get(None, 0.0) * 1e3 / steps
    return split


def host_turns(trace: dict) -> list | None:
    """Seconds of each host turn in the window: from the end of one
    ``engine.decode_sync`` to the start of the next ``engine.decode_dispatch``.
    A turn in which the host slept for an arrival (``engine.wait_arrival``)
    had no live work and is left out.  None without program spans."""
    spans = trace.get("program")
    if not spans:
        return None
    t0, t1 = trace["window"]
    inside = [e for e in spans if e[1] >= t0 and e[2] <= t1]
    waits = [e for e in inside if e[0].startswith("engine.wait_arrival")]
    turns, last_sync = [], None
    for name, a, b in inside:
        if name == "engine.decode_sync":
            last_sync = b
        elif name == "engine.decode_dispatch" and last_sync is not None:
            if not any(last_sync <= w[1] and w[2] <= a for w in waits):
                turns.append((a - last_sync) / 1e9)
            last_sync = None
    return turns


def idle_by_span(trace: dict) -> dict | None:
    """The window's idle device seconds by the innermost ``engine.*`` span
    the host was in, ``host.other`` where it was in none."""
    if trace.get("program") is None:
        return None
    return tr.tag_gaps(tr.idle_gaps(trace), trace["program"])

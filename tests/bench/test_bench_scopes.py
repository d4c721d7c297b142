"""The program's spans and named scopes in the trace reduction
(bench/program_trace.py) and the readers that use them, on a hand-made
trace with known answers; and the readers that were there before, which
must read the committed recorded trace exactly as they did."""
import copy
import gzip
import json
from pathlib import Path

import pytest

from bench_tiny import REPO
from test_bench_trace import FIXTURE, HAND

from bench import program_trace as pt
from bench import trace as tr
from bench.spec import metric_reader

# HAND (test_bench_trace.py) with the program's spans and scopes.  Decode
# runs at 30-60 and 70-90 (chunk 4, so 8 steps): dot.3 is decode attention,
# fusion.7 a norm nested inside it, fusion.1 unscoped in the decode program
# but a norm in the admit program, and while.9 a container that holds them.
SCOPED = copy.deepcopy(HAND)
SCOPED["devices"][0]["ops"] += [["while.9", 30, 60], ["fusion.7", 50, 53],
                                ["while.9", 70, 90]]
SCOPED["scopes"] = [{
    "jit_decode_fn(8)": {"dot.3": "decode_attention", "fusion.7": "norm",
                         "while.9": "decode_attention"},
    "jit_admit_fn(7)": {"fusion.1": "norm"},
}]
SCOPED["program"] = [
    ["engine.admit#16", 6, 11], ["engine.decode_dispatch", 26, 28],
    ["engine.decode_sync", 28, 61], ["engine.bookkeeping", 61, 63],
    ["engine.telemetry", 63, 64], ["engine.decode_dispatch", 66, 68],
    ["engine.decode_sync", 68, 91], ["engine.bookkeeping", 91, 92],
    ["engine.wait_arrival", 93, 96], ["engine.decode_dispatch", 97, 98],
    ["engine.decode_sync", 98, 99],
]
CTX = {"trace": SCOPED, "engine": {"chunk": 4}}
SCOPED_FIXTURE = Path(FIXTURE).parent / "trace_scopes.json.gz"


def _read(name, ctx):
    return metric_reader(name, REPO)(ctx)


# An XSpace as the TPU profiler writes it, cut down: each operation's event
# metadata carries its op-name path (tf_op, here once as an interned string)
# and its program's id; program 8 is the decode program, 7 an admission.
XSPACE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 30000 duration_ps: 30000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 30000 duration_ps: 20000 } }
  event_metadata { key: 1 value { id: 1 name: "jit_decode_fn(8)" } }
  event_metadata { key: 6 value { id: 6 name: "jit_admit_fn(7)" } }
  event_metadata { key: 2 value { id: 2 name: "%dot.3 = bf16[2] dot(x, y)"
    stats { metadata_id: 10 str_value: "jit(decode_fn)/while/body/decode_attention/dot_general:" }
    stats { metadata_id: 11 uint64_value: 8 } } }
  event_metadata { key: 3 value { id: 3 name: "%fusion.7 = f32[2] fusion(z)"
    stats { metadata_id: 10 ref_value: 12 } stats { metadata_id: 11 uint64_value: 8 } } }
  event_metadata { key: 4 value { id: 4 name: "%fusion.1 = f32[2] fusion(z)"
    stats { metadata_id: 10 str_value: "jit(admit_fn)/while/body/norm/rsqrt:" }
    stats { metadata_id: 11 uint64_value: 7 } } }
  event_metadata { key: 5 value { id: 5 name: "%fusion.2 = f32[2] fusion(z)"
    stats { metadata_id: 10 str_value: "jit(decode_fn)/while/body/dot_general:" }
    stats { metadata_id: 11 uint64_value: 8 } } }
  stat_metadata { key: 10 value { id: 10 name: "tf_op" } }
  stat_metadata { key: 11 value { id: 11 name: "program_id" } }
  stat_metadata { key: 12 value { id: 12 name: "jit(decode_fn)/while/body/decode_attention/norm/mul:" } }
}
planes { id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 40000 duration_ps: 5000 }
    events { metadata_id: 1 offset_ps: 28000 duration_ps: 33000 }
    events { metadata_id: 3 offset_ps: 1000 duration_ps: 1000 } }
  event_metadata { key: 1 value { id: 1 name: "engine.decode_sync" } }
  event_metadata { key: 2 value { id: 2 name: "engine.gc_probe" } }
  event_metadata { key: 3 value { id: 3 name: "bench.trace_open" } }
}
"""


def test_read_spans_and_scopes_from_an_xspace(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    got = pt.read(str(path))
    # the engine's spans in start order; the benchmark's own are not kept
    assert got["program"] == [["engine.decode_sync", 1028.0, 1061.0],
                              ["engine.gc_probe", 1040.0, 1045.0]]
    assert got["scopes"] == [{
        "jit_decode_fn(8)": {"dot.3": "decode_attention", "fusion.7": "norm"},
        "jit_admit_fn(7)": {"fusion.1": "norm"},
    }]


def test_read_xplane_adds_the_programs_marks_and_keeps_the_rest(tmp_path):
    """bench/trace.py's record of an xplane holds program_trace.read's
    ``program`` and ``scopes``; its window, device events and benchmark
    spans read as they did without them."""
    from jax.profiler import ProfileData

    text = XSPACE.replace(
        "events { metadata_id: 3 offset_ps: 1000 duration_ps: 1000 } }",
        "events { metadata_id: 3 offset_ps: 1000 duration_ps: 1000 }\n"
        "    events { metadata_id: 4 offset_ps: 90000 duration_ps: 1000 } }").replace(
        'event_metadata { key: 3 value { id: 3 name: "bench.trace_open" } }',
        'event_metadata { key: 3 value { id: 3 name: "bench.trace_open" } }\n'
        '  event_metadata { key: 4 value { id: 4 name: "bench.trace_close" } }')
    assert text.count("bench.trace_close") == 1
    path = tmp_path / "plugins" / "profile" / "t" / "t.xplane.pb"
    path.parent.mkdir(parents=True)
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    got = tr.read_xplane(str(tmp_path))
    marks = pt.read(str(path))
    assert got["program"] == marks["program"] and got["scopes"] == marks["scopes"]
    assert got["program"] and got["scopes"][0]
    rest = {k: v for k, v in got.items() if k not in marks}
    assert rest == {
        # from the end of bench.trace_open to the start of bench.trace_close
        "window": [1002.0, 1090.0],
        "devices": [{"ops": [["dot.3", 1030.0, 1050.0]],
                     "modules": [["jit_decode_fn(8)", 1030.0, 1060.0]]}],
        "host": [["bench.trace_open", 1001.0, 1002.0],
                 ["bench.trace_close", 1090.0, 1091.0]],
    }


def test_innermost_scope_of_an_op_path():
    assert pt.innermost_scope("jit(decode_fn)/while/body/decode_attention/norm/mul") == "norm"
    assert pt.innermost_scope("jit(decode_fn)/while/body/decode_attention/dot_general") == (
        "decode_attention")
    assert pt.innermost_scope("jit(decode_fn)/while/body/dot_general") is None
    # a scope name is a whole path part, not a substring of one
    assert pt.innermost_scope("jit(decode_fn)/layernorm_select/mul") is None


def test_scope_time_by_hand():
    # the containers and the admit program's ops are not counted; the norm
    # nested in attention counts as norm
    assert pt.scope_seconds(SCOPED, ("jit_decode_fn",)) == pytest.approx(
        {"decode_attention": 40e-9, "norm": 3e-9, None: 5e-9})
    assert pt.decode_split(CTX) == pytest.approx(
        {"decode_attention": 40e-6 / 8, "norm": 3e-6 / 8, None: 5e-6 / 8})
    assert _read("decode_attention_ms_per_step", CTX) == pytest.approx(5e-6)
    assert _read("norm_ms_per_step", CTX) == pytest.approx(3.75e-7)
    # the scoped and unscoped leaf time and the idle 53-55 make up the step
    split = sum(pt.decode_split(CTX).values())
    assert split + 2e-6 / 8 == pytest.approx(_read("decode_step_ms", CTX))


def test_host_turns_by_hand():
    # 61 -> 66 is a turn; 91 -> 97 slept for an arrival and is left out
    assert pt.host_turns(SCOPED) == pytest.approx([5e-9])
    assert _read("host_turn_ms", CTX) == pytest.approx(5e-6)


def test_idle_by_engine_span_by_hand():
    # busy 10-18, 30-60 (the while), 70-90: idle 0-10, 18-30, 60-70, 90-100
    idle = pt.idle_by_span(SCOPED)
    assert idle == pytest.approx({
        "host.other": 19e-9, "engine.admit": 4e-9, "engine.decode_dispatch": 5e-9,
        "engine.decode_sync": 7e-9, "engine.bookkeeping": 3e-9,
        "engine.telemetry": 1e-9, "engine.wait_arrival": 3e-9})
    assert sum(idle.values()) == pytest.approx(tr.window_s(SCOPED) - tr.busy_s(SCOPED))


def test_readers_read_nothing_without_the_programs_marks():
    # the record as bench/trace.py makes it, with no program spans or scopes
    assert _read("decode_attention_ms_per_step", {"trace": HAND, "engine": {"chunk": 4}}) is None
    assert _read("host_turn_ms", {"trace": HAND}) is None
    assert pt.idle_by_span(HAND) is None
    # a program built without named scopes
    unscoped = dict(SCOPED, scopes=[{}])
    assert _read("norm_ms_per_step", dict(CTX, trace=unscoped)) is None


def test_recorded_trace_with_the_programs_marks():
    """Two decode chunks and three admissions of qwen3-4b.chat-steady, cut
    from a trace recorded on a TPU v5e with the program's spans and scopes,
    and the completion times of that run."""
    from types import SimpleNamespace

    t = json.loads(gzip.decompress(SCOPED_FIXTURE.read_bytes()))
    served = t.pop("completions")
    done = {uid: SimpleNamespace(arrival_s=arr, admitted_s=adm, first_token_s=first)
            for uid, arr, adm, first in served["rows"]}
    ctx = {"trace": t, "engine": {"chunk": 8}, "completions": done,
           "trace_opened_s": served["trace_opened_s"]}
    values = {n: _read(n, ctx) for n in ("decode_attention_ms_per_step", "norm_ms_per_step",
                                         "host_turn_ms", "admit_to_first_p90_ms",
                                         "decode_step_ms")}
    assert all(v is not None and v > 0 for v in values.values()), values
    # the scoped and the unscoped leaf time make up the decode step, less the
    # moments inside the program with no operation running
    split = pt.decode_split(ctx)
    assert sum(split.values()) <= values["decode_step_ms"]
    assert sum(split.values()) == pytest.approx(values["decode_step_ms"], rel=0.02)
    assert split["decode_attention"] > split[None] > split["norm"]
    idle = pt.idle_by_span(t)
    assert set(idle) <= {"host.other"} | {e[0].split("#")[0] for e in t["program"]}
    assert sum(idle.values()) == pytest.approx(tr.window_s(t) - tr.busy_s(t), rel=1e-9)


def test_admit_to_first_token():
    from types import SimpleNamespace

    read = metric_reader("admit_to_first_p90_ms", REPO)
    done = {i: SimpleNamespace(arrival_s=float(i), admitted_s=i + 0.5,
                               first_token_s=i + 0.5 + (0.2 if i < 10 else 2.0))
            for i in range(20)}
    done[20] = SimpleNamespace(arrival_s=20.0, admitted_s=-1.0, first_token_s=-1.0)
    done[21] = SimpleNamespace(arrival_s=21.0, admitted_s=21.5, first_token_s=-1.0)
    assert read({"completions": done}) == pytest.approx(2000.0)
    # a traced run: only requests admitted before the profiler started
    assert read({"completions": done, "trace_opened_s": 9.6}) == pytest.approx(200.0)
    # completions from a program with no first-token time read nothing
    old = {i: SimpleNamespace(arrival_s=0.0, admitted_s=0.1) for i in range(3)}
    assert read({"completions": old}) is None


# What the readers and the breakdown read on the committed recorded trace
# (tests/bench/fixtures/trace_chat.json) with the qwen3-4b configuration and
# no completions, before the program's spans and scopes were added.
RECORDED = {
    "prefill_ms_per_ktok": 123.45015624999998,
    "prefill_roofline": 62.31161025345419,
    "decode_step_ms": 65.93356656249999,
    "decode_roofline": 0.0,
    "serve_mfu": 0.8685059100839915,
    "device_idle": 0.6698391733343079,
}
RECORDED_BREAKDOWN = {
    "device_ops": [
        ["while.63", 1.048196865], ["while.64", 1.031337484],
        ["broadcast_in_dim.229", 0.2182121340000001], ["broadcast_in_dim.230", 0.218211732],
        ["multiply_reduce_fusion.4", 0.18307351800000007],
        ["multiply_reduce_fusion.5", 0.1546367760000001],
        ["bitcast_add_fusion.3", 0.07948738599999998],
        ["dynamic-slice_bitcast_fusion.4", 0.038783838], ["fusion.257", 0.03878136700000003],
        ["dynamic-slice_bitcast_fusion.5", 0.03876520800000002]],
    "idle_gaps": [["bench.decode_chunk", 0.006144881999999992],
                  ["bench.admit", 0.0006932650000000004], ["host.other", 0.000488978]],
}


@pytest.fixture(scope="module")
def recorded_ctx():
    from bench.run import peaks_for
    from bench.spec import architecture

    config = json.loads((REPO / "bench" / "configs" / "qwen3-4b.json").read_text())
    return {"trace": json.loads(Path(FIXTURE).read_text()), "engine": config["engine"],
            "model": config["model"], "work": architecture(config, REPO).work(config["model"]),
            "peaks": peaks_for("TPU v5 lite", REPO),
            "completions": {}, "first": {}, "records": [], "trace_opened_s": None}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_trace_reads_as_before(recorded_ctx, name):
    assert metric_reader(name, REPO)(recorded_ctx) == RECORDED[name]


def test_recorded_breakdown_reads_as_before(recorded_ctx):
    assert json.loads(json.dumps(tr.breakdown(recorded_ctx["trace"]))) == RECORDED_BREAKDOWN

"""The trace reduction on hand-made event lists, and on a small trace
recorded on the chip (tests/data/)."""
import gzip
import json
from pathlib import Path

import pytest

import tracefile

DATA = Path(__file__).resolve().parent / "data"


def test_busy_union_and_idle_gaps():
    ops = [["a", 0, 10, ""], ["b", 5, 20, ""], ["c", 30, 40, ""],
           ["a", 45, 50, ""]]
    spans = [["decode_step", 0, 25], ["host_tokens", 25, 35],
             ["scatter", 40, 60]]
    assert tracefile.busy_intervals(ops, 0, 60) == [[0, 20], [30, 40],
                                                     [45, 50]]
    assert tracefile.busy_ns(ops, 0, 60) == 35
    assert tracefile.busy_ns(ops, 8, 35) == 17
    gaps = tracefile.idle_gaps(ops, spans, 0, 60)
    assert gaps[0] == ["host_tokens", 10e-9]
    assert gaps[1] == ["scatter", 10e-9]
    assert gaps[2] == ["scatter", 5e-9]
    assert tracefile.top_ops(ops, 0, 60) == [["a", 15e-9], ["b", 15e-9],
                                             ["c", 10e-9]]
    assert tracefile.top_ops(ops, 0, 8, 1) == [["a", 8e-9]]


def test_module_and_op_lookup():
    mods = [["jit_bench_qdot_wo(1)", 0, 7, ""],
            ["jit_bench_int8_wo(2)", 10, 11, ""]]
    assert tracefile.module_ns(mods, "jit_bench_qdot_wo") == [7]
    ops = [["%decode_attention_step.4 = f32[4,16,128] custom-call(s32[4] "
            "%p)", 4, 9, ""],
           ["%fusion.1 = s32[4] fusion(f32[4,16,128] "
            "%decode_attention_step.4)", 9, 12, ""]]
    assert tracefile.op_ns(ops, "%decode_attention_step", 0, 100) == [5]
    assert tracefile.op_ns(ops, "%decode_attention_step", 5, 100) == [4]
    assert tracefile.short_name(ops[1][0]) == "%fusion.1 fusion"
    assert tracefile.short_name(ops[0][0]) == \
        "%decode_attention_step.4 custom-call"


def test_recorded_chip_trace():
    path = DATA / "qwen3_events.json.gz"
    if not path.exists():
        pytest.skip("no recorded trace")
    ev = json.load(gzip.open(path, "rt"))
    want = json.loads((DATA / "qwen3_events_expect.json").read_text())
    (t0, t1), = tracefile.span_times(ev["spans"], "serve_window")
    ops = ev["devices"][0]["ops"]
    assert tracefile.busy_ns(ops, t0, t1) == want["busy_ns"]
    assert len(tracefile.op_ns(ops, "%decode_attention_step", t0, t1)) \
        == want["attention_kernel_events"]
    assert [m[0] for m in tracefile.top_ops(ops, t0, t1, 3)] \
        == want["top3"]
    assert tracefile.idle_gaps(ops, ev["spans"], t0, t1, 1)[0][0] \
        == want["idle_top"]

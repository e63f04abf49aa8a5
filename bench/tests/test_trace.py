"""Trace reduction: on hand-made events, and on a slice of a trace
recorded on a TPU v5e (``data/qwen2-chat.events.json.gz``: 3.1 s of a
``qwen2-1.5b.chat`` window, a decode step and a 256-token prefill chunk;
device ops and the benchmark's host spans)."""
from pathlib import Path

import pytest

from bench import trace as TR

DATA = Path(__file__).resolve().parent / "data"
DEV = "/device:TPU:0"


def op(name, start, dur, plane=DEV):
    return TR.Event(plane, TR.OPS_LINE, name, start, dur)


def span(name, start, dur):
    return TR.Event(TR.HOST_PLANE, "python3", name, start, dur)


def test_union_merges_overlaps():
    assert TR.union_ns([(0, 10), (5, 15), (20, 30), (30, 31)]) == 26
    assert TR.union_ns([]) == 0


def test_hlo_names():
    e = op("%paged_attention.6 = bf16[32,1,1536]{2,1,0} custom-call(s32[32]"
           " %a), custom_call_target=\"tpu_custom_call\"", 0, 1)
    assert TR.op_family(e) == "paged_attention"
    assert TR.opcode(e) == "custom-call"
    e = op("%all-reduce.3 = f32[8,4096]{1,0} all-reduce(f32[8,4096] %x)", 0, 1)
    assert TR.opcode(e) == "all-reduce"
    assert TR.op_family(op("fusion.12", 0, 1)) == "fusion"


def test_reduce_counts_busy_ops_and_gaps():
    events = [
        op("%while.2 = (s32[]) while((s32[]) %t), body=%b", 0, 100),
        op("%matrixflow_gemm_block_major.1 = bf16[8] custom-call(bf16[8] %x)",
           0, 40),
        op("%paged_attention.2 = bf16[8] custom-call(bf16[8] %x)", 40, 60),
        op("%fusion.3 = bf16[8] fusion(bf16[8] %x), kind=kLoop", 150, 50),
        span("step", 0, 120), span("submit", 100, 40),
        span("client", 0, 300),
    ]
    red = TR.reduce(events, 0, 300)
    assert red.busy_s[DEV] == pytest.approx(150e-9)
    assert red.op_s[DEV]["paged_attention"] == pytest.approx(60e-9)
    assert "while" not in red.op_s[DEV]            # a container, not work
    assert red.mean_op_s(TR.GEMM_OPS) == pytest.approx(40e-9)
    assert red.mean_op_s(TR.ATTENTION_OPS) == pytest.approx(60e-9)
    # gaps [100, 150) in "submit" (innermost) and [200, 300) in "client"
    assert red.gaps == [("client", pytest.approx(100e-9)),
                        ("submit", pytest.approx(50e-9))]


def test_window_clips_events():
    red = TR.reduce([op("fusion.1", 0, 100)], 50, 150)
    assert red.busy_s[DEV] == pytest.approx(50e-9)
    assert red.window_s == pytest.approx(100e-9)


def test_recorded_chat_slice():
    events = TR.load_json(DATA / "qwen2-chat.events.json.gz")
    win = next(e for e in events if e.name == "window")
    red = TR.reduce(events, win.start_ns, win.end_ns)
    assert red.window_s == pytest.approx(3.1)
    busy = red.busy_s[DEV]
    assert 0.95 * red.window_s < busy <= red.window_s
    # the paged kernel holds the device; the GEMMs are a few percent
    attn = red.mean_op_s(TR.ATTENTION_OPS)
    gemm = red.mean_op_s(TR.GEMM_OPS)
    assert attn > 0.8 * busy and 0.01 * busy < gemm < 0.1 * busy
    assert red.top_ops(1)[0][0] == "paged_attention"
    assert sum(red.op_s[DEV].values()) <= busy * 1.0001
    # the gaps fall between engine calls, in the benchmark's spans
    assert {name for name, _ in red.gaps[:5]} <= {"step", "submit"}
    assert red.gaps[0][1] < 0.01

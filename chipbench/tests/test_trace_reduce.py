"""The trace reduction: busy as a union, idle share, kernel time per
program and span self time, on hand-made events and on a small trace
recorded on a TPU v5e (``testdata/small.xplane.pb``: a jitted cumsum,
the distance kernel, inside annotations ``outer`` > ``inner``)."""
from pathlib import Path

import pytest

import trace_reduce

FIXTURE = Path(__file__).resolve().parents[1] / "testdata" / "small.xplane.pb"


def test_union_merges_overlaps_and_gaps():
    total, merged = trace_reduce.union_seconds(
        [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert merged == [[0, 20], [30, 45], [50, 50]]
    assert total == pytest.approx(35e-9)


def test_span_self_time_subtracts_nested_spans():
    ev = [("t1", "outer", 0, 100), ("t1", "inner", 10, 40),
          ("t1", "inner", 50, 60), ("t1", "leaf", 15, 20),
          ("t2", "outer", 0, 50)]
    got = trace_reduce.span_self_seconds(ev)
    assert got["outer"] == pytest.approx((100 - 30 - 10 + 50) / 1e9)
    assert got["inner"] == pytest.approx((30 - 5 + 10) / 1e9)
    assert got["leaf"] == pytest.approx(5 / 1e9)
    # a span that overlaps the open one without nesting is counted whole
    got = trace_reduce.span_self_seconds([("t", "a", 0, 10),
                                          ("t", "b", 5, 20)])
    assert got == {"a": pytest.approx(10e-9), "b": pytest.approx(15e-9)}


def test_recorded_tpu_trace():
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(FIXTURE))
    s = trace_reduce.reduce(pd, chips=1, window_s=1.0)
    assert s["devices"] == 1
    ops = sum(s["ops"].values())
    # busy is the union: never more than the summed op time, never 0
    assert s["busy_s"] > 0
    assert s["op_events"] == 25
    assert set(s["kernels"]) == {"match_valid_pallas"}
    assert 0 < s["kernels"]["match_valid_pallas"] < ops
    assert any(k.startswith("jit_match_valid_pallas/") for k in s["ops"])
    # three programs ran: busy is their union, no more than the window
    assert s["busy_ops_s"] <= s["busy_s"]
    spans = s["span_self_s"]
    assert spans["inner"] > 0.005 and spans["outer"] > 0.01
    assert len(s["breakdown"]["device_ops"]) <= trace_reduce.TOP
    assert s["breakdown"]["idle_gaps"][0][1] > 0


def test_op_and_module_names():
    ev = ('%gotoh_forward_pallas.1 = (s8[7,16640,16640]) custom-call(s32[7,2] '
          '%pad.6), custom_call_target="tpu_custom_call", operand_layout')
    assert trace_reduce.op_name(ev) == "gotoh_forward_pallas"
    assert trace_reduce.is_kernel(ev)
    assert trace_reduce.op_name("%while.20 = (s32[]) while(...)") == "while"
    assert trace_reduce.op_name("%copy = f32[4] copy(f32[4] %x)") == "copy"
    assert not trace_reduce.is_kernel("%fusion.3 = f32[4] fusion(...)")
    assert trace_reduce.module_name("jit_f(1234)") == "jit_f"


def test_cut_device_record_is_detected():
    host = [("python", "msa_run", 0, 9e9), ("python", "map1", 1e8, 8e9),
            ("python", "write", 8.5e9, 9e9)]
    assert not trace_reduce.device_record_cut(7.9e9, host)
    host.append(("python", "map1", 9.1e9, 12e9))
    assert trace_reduce.device_record_cut(7.9e9, host)
    assert not trace_reduce.device_record_cut(9.05e9, host)


def test_truncated_trace_silences_whole_window_metrics():
    import harness
    tr = {"truncated": True, "busy_s": 5.0, "window_s": 6.0, "devices": 1,
          "kernels": {"gotoh_forward_pallas": 2.0}}
    ctx = {"trace": tr, "work": {"dp_cells": 10 ** 10}}
    for name in ("idle_share.batch", "sw.gcups.batch"):
        assert harness.load_module("metrics", name).read(ctx) is None

"""The end-to-end and per-layer arithmetic on numbers worked by hand."""
import pytest

import harness
import stats

def metric(name):
    return harness.load_module("metrics", name)


def test_family_s_counts_the_family_in_flight():
    # 3 families done by 30 s, the 4th started at 29 s and ended at 38 s:
    # it is finished and counted, so 38 s over 4 families
    assert stats.per_family_seconds(38.0, 4) == 9.5
    with pytest.raises(ValueError):
        stats.per_family_seconds(30.0, 0)


def test_sw_gcups():
    ctx = {"trace": {"truncated": False,
                     "kernels": {"gotoh_forward_pallas": 2.0,
                                 "match_valid_pallas": 5.0}},
           "work": {"dp_cells": 95 * 16569 * 16569}}
    assert metric("sw.gcups.batch").read(ctx) == pytest.approx(
        95 * 16569 * 16569 / 2.0 / 1e9)
    ctx["trace"]["kernels"] = {"match_valid_pallas": 5.0}
    assert metric("sw.gcups.batch").read(ctx) is None
    assert metric("sw.gcups.batch").read({"trace": None, "work": {}}) is None


def test_idle_share():
    ctx = {"trace": {"busy_s": 7.5, "window_s": 10.0, "devices": 1,
                     "truncated": False}}
    assert metric("idle_share.batch").read(ctx) == pytest.approx(25.0)
    assert metric("idle_share.batch").read({"trace": None}) is None


def test_span_seconds_per_family():
    ctx = {"work": {"families": 4}, "spans": {"map1": 30.0, "tree": 2.0}}
    assert metric("map1_s.batch").read(ctx) == 7.5
    assert metric("tree_s.batch").read(ctx) == 0.5
    assert metric("tree_s.batch").read({"work": {"families": 0},
                                        "spans": {}}) is None

"""The per-layer readers of map(1)'s phases, assembly and FASTA write, on
numbers worked by hand."""
import pytest

import harness
from repro.obs.metrics import MetricsRegistry


def metric(name):
    return harness.load_module("metrics", name)


SPAN_READERS = [("map1.chain_s.batch", "map1.chain"),
                ("map1.dp_s.batch", "map1.dp"),
                ("assemble_s.batch", "assemble"),
                ("write_s.batch", "write")]


@pytest.mark.parametrize("name,span", SPAN_READERS)
def test_span_seconds_per_family(name, span):
    # 4 families: 6.0 s in the span in all -> 1.5 s a family
    ctx = {"work": {"families": 4}, "spans": {span: 6.0, "map1": 30.0}}
    assert metric(name).read(ctx) == 1.5


@pytest.mark.parametrize("name,span", SPAN_READERS)
def test_span_reader_silent_without_families_or_span(name, span):
    assert metric(name).read({"work": {"families": 0},
                              "spans": {span: 6.0}}) is None
    assert metric(name).read({"work": {}, "spans": {span: 6.0}}) is None
    assert metric(name).read({"work": {"families": 4},
                              "spans": {"map1": 30.0}}) is None


def cell_counters(reg, useful, pad, api="to_center"):
    reg.counter("repro_align_cells_total", "", ("api",)).labels(
        api=api).inc(useful)
    reg.counter("repro_align_pad_cells_total", "", ("api",)).labels(
        api=api).inc(pad)


def test_dp_useful_share():
    reg = MetricsRegistry()
    # 1,023 pairs of 1,500 x 1,500 run as 2 x 953 rows of 1,500 x 1,500
    useful = 1023 * 1500 * 1500
    cell_counters(reg, useful, 1906 * 1500 * 1500 - useful)
    # the pairs API is another layer's, and left out
    cell_counters(reg, 10, 990, api="pairs")
    got = metric("map1.dp_useful.batch").read({}, registry=reg)
    assert got == pytest.approx(100.0 * 1023 / 1906)


def test_dp_useful_silent_when_nothing_dispatched():
    reg = MetricsRegistry()
    assert metric("map1.dp_useful.batch").read({}, registry=reg) is None
    cell_counters(reg, 0, 0)
    cell_counters(reg, 10, 990, api="pairs")
    assert metric("map1.dp_useful.batch").read({}, registry=reg) is None


def test_chain_fail_share():
    reg = MetricsRegistry()
    fam = reg.counter("repro_kmer_chain_pairs_total", "", ("outcome",))
    # two families of 96: 190 chained pairs, 5 kept
    fam.labels(outcome="kept").inc(5)
    fam.labels(outcome="failed").inc(185)
    got = metric("map1.chain_fail.batch").read({}, registry=reg)
    assert got == pytest.approx(100.0 * 185 / 190)


def test_chain_fail_silent_when_nothing_chained():
    reg = MetricsRegistry()
    assert metric("map1.chain_fail.batch").read({}, registry=reg) is None
    reg.counter("repro_kmer_chain_pairs_total", "", ("outcome",))
    assert metric("map1.chain_fail.batch").read({}, registry=reg) is None

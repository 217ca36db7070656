"""map(1)'s spans and counters: the ``map1.chain`` / ``map1.dp`` split of
the stage, the k-mer chain outcome counter, and the DP cell counters as
the engine's calls were actually shaped (chunk duplicates and full-DP
fallback rows count as padding)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.align import AlignEngine
from repro.align import backends
from repro.align import engine as engine_mod
from repro.core import alphabet as ab
from repro.core.msa import MSAConfig, center_star_msa
from repro.obs import REGISTRY, TRACER, disabled
from repro.obs import trace as obs_trace

SUB = ab.dna_matrix().astype(jnp.float32)


def _value(name, **labels):
    fam = REGISTRY.snapshot().get(name, {"samples": []})
    return sum(s["value"] for s in fam["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


def _cells(api):
    return (_value("repro_align_cells_total", api=api),
            _value("repro_align_pad_cells_total", api=api))


def _traced_spans(trace_id):
    return [s for s in TRACER.spans() if s.trace_id == trace_id]


@pytest.fixture
def kmer_family(dna_family):
    """The shared 300 bp family plus one unrelated sequence, whose chain
    against the center fails: 8 chained pairs, some kept, one failed."""
    r = np.random.default_rng(7)
    return dna_family + ["".join(r.choice(list("ACGT"), 260))]


KMER = MSAConfig(method="kmer", k=8, backend="jnp")


def test_map1_phases_are_children_of_map1(kmer_family):
    with obs_trace.request_trace() as tid:
        center_star_msa(kmer_family, KMER)
    spans = _traced_spans(tid)
    (map1,) = [s for s in spans if s.name == "map1"]
    (chain,) = [s for s in spans if s.name == "map1.chain"]
    (dp,) = [s for s in spans if s.name == "map1.dp"]
    assert chain.parent_id == dp.parent_id == map1.span_id
    # nothing nests under the phases, and they run in turn inside map1
    assert not [s for s in spans
                if s.parent_id in (chain.span_id, dp.span_id)]
    assert map1.t0 <= chain.t0 <= chain.t1 <= dp.t0 <= dp.t1 <= map1.t1
    assert chain.attrs["n"] == len(kmer_family) - 1


def test_plain_method_has_no_chain_phase(dna_family):
    with obs_trace.request_trace() as tid:
        center_star_msa(dna_family, MSAConfig(method="plain", backend="jnp"))
    names = [s.name for s in _traced_spans(tid)]
    assert names.count("map1.dp") == 1 and "map1.chain" not in names


def test_chain_outcomes_count_every_pair(kmer_family):
    kept0 = _value("repro_kmer_chain_pairs_total", outcome="kept")
    failed0 = _value("repro_kmer_chain_pairs_total", outcome="failed")
    res = center_star_msa(kmer_family, KMER)
    kept = _value("repro_kmer_chain_pairs_total", outcome="kept") - kept0
    failed = _value("repro_kmer_chain_pairs_total",
                    outcome="failed") - failed0
    assert kept + failed == len(kmer_family) - 1
    assert failed == res.n_fallback >= 1
    assert kept >= 1


def test_disabled_is_bit_identical_and_records_nothing(kmer_family):
    on = center_star_msa(kmer_family, KMER)
    before = len(TRACER.spans())
    chained = _value("repro_kmer_chain_pairs_total")
    with disabled(), obs_trace.request_trace() as tid:
        off = center_star_msa(kmer_family, KMER)
    np.testing.assert_array_equal(on.msa, off.msa)
    assert (on.center_idx, on.n_fallback, on.width) == \
        (off.center_idx, off.n_fallback, off.width)
    assert len(TRACER.spans()) == before and not _traced_spans(tid)
    assert _value("repro_kmer_chain_pairs_total") == chained


def test_chunk_duplicates_count_as_pad(monkeypatch):
    # 5 queries of one bucket (width 40) against a 38-long center held in
    # a 40-wide array; a budget of two (40, 41) direction matrices splits
    # them 2 + 2 + 1, the last chunk filled to 2 with a duplicate row
    lens = np.array([40, 39, 37, 36, 34], np.int32)
    rng = np.random.default_rng(3)
    Q = rng.integers(0, 4, (5, 40)).astype(np.int8)
    b = np.full(40, 5, np.int8)
    b[:38] = rng.integers(0, 4, 38)
    monkeypatch.setattr(engine_mod, "DIRS_BUDGET_BYTES", 2 * 40 * 41)
    eng = AlignEngine(SUB, gap_open=3, gap_extend=1, gap_code=5,
                      backend="jnp")
    useful0, pad0 = _cells("to_center")
    eng.align_to_center(Q, lens, b, jnp.int32(38))
    useful, pad = (x - x0 for x, x0 in zip(_cells("to_center"),
                                           (useful0, pad0)))
    assert useful == (40 + 39 + 37 + 36 + 34) * 38          # 7,068
    # 3 calls of 2 rows, each row a 40 x 40 rectangle
    assert useful + pad == 3 * 2 * 40 * 40                  # 9,600
    assert pad == 9600 - 7068


def _overflow_pair():
    """One pair a band of 8 cannot hold: a 30-column insert (the banded
    overflow case of ``test_align_engine``)."""
    pre, post = "ACGTACGTACGT", "TTGGCCAATTGG"
    a = ab.DNA.encode(pre + post)
    t = ab.DNA.encode(pre + "C" * 30 + post)
    Q = np.zeros((1, 64), np.int8)
    Q[0, :len(a)] = a
    T = np.zeros((1, 64), np.int8)
    T[0, :len(t)] = t
    return Q, len(a), T, len(t)


@pytest.mark.parametrize("api", ["to_center", "pairs"])
def test_band_overflow_fallback_adds_its_cells(api):
    Q, la, T, lt = _overflow_pair()
    eng = AlignEngine(SUB, gap_open=3, gap_extend=1, gap_code=5,
                      backend="banded", band=8, bucket=False)
    useful0, pad0 = _cells(api)
    if api == "to_center":
        res = eng.align_to_center(Q, np.int32([la]), T[0], jnp.int32(lt))
    else:
        res = eng.align_pairs(Q, np.int32([la]), T, np.int32([lt]))
    assert res.n_fallback == 1
    useful, pad = (x - x0 for x, x0 in zip(_cells(api), (useful0, pad0)))
    assert useful == 24 * 54
    # the banded call's 64 x 64 rectangle, then the full-DP fallback's
    assert useful + pad == 64 * 64 + 64 * 64


def test_pallas_group_slots_count_as_pad():
    """The pallas kernel runs pairs 8 to a program: 7 queries in one call
    dispatch 8 slots' cells, and the useful cells are the jnp backend's."""
    lens = np.array([40, 39, 37, 36, 34, 40, 33], np.int32)
    rng = np.random.default_rng(5)
    Q = rng.integers(0, 4, (7, 40)).astype(np.int8)
    b = np.full(40, 5, np.int8)
    b[:38] = rng.integers(0, 4, 38)
    got = {}
    for backend in ("jnp", "pallas"):
        eng = AlignEngine(SUB, gap_open=3, gap_extend=1, gap_code=5,
                          backend=backend)
        before = _cells("to_center")
        eng.align_to_center(Q, lens, b, jnp.int32(38))
        got[backend] = tuple(x - x0 for x, x0 in
                             zip(_cells("to_center"), before))
    useful, pad = got["pallas"]
    assert useful == got["jnp"][0] == int(lens.sum()) * 38
    assert sum(got["jnp"]) == 7 * 40 * 40
    assert useful + pad == 8 * 40 * 40


@pytest.mark.parametrize("n_pairs,length,calls,slots", [
    (1023, 1500, 2, 1920),       # rrna16s-nj: 2 chunks of 953 -> 960 slots
    (95, 16569, 14, 112),        # mtdna-msa: 14 chunks of 7 -> 8 slots
])
def test_pallas_cell_plans_count_group_slots(monkeypatch, n_pairs, length,
                                             calls, slots):
    """The benchmark cells' map(1) plans on the pallas backend, with the
    kernel call stubbed out: the direction budget splits the pairs into
    equal chunks, and each chunk counts its rows rounded up to whole
    8-pair groups."""
    def stub(Q, lens, b, lb, sub, **_):
        B, P = Q.shape[0], Q.shape[1] + b.shape[0]
        rows = jnp.zeros((B, P), jnp.int8)
        return backends.BatchAlignment(jnp.zeros((B,), jnp.float32), rows,
                                       rows, jnp.zeros((B,), jnp.int32),
                                       jnp.ones((B,), jnp.bool_))

    monkeypatch.setattr(backends, "pallas_align_batch", stub)
    eng = AlignEngine(SUB, gap_open=3, gap_extend=1, gap_code=5,
                      backend="pallas")
    Q = np.zeros((n_pairs, length), np.int8)
    lens = np.full(n_pairs, length, np.int32)
    calls0 = _value("repro_align_calls_total", api="to_center",
                    backend="pallas")
    before = _cells("to_center")
    eng.align_to_center(Q, lens, np.zeros(length, np.int8),
                        jnp.int32(length))
    useful, pad = (x - x0 for x, x0 in zip(_cells("to_center"), before))
    assert _value("repro_align_calls_total", api="to_center",
                  backend="pallas") - calls0 == calls
    assert useful == n_pairs * length * length
    assert useful + pad == slots * length * length

"""The mesh path (``dist.mapreduce.msa_over_mesh``) on four devices against
the host driver (``core.msa.center_star_msa``): rows, center and
``n_fallback``, chain outcomes, DP cell counts, spans, and the chain
program traced once per shard at the shard's full row count.

One subprocess with four forced host devices (the main pytest process
keeps the true device count) runs a seeded family in which some k-mer
chains hold and some fail: 11 queries over 4 shards in 2 chunks, padded
to 16 rows, on the ``jnp`` backend and on ``pallas`` in interpret mode.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, %r)
import json
import numpy as np
import jax

from repro.core import msa as msa_mod
from repro.core.msa import MSAConfig, center_star_msa
from repro.dist import mapreduce
from repro.launch.mesh import make_local_mesh
from repro.obs import REGISTRY, TRACER, disabled
from repro.obs import trace as obs_trace

assert jax.device_count() == 4

rng = np.random.default_rng(11)
base = "".join(rng.choice(list("ACGT"), 120))
def mut(s):
    s = list(s)
    for _ in range(3):
        s[rng.integers(0, len(s))] = "ACGT"[rng.integers(0, 4)]
    del s[rng.integers(0, len(s))]
    s.insert(rng.integers(0, len(s)), "ACGT"[rng.integers(0, 4)])
    if rng.random() < 0.5:
        del s[rng.integers(0, len(s))]
    return "".join(s)
# 9 close relatives (their chains hold) and 2 unrelated sequences (theirs
# fail): 12 sequences, 11 queries
seqs = ([base] + [mut(base) for _ in range(9)]
        + ["".join(rng.choice(list("ACGT"), n)) for n in (110, 118)])
mesh = make_local_mesh((4, 1), ("data", "model"))


def value(name, **labels):
    fam = REGISTRY.snapshot().get(name, {"samples": []})
    return sum(s["value"] for s in fam["samples"]
               if all(s["labels"].get(k) == v for k, v in labels.items()))


COUNTS = {"kept": ("repro_kmer_chain_pairs_total", {"outcome": "kept"}),
          "failed": ("repro_kmer_chain_pairs_total", {"outcome": "failed"}),
          "useful": ("repro_align_cells_total", {"api": "to_center"}),
          "pad": ("repro_align_pad_cells_total", {"api": "to_center"})}


def counted(fn):
    before = {k: value(n, **l) for k, (n, l) in COUNTS.items()}
    out = fn()
    return out, {k: value(n, **l) - before[k] for k, (n, l) in COUNTS.items()}


spy_shapes = []
real_chain = msa_mod.kmer_align_batch


def spy(Q, *args, **kw):
    spy_shapes.append(list(Q.shape))
    return real_chain(Q, *args, **kw)


out = {"n": len(seqs), "lens": [len(s) for s in seqs]}
for backend in ("jnp", "pallas"):
    cfg = MSAConfig(method="kmer", k=8, max_anchors=64, max_seg=48,
                    backend=backend)
    host, host_counts = counted(lambda: center_star_msa(seqs, cfg))
    spy_shapes.clear()
    msa_mod.kmer_align_batch = spy
    try:
        with obs_trace.request_trace() as tid:
            mesh_res, mesh_counts = counted(
                lambda: mapreduce.msa_over_mesh(seqs, cfg, mesh,
                                                map_chunks=2))
    finally:
        msa_mod.kmer_align_batch = real_chain
    spans = [s for s in TRACER.spans() if s.trace_id == tid]
    by_id = {s.span_id: s for s in spans}
    n_spans = len(TRACER.spans())
    with disabled(), obs_trace.request_trace() as tid_off:
        off, off_counts = counted(
            lambda: mapreduce.msa_over_mesh(seqs, cfg, mesh, map_chunks=2))
    out[backend] = {
        "rows_equal": bool(np.array_equal(mesh_res.msa, host.msa)),
        "host": [host.center_idx, host.n_fallback, host.width],
        "mesh": [mesh_res.center_idx, mesh_res.n_fallback, mesh_res.width],
        "host_counts": host_counts, "mesh_counts": mesh_counts,
        "chain_traces": list(spy_shapes),
        "spans": [{"name": s.name, "dist": s.attrs.get("dist"),
                   "parent": by_id[s.parent_id].name
                   if s.parent_id in by_id else None,
                   "t0": s.t0, "t1": s.t1} for s in spans],
        "off_equal": bool(np.array_equal(off.msa, mesh_res.msa)),
        "off": [off.center_idx, off.n_fallback, off.width],
        "off_counts": off_counts,
        "off_spans": len(TRACER.spans()) - n_spans,
        "off_traced": len([s for s in TRACER.spans()
                           if s.trace_id == tid_off]),
    }
print("RESULT " + json.dumps(out))
'''

BACKENDS = ["jnp", "pallas"]
N_SHARDS, MAP_CHUNKS, ROWS = 4, 2, 16          # 11 queries padded to 16


@pytest.fixture(scope="module")
def run():
    src = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT % src],
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


@pytest.mark.parametrize("backend", BACKENDS)
def test_mesh_rows_center_and_fallbacks_equal_the_host(run, backend):
    got = run[backend]
    assert got["rows_equal"]
    assert got["mesh"] == got["host"]
    # the family is built so that some chains hold and some fail
    assert 1 <= got["host"][1] < run["n"] - 1


@pytest.mark.parametrize("backend", BACKENDS)
def test_chain_traced_once_per_shard_at_full_rows(run, backend):
    (shape,) = run[backend]["chain_traces"]
    assert shape == [ROWS // N_SHARDS, max(run["lens"])]


@pytest.mark.parametrize("backend", BACKENDS)
def test_chain_outcomes_equal_the_host(run, backend):
    host, mesh = run[backend]["host_counts"], run[backend]["mesh_counts"]
    assert (mesh["kept"], mesh["failed"]) == (host["kept"], host["failed"])
    assert mesh["kept"] + mesh["failed"] == run["n"] - 1
    assert mesh["failed"] == run[backend]["mesh"][1]


@pytest.mark.parametrize("backend", BACKENDS)
def test_dp_cells_count_as_dispatched(run, backend):
    lens = run["lens"]
    center = run[backend]["mesh"][0]
    lmax = max(lens)
    useful = sum(n for i, n in enumerate(lens) if i != center) * lens[center]
    # 2 rows a chunk: the pallas kernel's group of 8 slots, jnp's 2 rows
    slots = 8 if backend == "pallas" else ROWS // (N_SHARDS * MAP_CHUNKS)
    mesh = run[backend]["mesh_counts"]
    assert mesh["useful"] == useful
    assert mesh["useful"] + mesh["pad"] == \
        N_SHARDS * MAP_CHUNKS * slots * lmax * lmax


@pytest.mark.parametrize("backend", BACKENDS)
def test_map1_phases_sit_under_map1_with_dist(run, backend):
    spans = run[backend]["spans"]
    (map1,) = [s for s in spans if s["name"] == "map1"]
    phases = [s for s in spans if s["name"].startswith("map1.")]
    assert [s["name"] for s in phases] == ["map1.place", "map1.chain",
                                           "map1.dp"]
    assert all(s["parent"] == "map1" and s["dist"] is True for s in phases)
    assert map1["dist"] is True
    # the phases run in turn inside map1
    t = [map1["t0"]] + [x for s in phases for x in (s["t0"], s["t1"])]
    assert t == sorted(t) and t[-1] <= map1["t1"]


@pytest.mark.parametrize("backend", BACKENDS)
def test_disabled_is_bit_identical_and_records_nothing(run, backend):
    got = run[backend]
    assert got["off_equal"] and got["off"] == got["mesh"]
    assert got["off_spans"] == 0 and got["off_traced"] == 0
    assert all(v == 0 for v in got["off_counts"].values())


# ---------------------------------------------- chain calls within the budget

def _chain_programs(max_anchors=256, max_seg=64, k=11, L=2000):
    import jax.numpy as jnp

    from repro.core import alphabet as ab
    from repro.dist import mapreduce
    from repro.launch.mesh import make_local_mesh

    mesh = make_local_mesh((1, 1), ("data", "model"))
    progs = mapreduce.distributed_center_star(
        mesh, method="kmer", sub=jnp.asarray(ab.dna_matrix()), gap_code=5,
        out_len=2 * L + 64, num_slots=L + 1, gap_open=3, gap_extend=1, k=k,
        max_anchors=max_anchors, max_seg=max_seg)
    return mesh, progs


def _chain_shapes(mesh, rows, L, k):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    def sd(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    return (sd((rows, L), jnp.int8, P("data", None)),
            sd((rows,), jnp.int32, P("data")), sd((L,), jnp.int8, P()),
            sd((), jnp.int32, P()), sd((4 ** k, 4), jnp.int32, P()))


def test_chain_temporaries_stay_within_the_budget_at_a_large_shard(
        monkeypatch):
    """A shard four times what the budget holds compiles to chain calls
    whose temporaries fit it; one call over the shard would not."""
    from repro.align import engine
    from repro.dist import mapreduce

    rows, L = 64, 2000
    budget = 16 * mapreduce.chain_row_bytes(256, 64)

    def temps():
        mesh, progs = _chain_programs(L=L)
        compiled = progs.chain.lower(*_chain_shapes(mesh, rows, L, 11)) \
            .compile()
        return compiled.memory_analysis().temp_size_in_bytes

    assert temps() > budget                   # the whole shard in one call
    monkeypatch.setattr(engine, "DIRS_BUDGET_BYTES", budget)
    assert temps() <= budget


@pytest.mark.parametrize("rows", [12, 11])
def test_chunked_chain_equals_one_call(monkeypatch, rows):
    """Chained rows and ``ok`` flags do not depend on how many calls the
    chain takes: 3 calls of 4 (12 rows) or of 4 with one empty pad row
    (11 rows) against one call."""
    import jax.numpy as jnp
    import numpy as np

    from repro.align import engine
    from repro.core import kmer_index
    from repro.dist import mapreduce
    from repro.dist import sharding as sh

    k, A, seg, L = 6, 48, 24, 150
    rng = np.random.default_rng(5)
    base = rng.integers(0, 4, L)
    Q = np.stack([np.where(rng.random(L) < (0.02 if i % 3 else 0.6),
                           rng.integers(0, 4, L), base)
                  for i in range(rows)]).astype(np.int8)
    lens = rng.integers(L - 20, L + 1, rows).astype(np.int32)
    center = base.astype(np.int8)

    def chain():
        mesh, progs = _chain_programs(max_anchors=A, max_seg=seg, k=k, L=L)
        table = kmer_index.build_center_index(jnp.asarray(center), L, k=k)
        out = progs.chain(sh.shard_rows(Q, mesh, "data"),
                          sh.shard_rows(lens, mesh, "data"),
                          sh.broadcast(center, mesh), jnp.int32(L),
                          sh.broadcast(table, mesh))
        return [np.asarray(o) for o in out]

    whole = chain()
    monkeypatch.setattr(engine, "DIRS_BUDGET_BYTES",
                        4 * mapreduce.chain_row_bytes(A, seg))
    chunked = chain()
    assert 0 < whole[2].sum() < rows          # some chains hold, some fail
    for a, b in zip(whole, chunked):
        np.testing.assert_array_equal(a, b)

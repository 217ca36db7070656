"""repro.phylo: tiled distance parity, streamed medoids, the HPTree
pipeline's memory bound + dense equivalence, the TreeEngine registry, the
mesh strip hook, and the tree_run launcher at N=2000."""
import json

import jax.numpy as jnp
import numpy as np

from repro.core import alphabet as ab
from repro.core import cluster, distance, treeio
from repro.data import SimConfig, simulate_family
from repro.launch import tree_run
from repro.phylo import (TileAccountant, TileContext, TreeEngine,
                         resolve_tree_backend, tiled_phylogeny)

GAP, NCH = ab.DNA.gap_code, ab.DNA.n_chars


def _ctx(**kw):
    return TileContext(gap_code=GAP, n_chars=NCH, **kw)


def _rand_msa(n, L, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, GAP + 1, (n, L)).astype(np.int8)  # incl. gaps


def _aligned_family(n, L=300, sub=0.03, seed=0):
    """Substitution-only family: equal-length rows == already aligned."""
    fam = simulate_family(SimConfig(n_leaves=n, root_len=L, branch_sub=sub,
                                    branch_indel=0.0, seed=seed))
    S, _ = ab.encode_batch(fam.seqs, ab.DNA)
    return fam, np.asarray(S)


def _dense(msa, correct=True):
    return np.asarray(distance.distance_matrix(
        jnp.asarray(msa), gap_code=GAP, n_chars=NCH, correct=correct))


# ----------------------------------------------------------------- tiles


def test_tiled_full_matches_dense_exactly():
    """Tile-assembled matrix == dense, incl. N not divisible by the tile."""
    for n, L, rb, cb in [(30, 70, 16, 16), (33, 64, 8, 16),
                         (64, 128, 16, 64), (13, 40, 5, 7)]:
        msa = _rand_msa(n, L, seed=n)
        tiled = _ctx(row_block=rb, col_block=cb).full(msa)
        np.testing.assert_array_equal(tiled, _dense(msa))


def test_tiled_full_uncorrected_parity():
    msa = _rand_msa(21, 50, seed=9)
    tiled = _ctx(row_block=8, col_block=6, correct=False).full(msa)
    np.testing.assert_array_equal(tiled, _dense(msa, correct=False))


def test_streamed_medoids_match_dense():
    """greedy_k_center picks the same medoids as the (m, m) dense helper."""
    msa = _rand_msa(40, 80, seed=3)
    dense_med = cluster.farthest_point_medoids(_dense(msa), 5)
    tiled_med = _ctx(row_block=16).greedy_k_center(msa, 5)
    np.testing.assert_array_equal(tiled_med, dense_med)


def test_strips_respect_budget():
    """Exactly one row-block strip resident at a time while streaming."""
    msa = _rand_msa(50, 60, seed=1)
    acct = TileAccountant()
    ctx = _ctx(row_block=16, accountant=acct)
    for start, stop, strip in ctx.strips(msa):
        assert strip.shape == (stop - start, 50)
        assert acct.resident == 16 * 50 * 4
    assert acct.resident == 0
    assert acct.peak == 16 * 50 * 4


def _mesh_parity(use_kernel):
    """Shard-mapped count strips and nearest-anchor counts (the
    dist.mapreduce hooks) give the host tiles' distances bit for bit: the
    mesh moves only exact counts, and the JC69 tail is the host's."""
    from repro.launch.mesh import make_local_mesh
    msa = _rand_msa(39, 64, seed=7)
    mesh = make_local_mesh((1, 1), ("data", "model"))
    out = np.zeros((39, 39), np.float32)
    host = np.zeros((39, 39), np.float32)
    for start, stop, strip in _ctx(row_block=16, mesh=mesh,
                                   use_kernel=use_kernel).strips(msa):
        out[start:stop] = strip
    for start, stop, strip in _ctx(row_block=16,
                                   use_kernel=use_kernel).strips(msa):
        host[start:stop] = strip
    np.testing.assert_array_equal(out, host)
    np.fill_diagonal(out, 0.0)
    np.testing.assert_allclose(out, _dense(msa), rtol=1e-5, atol=1e-6)

    # the assignment stage's shard-mapped path (rows sharded, anchors
    # replicated) against the host strips
    ctx = _ctx(row_block=16, mesh=mesh, use_kernel=use_kernel)
    xd = ctx.nearest(msa, msa[:5])
    host_xd = _ctx(row_block=16, use_kernel=use_kernel).nearest(msa, msa[:5])
    np.testing.assert_array_equal(xd, host_xd)
    ctx.release(xd)
    assert ctx.accountant.resident == 0


def test_mesh_strip_hook_parity():
    """Mesh hooks on the jnp counts == host tiles, bit for bit."""
    _mesh_parity(use_kernel=False)


def test_mesh_strip_hook_kernel_parity():
    """Mesh hooks on the distance kernel (interpreted off the TPU) ==
    host kernel tiles, bit for bit — the chip's --dist --tree tiled path."""
    _mesh_parity(use_kernel=True)


# ------------------------------------------------------------- pipeline


def test_dense_vs_tiled_rf_zero():
    """Satellite: RF == 0 between dense and tiled NJ trees on clean data."""
    _, msa = _aligned_family(40, sub=0.02, seed=11)
    kw = dict(gap_code=GAP, n_chars=NCH, seed=0)
    dense_tree = TreeEngine(backend="dense", **kw).build(msa)
    tiled_tree = TreeEngine(backend="tiled", row_block=64, col_block=16,
                            **kw).build(msa)
    assert tiled_tree.backend == "tiled-exact"
    assert treeio.rf_distance(dense_tree, tiled_tree, 40) == 0


def test_tiled_pipeline_equals_dense_cluster_path():
    """Same config -> the tiled pipeline is bit-identical to core.cluster."""
    _, msa = _aligned_family(150, L=200, seed=5)
    cfg = cluster.ClusterConfig(target_cluster=24, seed=2)
    cp_dense = cluster.cluster_phylogeny(msa, gap_code=GAP, n_chars=NCH,
                                         cfg=cfg)
    cp_tiled = tiled_phylogeny(msa, tiles=_ctx(row_block=32), cfg=cfg)
    np.testing.assert_array_equal(cp_tiled.medoids, cp_dense.medoids)
    np.testing.assert_array_equal(cp_tiled.assignments, cp_dense.assignments)
    np.testing.assert_array_equal(cp_tiled.children, cp_dense.children)
    assert treeio.to_newick(cp_tiled.children, cp_tiled.blen, cp_tiled.root) \
        == treeio.to_newick(cp_dense.children, cp_dense.blen, cp_dense.root)


def test_tiled_pipeline_covers_all_leaves_exactly_once():
    n = 150
    _, msa = _aligned_family(n, L=200, seed=5)
    cp = tiled_phylogeny(msa, tiles=_ctx(row_block=32),
                         cfg=cluster.ClusterConfig(target_cluster=24, seed=2))
    sets = treeio.leaf_sets(cp.children, cp.root, n)
    assert sets[cp.root] == frozenset(range(n))
    # every leaf referenced as a child exactly once
    refs = [int(x) for row in cp.children for x in row if 0 <= x < n]
    assert sorted(refs) == list(range(n))


def test_tiled_pipeline_memory_bound():
    """Resident distance storage stays <= one (row_block, N) strip."""
    n = 300
    _, msa = _aligned_family(n, L=200, seed=8)
    acct = TileAccountant()
    tiled_phylogeny(msa, tiles=_ctx(row_block=32, accountant=acct),
                    cfg=cluster.ClusterConfig(target_cluster=24, seed=0))
    assert 0 < acct.peak <= 32 * n * 4
    assert acct.resident == 0


# --------------------------------------------------------------- engine


def test_resolve_tree_backend():
    r = resolve_tree_backend
    assert r("auto", n=40, cluster_threshold=64) == "dense"
    assert r("auto", n=200, cluster_threshold=64) == "cluster"
    assert r("auto", n=5000, cluster_threshold=64, row_block=128) == "tiled"
    assert r("auto", n=200, cluster_threshold=199) == "cluster"
    assert r("cluster", n=40, cluster_threshold=64) == "dense"
    assert r("cluster", n=65, cluster_threshold=64) == "cluster"
    assert r("tiled", n=40, row_block=64) == "tiled-exact"
    assert r("tiled", n=200, row_block=64) == "tiled"
    assert r("dense", n=10**6) == "dense"
    try:
        r("hptree", n=10)
        assert False, "expected ValueError"
    except ValueError:
        pass


def test_engine_two_leaves():
    """A 2-sequence input still yields a tree (the old msa_run behavior)."""
    msa = _rand_msa(2, 60, seed=4)
    res = TreeEngine(gap_code=GAP, n_chars=NCH, backend="auto").build(msa)
    assert res.backend == "dense" and res.n_leaves == 2
    nwk = res.newick(["a", "b"])
    assert nwk.count(",") == 1 and "a" in nwk and "b" in nwk


def test_engine_cluster_threshold_gate():
    _, msa = _aligned_family(40, seed=3)
    kw = dict(gap_code=GAP, n_chars=NCH)
    assert TreeEngine(backend="cluster", cluster_threshold=64,
                      **kw).build(msa).backend == "dense"
    res = TreeEngine(backend="cluster", cluster_threshold=16,
                     target_cluster=12, **kw).build(msa)
    assert res.backend == "cluster"
    assert treeio.leaf_sets(res.children, res.root, 40)[res.root] \
        == frozenset(range(40))


# ------------------------------------------------------------ launchers


def test_tree_run_2000_tiled_within_budget(tmp_path):
    """Acceptance: tree_run on 2000 sequences with the tiled backend, peak
    resident distance storage <= one tile row-block strip."""
    n, L = 2000, 120
    rng = np.random.default_rng(0)
    base = rng.integers(0, 4, L).astype(np.int8)
    msa = np.tile(base, (n, 1))
    mask = rng.random((n, L)) < 0.05
    msa[mask] = rng.integers(0, 4, int(mask.sum())).astype(np.int8)
    fasta = tmp_path / "aligned.fasta"
    with open(fasta, "w") as f:
        for i in range(n):
            f.write(f">s{i}\n{ab.DNA.decode(msa[i])}\n")

    out = tmp_path / "tree_out"
    tree_run.main(["--fasta", str(fasta), "--out", str(out),
                   "--backend", "tiled", "--row-block", "128"])
    report = json.loads((out / "report.json").read_text())
    assert report["n_sequences"] == n
    assert report["backend"] == "tiled"
    stats = report["tile_stats"]
    assert stats["row_block_bytes"] == 128 * n * 4
    assert 0 < stats["peak_resident_bytes"] <= stats["row_block_bytes"]
    nwk = (out / "tree.nwk").read_text()
    assert nwk.count(",") == n - 1 and nwk.strip().endswith(";")


def test_msa_run_tree_flags(tmp_path):
    """msa_run: --tree tiled + --cluster-threshold + --tree-ll wiring."""
    fam = simulate_family(SimConfig(n_leaves=12, root_len=300,
                                    branch_sub=0.02, branch_indel=0.001,
                                    seed=6))
    fasta = tmp_path / "fam.fasta"
    with open(fasta, "w") as f:
        for nm, s in zip(fam.names, fam.seqs):
            f.write(f">{nm}\n{s}\n")
    from repro.launch import msa_run

    out = tmp_path / "out1"
    msa_run.main(["--fasta", str(fasta), "--out", str(out), "--method",
                  "kmer", "--k", "10", "--tree", "tiled"])
    report = json.loads((out / "report.json").read_text())
    assert report["tree_backend"] == "tiled-exact"    # 12 <= row_block
    assert "log_likelihood" not in report             # gated behind --tree-ll

    out2 = tmp_path / "out2"
    msa_run.main(["--fasta", str(fasta), "--out", str(out2), "--method",
                  "kmer", "--k", "10", "--tree", "cluster",
                  "--cluster-threshold", "4", "--tree-ll"])
    report2 = json.loads((out2 / "report.json").read_text())
    assert report2["tree_backend"] == "cluster"       # 12 > threshold 4
    assert np.isfinite(report2["log_likelihood"])

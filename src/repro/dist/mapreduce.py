"""Shard-mapped center-star MSA: the paper's Fig. 3 pipeline on a mesh.

Spark terms -> mesh terms:

  RDD of sequence shards     leading-dim sharding over the 'data' axis
  broadcast(center, index)   replicated operands (PartitionSpec())
  map(1)  align-to-center    jitted ``core.msa.kmer_align_batch`` /
                             a ``repro.align`` backend primitive per shard
                             (jnp scan, Pallas SW kernel, or banded DP —
                             jnp or native Pallas)
  reduce(1) merge profiles   local columnwise max, then one ``pmax``
  map(2)  re-emit rows       ``core.centerstar.build_rows`` per shard

``distributed_center_star`` builds the pipeline as two shard-mapped
programs, the way the host driver splits map(1). ``chain`` (k-mer method
only) chains a shard's queries against the center in as few calls as fit
the per-call budget (one call a shard unless the shard is very large) and
returns the anchored rows with per-pair ``ok`` flags. ``align`` runs the
full DP in ``map_chunks`` sequential chunks (O(n·m) directions a pair
bound the chunk), keeps the chained rows
where ``ok``, merges the insert profiles and re-emits the rows. The only
cross-device traffic is the (num_slots,) int32 profile pmax — the paper's
observation that center-star reduces to an embarrassingly parallel map
plus a tiny reduction.

Shard-count bookkeeping: shard_map needs the sequence count to divide the
data-axis size; ``pad_rows`` adds empty-query rows (length 0) that align to
all-gap rows and contribute nothing to the merged profile, and
``unpad_rows`` drops them again.

Consumers: ``launch/msa_run --dist`` (batch CLI), ``repro.serve`` (the
web service routes requests of >= ``dist_threshold`` sequences through
``msa_over_mesh`` and shard-maps ``/tree`` distance strips through
``distance_strip_over_mesh`` / ``nearest_anchor_over_mesh`` on the same
mesh), ``repro.phylo.ml`` (ML bootstrap replicates fan out through
``bootstrap_over_mesh``), ``repro.phylo.treesearch`` (the K-start
NNI+SPR fleet scores its candidate block through
``treesearch_over_mesh``), and ``launch/dryrun`` (512-device
lower+compile sweeps).
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..align import AlignEngine
from ..align import engine as _engine
from ..align.engine import _pad_cols
from ..core import centerstar, kmer_index
from ..core import msa as msa_mod
from ..obs import metrics as _obs
from ..obs import trace as _trace
from . import sharding as sh

_C_MAP_CALLS = _obs.counter("repro_dist_map_calls_total",
                            "host-side mesh pipeline invocations", ("stage",))


def chain_row_bytes(max_anchors: int, max_seg: int) -> int:
    """Device temporaries of the chain program, a query row: its
    ``max_anchors + 1`` inter-anchor segment DPs of (max_seg + 1)^2 cells
    dominate, whatever the sequence length. Six bytes a cell bounds what
    compiles read at the defaults (256, 64): ~5.8 MB a row for a v5e at
    168 and 336 rows, ~2.4 MB on the CPU."""
    return 6 * (max_anchors + 1) * (max_seg + 1) ** 2


def pad_rows(x, multiple_of: int, fill=0):
    """Pad the leading dim up to a multiple of ``multiple_of``.

    Returns (padded, original_n). For query batches pass ``fill=0`` (a valid
    alphabet code) and pad the matching ``lens`` with 0 so padded rows align
    as empty queries.
    """
    x = np.asarray(x)
    n = x.shape[0]
    rem = (-n) % multiple_of
    if rem == 0:
        return x, n
    pad = np.full((rem,) + x.shape[1:], fill, x.dtype)
    return np.concatenate([x, pad], axis=0), n


def unpad_rows(x, n: int):
    """Drop the rows ``pad_rows`` added."""
    return x[:n]


def _chunked(f, n_chunks: int, *arrs):
    """Run ``f`` over ``n_chunks`` sequential slices of the leading dim.

    Bounds per-device temp memory (the DP direction matrices live only for
    one chunk); the chunk loop is a lax.map so it stays inside jit.
    """
    if n_chunks <= 1:
        return f(*arrs)
    resh = tuple(a.reshape((n_chunks, a.shape[0] // n_chunks) + a.shape[1:])
                 for a in arrs)
    out = jax.lax.map(lambda xs: f(*xs), resh)
    return jax.tree.map(lambda o: o.reshape((-1,) + o.shape[2:]), out)


class CenterStarPrograms(NamedTuple):
    """The mesh pipeline of one problem geometry, as two jitted programs.

    ``chain(Q, lens, center, lc, table) -> (a_rows, b_rows, ok)``, rows
    and flags sharded like ``Q`` (``None`` unless ``method='kmer'``);
    ``align(Q, lens, center, lc[, a_rows, b_rows, ok]) -> (rows, G)``, the
    chained rows and flags passed on only for ``method='kmer'``.
    ``engine`` is the configured map(1) DP engine, which counts the DP
    cells the ``align`` program dispatches.
    """
    chain: Optional[Callable]
    align: Callable
    engine: AlignEngine


def distributed_center_star(mesh: Mesh, *, method: str, sub, gap_code: int,
                            out_len: int, num_slots: int, gap_open: int,
                            gap_extend: int, k: int = 11, stride: int = 1,
                            max_anchors: int = 256, max_seg: int = 64,
                            map_chunks: int = 1, data_axis: str = "data",
                            local: bool = False, backend: str = "auto",
                            band: int = 64) -> CenterStarPrograms:
    """Build the distributed pipeline for one problem geometry.

    Returns ``CenterStarPrograms``: ``align(Q, lens, center, lc,
    *chain(Q, lens, center, lc, table))`` on the k-mer method, else
    ``align(Q, lens, center, lc)``, gives ``(rows, G)`` where ``rows`` is
    (N, out_len) int8 sharded over ``data_axis`` and ``G`` the merged
    (num_slots,) insert profile, replicated. Inputs are placed with
    ``sharding.shard_rows`` / ``sharding.broadcast``; N must divide the
    data-axis size times ``map_chunks`` (``pad_rows``).

    The k-mer method chains each shard's queries in as few calls as its
    per-row temporaries (``chain_row_bytes``) allow within
    ``align.engine.DIRS_BUDGET_BYTES`` (one call a shard at 168 rows of
    mtDNA), then re-aligns every query with the full global DP, chunk by
    chunk, keeping the DP's rows where the chain failed: the host
    driver's result exactly.

    ``backend`` picks the map(1) DP primitive from the ``repro.align``
    registry (jnp scan / Pallas SW kernel / banded O(n·band) DP as a jnp
    scan or the native ``banded-pallas`` wavefront kernel). The banded
    backends accept their result in-graph without the host driver's
    per-pair overflow fallback — re-aligning in-graph would materialize
    the full direction matrix for every pair, exactly what banding is
    there to avoid; size the band for the workload instead.
    """
    if method not in ("kmer", "plain", "sw"):
        raise ValueError(f"unknown method {method!r}")
    # rows a chain call may hold within the engine's per-call budget
    chain_rows = max(1, _engine.DIRS_BUDGET_BYTES
                     // chain_row_bytes(max_anchors, max_seg))
    sub = jnp.asarray(sub, jnp.float32)
    engine = AlignEngine(sub, gap_open=gap_open, gap_extend=gap_extend,
                         gap_code=gap_code, backend=backend, band=band,
                         local=local, bucket=False)

    def _dp(Q, lens, center, lc, *, dp_local=local):
        res = engine.batch_fn(local=dp_local)(Q, lens, center, lc)
        return res.a_row, res.b_row

    def _chain(Q, lens, center, lc, table):
        def f(q, l):
            return msa_mod.kmer_align_batch(
                q, l, center, lc, table, sub, k=k, stride=stride,
                max_anchors=max_anchors, max_seg=max_seg, gap_open=gap_open,
                gap_extend=gap_extend, gap_code=gap_code)
        rows = Q.shape[0]                      # this shard's rows
        n = -(-rows // chain_rows)
        if n <= 1:
            return f(Q, lens)
        # equal calls of the fewest rows that fit; the pad rows are empty
        pad = n * -(-rows // n) - rows
        out = _chunked(f, n, jnp.pad(Q, ((0, pad), (0, 0))),
                       jnp.pad(lens, (0, pad)))
        return jax.tree.map(lambda o: o[:rows], out)

    def _keep_chained(Q, lens, center, lc, a_rows, b_rows, ok):
        # the kmer assembly is global; its fallback must be too
        da, db = _dp(Q, lens, center, lc, dp_local=False)
        width = max(a_rows.shape[-1], da.shape[-1])
        return (jnp.where(ok[:, None], _pad_cols(a_rows, width, gap_code),
                          _pad_cols(da, width, gap_code)),
                jnp.where(ok[:, None], _pad_cols(b_rows, width, gap_code),
                          _pad_cols(db, width, gap_code)))

    def _align(Q, lens, center, lc, *chained):
        if method == "kmer":
            a_rows, b_rows = _chunked(
                lambda q, l, a, b, o: _keep_chained(q, l, center, lc,
                                                    a, b, o),
                map_chunks, Q, lens, *chained)
        else:
            a_rows, b_rows = _chunked(
                lambda q, l: _dp(q, l, center, lc), map_chunks, Q, lens)
        g = centerstar.gap_profiles(a_rows, b_rows, gap_code=gap_code,
                                    num_slots=num_slots)
        G = jax.lax.pmax(jnp.max(g, axis=0), data_axis)          # reduce(1)
        rows = _chunked(
            lambda a, b: centerstar.build_rows(a, b, G, gap_code=gap_code,
                                               out_len=out_len),
            map_chunks, a_rows, b_rows)
        return rows, G

    row2 = P(data_axis, None)
    row1 = P(data_axis)
    query = (row2, row1, P(), P())
    chain = None
    if method == "kmer":
        chain = jax.jit(sh.shard_map(
            _chain, mesh, in_specs=query + (P(),),
            out_specs=(row2, row2, row1), check_vma=False))
        query = query + (row2, row2, row1)
    align = jax.jit(sh.shard_map(_align, mesh, in_specs=query,
                                 out_specs=(row2, P()), check_vma=False))
    return CenterStarPrograms(chain, align, engine)


def _count_fn(*, gap_code: int, n_chars: int, use_kernel: bool):
    """(a, b) -> (match, valid) exact f32 counts: the Pallas distance kernel
    or the jnp one-hot matmuls (``core.distance.match_valid_counts``)."""
    if use_kernel:
        from ..kernels.distance import match_valid_pallas
        return functools.partial(match_valid_pallas, gap_code=gap_code,
                                 n_chars=n_chars)
    from ..core import distance as dist_mod
    return functools.partial(dist_mod.match_valid_counts, gap_code=gap_code,
                             n_chars=n_chars)


def distance_strip_over_mesh(mesh: Mesh, *, gap_code: int, n_chars: int,
                             use_kernel: bool = False,
                             data_axis: str = "data"):
    """Tree-stage hook: jitted ``fn(rows_blk, S) -> (match, valid)``, each
    an (rb, N) count strip.

    The phylogeny analogue of the MSA map stage: ``S`` is the full aligned
    row set sharded over ``data_axis`` (place once with
    ``sharding.shard_rows``; pad with ``pad_rows`` first), ``rows_blk`` a
    replicated (row_block, L) block. Each device counts ``rows_blk``
    against its shard — a row-block x column-block tile, through the
    distance kernel when ``use_kernel`` — and the strip comes back
    concatenated over the column dim (out spec ``P(None, data_axis)``).
    The counts are exact, so ``repro.phylo.tiles.TileContext`` turns them
    into distances exactly as its host tiles do (bit-identical strips); it
    streams these strips so no host holds more than one.
    """
    count = _count_fn(gap_code=gap_code, n_chars=n_chars,
                      use_kernel=use_kernel)
    fn = sh.shard_map(count, mesh, in_specs=(P(), P(data_axis, None)),
                      out_specs=(P(None, data_axis), P(None, data_axis)),
                      check_vma=False)
    return jax.jit(fn)


def nearest_anchor_over_mesh(mesh: Mesh, *, gap_code: int, n_chars: int,
                             use_kernel: bool = False,
                             data_axis: str = "data"):
    """Tree-stage hook: jitted ``fn(S, anchors) -> (match, valid)``, each
    (N, k) counts.

    The assignment stage of the tiled HPTree pipeline: ``S`` is the full
    row set sharded over ``data_axis``, ``anchors`` the k medoid rows
    replicated — each device counts its rows against every medoid (the
    transpose of ``distance_strip_over_mesh``'s tiling, chosen because
    k << N so sharding the long axis is the one that balances).
    """
    count = _count_fn(gap_code=gap_code, n_chars=n_chars,
                      use_kernel=use_kernel)
    fn = sh.shard_map(count, mesh, in_specs=(P(data_axis, None), P()),
                      out_specs=(P(data_axis, None), P(data_axis, None)),
                      check_vma=False)
    return jax.jit(fn)


def bootstrap_over_mesh(mesh: Mesh, *, gap_code: int, n_chars: int,
                        correct: bool = True, data_axis: str = "data"):
    """Tree-stage hook: shard ML bootstrap replicates over the mesh.

    Returns jitted ``fn(patterns, W) -> (children (B, 2N-1, 2), blen)``.
    ``W`` is the (B, P) replicate site-weight matrix sharded over
    ``data_axis`` (pad B with ``pad_rows`` first — all-zero padding rows
    produce saturated-distance throwaway trees that ``unpad_rows``
    drops); ``patterns`` is the compressed site-pattern matrix,
    replicated. Each device runs weighted-distance + vmapped NJ for its
    replicates (``repro.phylo.ml.replicate_trees``) — embarrassingly
    parallel, and per-replicate math is independent of the partitioning,
    so a fixed seed is bit-reproducible across mesh shapes.
    """
    from ..phylo import ml as ml_mod

    def _rep(patterns, W):
        return ml_mod.replicate_trees(patterns, W, gap_code=gap_code,
                                      n_chars=n_chars, correct=correct)

    fn = sh.shard_map(_rep, mesh, in_specs=(P(), P(data_axis, None)),
                      out_specs=(P(data_axis, None, None),
                                 P(data_axis, None, None)),
                      check_vma=False)
    return jax.jit(fn)


def treesearch_over_mesh(mesh: Mesh, *, model: str, site_chunk: int = 2048,
                         data_axis: str = "data"):
    """Tree-stage hook: shard K-start tree-search candidate scoring.

    Returns jitted ``fn(patterns, weights, children_k, blen_k, order_k,
    params_k) -> (K, C) logL``. The per-search candidate blocks
    (``(K, C, 2N-1, 2)`` children/blen, ``(K, C, N-1)`` orders) and the
    per-search model parameters shard over ``data_axis`` (pad K with
    ``pad_rows`` first — all-zero padding rows score garbage trees that
    ``unpad_rows`` drops); the compressed site patterns and weights are
    replicated. Each device runs ``repro.phylo.treesearch.score_fleet``
    for its searches — per-(search, candidate) math is independent of
    the partitioning, so a fixed seed is bit-reproducible across mesh
    shapes (the same invariant ``bootstrap_over_mesh`` holds).
    """
    from ..phylo import treesearch as ts_mod

    def _score(patterns, weights, ch_k, bl_k, od_k, pr_k):
        return ts_mod.score_fleet(patterns, weights, ch_k, bl_k, od_k, pr_k,
                                  model=model, site_chunk=site_chunk)

    fn = sh.shard_map(_score, mesh,
                      in_specs=(P(), P(),
                                P(data_axis, None, None, None),
                                P(data_axis, None, None, None),
                                P(data_axis, None, None),
                                P(data_axis, None)),
                      out_specs=P(data_axis, None), check_vma=False)
    return jax.jit(fn)


def search_over_mesh(mesh: Mesh, *, k: int, stride: int = 1,
                     max_anchors: int = 32, max_seg: int = 1 << 20,
                     data_axis: str = "data"):
    """Search-stage hook: jitted seeding prefilter over a sharded DB.

    Returns ``fn(Q, qlens, dblens, tables) -> (B, D) anchor counts``.
    The per-sequence k-mer tables (not the rows — seeding only probes
    tables) are sharded over ``data_axis`` (place with
    ``sharding.shard_rows``; pad D with ``pad_rows`` first), the query
    batch is replicated — each device chains anchors for every
    (query, local DB row) pair and the count matrix comes back
    concatenated over the DB dim (out spec ``P(None, data_axis)``). Counts are per-pair integers independent of
    the partitioning, so results are bit-identical across mesh shapes —
    the invariant ``repro.search`` builds its mesh/host equivalence on.
    The candidate *rescoring* stays a host concern: the surviving pair
    set re-enters ``AlignEngine.align_pairs`` (pow2-bucketed), identical
    on every mesh because the surviving set is.
    """
    from ..search.engine import seed_counts_batch

    def _seed(Q, qlens, dblens, tables):
        return seed_counts_batch(Q, qlens, dblens, tables, k=k,
                                 stride=stride, max_anchors=max_anchors,
                                 max_seg=max_seg)

    fn = sh.shard_map(_seed, mesh,
                      in_specs=(P(), P(), P(data_axis),
                                P(data_axis, None, None)),
                      out_specs=P(None, data_axis), check_vma=False)
    return jax.jit(fn)


def center_row(center, lc, G, *, gap_code: int, out_len: int):
    """The broadcast center's own row in the merged frame (host-side wrap)."""
    return centerstar.center_msa_row(center, lc, G, gap_code=gap_code,
                                     out_len=out_len)


@functools.lru_cache(maxsize=8)
def _mesh_programs(mesh: Mesh, cfg, *, out_len: int, num_slots: int,
                   map_chunks: int, data_axis: str) -> CenterStarPrograms:
    """``distributed_center_star`` for an ``MSAConfig``, built once per
    geometry so later families of the same shapes reuse its traces."""
    return distributed_center_star(
        mesh, method=cfg.method, sub=cfg.matrix(),
        gap_code=cfg.alpha().gap_code, out_len=out_len, num_slots=num_slots,
        gap_open=cfg.gap_open, gap_extend=cfg.gap_extend, k=cfg.k,
        stride=cfg.stride, max_anchors=cfg.max_anchors, max_seg=cfg.max_seg,
        map_chunks=map_chunks, data_axis=data_axis, local=cfg.local,
        backend=cfg.backend, band=cfg.band)


def msa_over_mesh(seqs, cfg, mesh: Mesh, *, data_axis: str = "data",
                  map_chunks: Optional[int] = None, out_pad: int = 64):
    """Host driver: ``core.msa.center_star_msa`` semantics over a mesh.

    Handles everything the jitted programs cannot: center selection,
    padding the query count to the shard count, placing operands
    (``shard_rows``/``broadcast``), reading the chain outcomes, appending
    the center's own row, and trimming to the realized width. ``cfg`` is
    a ``core.msa.MSAConfig``. Returns a ``core.msa.MSAResult`` whose
    ``n_fallback`` counts the real pairs whose k-mer chain failed, as the
    host driver's does.

    ``map_chunks=None`` sizes the per-shard chunk loop so one chunk's
    full-DP direction matrices stay within
    ``align.engine.DIRS_BUDGET_BYTES``, as the host driver's calls do.

    Spans under ``map1`` (all with ``dist=True``): ``map1.place`` (the
    center k-mer index, the row padding and the placement of the operands
    on the mesh), ``map1.chain`` (k-mer method only: the chain program and
    the host read of its ``ok`` flags) and ``map1.dp`` (the DP, reduce(1)
    and map(2) program). ``map1.place`` and ``map1.dp`` end on their
    device work only when recorded. The chain outcomes of the real rows
    go to ``repro_kmer_chain_pairs_total``, and the DP cells to the
    engine's ``to_center`` counters as dispatched: every shard's every
    chunk, each of its rows (rounded up to the kernel's slot groups) a
    full Lmax x center-width rectangle.
    """
    alpha = cfg.alpha()
    gap = alpha.gap_code
    S, lens = msa_mod.encode_for_msa(seqs, cfg)
    N, Lmax = S.shape
    if N < 2:
        return msa_mod.MSAResult(np.asarray(S), 0, 0, Lmax, "first")
    with _trace.span("center", n=int(N), mode=cfg.center, dist=True):
        cidx, center_mode = msa_mod._select_center(S, lens, cfg)
    center, lc = S[cidx], lens[cidx]
    others = np.array([i for i in range(N) if i != cidx])
    n_shards = sh.axis_size(mesh, data_axis)
    if map_chunks is None:
        full_dp = cfg.backend not in ("banded", "banded-pallas")
        per = max(1, _engine.DIRS_BUDGET_BYTES // (Lmax * (Lmax + 1)))
        rows = -(-len(others) // n_shards)
        map_chunks = -(-rows // per) if full_dp else 1

    out_len = 2 * Lmax + out_pad
    num_slots = int(center.shape[0]) + 1
    per_chunk = -(-len(others) // (n_shards * map_chunks))
    _C_MAP_CALLS.labels(stage="msa").inc()
    with _trace.span("map1", n=int(N) - 1, method=cfg.method,
                     backend=cfg.backend, dist=True, n_shards=n_shards,
                     shard_rows=per_chunk * map_chunks,
                     map_chunks=map_chunks):
        progs = _mesh_programs(mesh, cfg, out_len=out_len,
                               num_slots=num_slots, map_chunks=map_chunks,
                               data_axis=data_axis)
        with _trace.span("map1.place", n=int(N) - 1, dist=True) as sp:
            # per-shard row count must also divide map_chunks for
            # _chunked's reshape
            Q, n_q = pad_rows(np.asarray(S)[others], n_shards * map_chunks)
            qlens, _ = pad_rows(np.asarray(lens)[others],
                                n_shards * map_chunks)
            operands = [sh.shard_rows(Q, mesh, data_axis),
                        sh.shard_rows(qlens, mesh, data_axis),
                        sh.broadcast(center, mesh), jnp.int32(lc)]
            table = None
            if cfg.method == "kmer":
                table = sh.broadcast(kmer_index.build_center_index(
                    center, lc, k=cfg.k), mesh)
            if sp is not None:
                jax.block_until_ready((operands, table))
        chained, n_fallback = (), 0
        if cfg.method == "kmer":
            with _trace.span("map1.chain", n=n_q, dist=True):
                chained = progs.chain(*operands, table)
                ok = np.asarray(chained[2])[:n_q]
            n_kept = int(ok.sum())
            n_fallback = n_q - n_kept
            msa_mod._M_CHAIN.labels(outcome="kept").inc(n_kept)
            msa_mod._M_CHAIN.labels(outcome="failed").inc(n_fallback)
        progs.engine.record_to_center(n_shards * map_chunks, per_chunk,
                                      qlens[:n_q], lc, Lmax,
                                      int(center.shape[0]))
        with _trace.span("map1.dp", n=n_q, dist=True) as sp:
            rows, G = progs.align(*operands, *chained)
            if sp is not None:
                jax.block_until_ready((rows, G))

    with _trace.span("assemble", n=int(N), dist=True):
        width = centerstar.msa_width(G, int(lc))
        if width > out_len:
            raise ValueError(
                f"merged width {width} exceeds out_len {out_len}; rerun "
                f"with a larger out_pad (sequences too diverged for 2*Lmax)")
        crow = center_row(center, lc, G, gap_code=gap, out_len=out_len)
        msa = np.full((N, out_len), gap, np.int8)
        msa[others] = unpad_rows(np.asarray(rows), n_q)
        msa[cidx] = np.asarray(crow)
    return msa_mod.MSAResult(msa[:, :width], int(cidx), n_fallback, width,
                             center_mode)

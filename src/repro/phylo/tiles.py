"""Tiled distance-matrix engine: (row-block x column-block) JC69 tiles.

The phylogeny stage's hot input is the (N, N) JC69 distance matrix. Dense
``core.distance.distance_matrix`` materializes all of it on one host — the
scaling cliff this subsystem removes. ``TileContext`` computes the same
matrix as independent tiles and exposes *streaming block-reductions* so the
HPTree pipeline (``repro.phylo.pipeline``) never holds more than one tile
row-block strip of distance storage per host:

  ``strips``          generator of (row_block, M) strips, one resident at a
                      time; shard-mapped over the ``repro.dist`` mesh when
                      one is given (``dist.mapreduce.distance_strip_over_mesh``)
  ``row_sums``        streamed row-sum reduction (medoid seeding)
  ``greedy_k_center`` streamed farthest-point medoid selection — identical
                      picks to ``core.cluster.farthest_point_medoids`` with
                      no (m, m) sample matrix
  ``nearest``         (N, k) distances to k anchor rows, strip by strip
  ``full``            assemble the whole matrix tile by tile — the parity /
                      debug / small-N-exact path, not the production one

Tiles reuse ``kernels/distance`` on device (compiled Pallas on TPU) with
``core.distance.cross_distance`` as the oracle everywhere else. Because the
underlying (match, valid) counts are exact integers in f32, every tile is
*bitwise equal* to the corresponding dense sub-block regardless of backend
or tiling — pinned by ``tests/test_phylo_engine.py``.

``TileAccountant`` tracks resident distance bytes; the acceptance test
asserts ``peak_resident_bytes <= row_block * N * 4`` through it.
"""
from __future__ import annotations

import dataclasses
from typing import Iterator, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core import distance as dist_mod
from ..obs import metrics as _obs

_G_RESIDENT = _obs.gauge("repro_tile_resident_bytes",
                         "distance bytes currently resident (last accountant)")
_C_TILES = _obs.counter("repro_tiles_total", "distance tiles materialized")
_C_TILE_BYTES = _obs.counter("repro_tile_bytes_total",
                             "distance bytes materialized, cumulative")


class TileAccountant:
    """Byte accounting for resident distance storage (tile-callback hook).

    Every distance buffer the tiled pipeline materializes passes through
    ``alloc``/``free``; ``peak_resident_bytes`` is the memory bound the
    tiled backend advertises (one row-block strip), asserted in tests and
    reported by ``launch/tree_run.py``.
    """

    def __init__(self):
        self.resident = 0
        self.peak = 0
        self.n_tiles = 0
        self.total_bytes = 0

    def alloc(self, nbytes: int) -> int:
        nbytes = int(nbytes)
        self.resident += nbytes
        self.peak = max(self.peak, self.resident)
        self.n_tiles += 1
        self.total_bytes += nbytes
        _C_TILES.inc()
        _C_TILE_BYTES.inc(nbytes)
        _G_RESIDENT.set(self.resident)
        return nbytes

    def free(self, nbytes: int) -> None:
        self.resident -= int(nbytes)
        _G_RESIDENT.set(self.resident)

    def stats(self) -> dict:
        return {"peak_resident_bytes": self.peak,
                "n_tiles": self.n_tiles,
                "total_tile_bytes": self.total_bytes}


@dataclasses.dataclass
class TileContext:
    """One configured tile engine (alphabet + tile geometry + placement)."""

    gap_code: int
    n_chars: int
    correct: bool = True           # JC69 correction (off for protein)
    row_block: int = 128
    col_block: Optional[int] = None   # ``full`` only; defaults to row_block
    use_kernel: Optional[bool] = None  # None -> compiled Pallas on TPU only
    mesh: Optional[object] = None      # jax Mesh: shard-map the strips
    data_axis: str = "data"
    accountant: Optional[TileAccountant] = None

    def __post_init__(self):
        if self.use_kernel is None:
            from ..kernels import default_interpret
            self.use_kernel = not default_interpret()
        if self.accountant is None:
            self.accountant = TileAccountant()

    # ------------------------------------------------------------ accounting

    def track(self, arr: np.ndarray) -> np.ndarray:
        self.accountant.alloc(arr.nbytes)
        return arr

    def release(self, arr: np.ndarray) -> None:
        self.accountant.free(arr.nbytes)

    # ------------------------------------------------------------ tile math

    def block(self, rows, cols) -> np.ndarray:
        """One (r, c) distance tile between two row sets."""
        rows = jnp.asarray(rows)
        cols = jnp.asarray(cols)
        if self.use_kernel:
            from ..kernels.distance import match_valid_pallas
            m, v = match_valid_pallas(rows, cols, n_chars=self.n_chars,
                                      gap_code=self.gap_code)
        else:
            m, v = dist_mod.match_valid_counts(rows, cols,
                                               gap_code=self.gap_code,
                                               n_chars=self.n_chars)
        return self._distance(m, v)

    def _distance(self, match, valid) -> np.ndarray:
        """The shared JC69 tail of host tiles and shard-mapped count strips:
        one program for both, so a mesh changes no distance bit."""
        return np.asarray(dist_mod.counts_to_distance(match, valid,
                                                      correct=self.correct))

    def square(self, rows, pad_to: Optional[int] = None) -> np.ndarray:
        """Small dense symmetric matrix (per-cluster / skeleton blocks).

        ``pad_to`` pads the row count with gap rows so every per-cluster
        call compiles at one shape; the caller crops. Real-row entries are
        unaffected (pairwise counts are row-independent).
        """
        rows = np.asarray(rows)
        n = rows.shape[0]
        if pad_to is not None and n < pad_to:
            pad = np.full((pad_to - n, rows.shape[1]), self.gap_code,
                          rows.dtype)
            rows = np.concatenate([rows, pad], axis=0)
        d = dist_mod.distance_matrix(jnp.asarray(rows), gap_code=self.gap_code,
                                     n_chars=self.n_chars,
                                     correct=self.correct)
        return np.asarray(d)[:n, :n] if pad_to is not None else np.asarray(d)

    # ------------------------------------------------------------- streaming

    def strips(self, msa, cols=None) -> Iterator[Tuple[int, int, np.ndarray]]:
        """Yield ``(start, stop, strip)`` row-block strips of the cross
        distance between ``msa`` and ``cols`` (default: ``msa`` itself, i.e.
        one row-block of the (N, N) matrix per step).

        Exactly one strip is resident at a time (alloc on yield, free on
        resume). With a mesh and ``cols is None`` the strip computation is
        shard-mapped: each device computes its column shard of the tile row.
        """
        msa = np.asarray(msa)
        n, L = msa.shape
        cols_arr = msa if cols is None else np.asarray(cols)
        m = cols_arr.shape[0]
        rb = self.row_block
        mesh_fn = None
        if self.mesh is not None and cols is None:
            mesh_fn, S = self._mesh_strip_fn(msa)
        for start in range(0, n, rb):
            stop = min(start + rb, n)
            blk = msa[start:stop]
            if blk.shape[0] < rb:      # keep one compiled strip shape
                pad = np.full((rb - blk.shape[0], L), self.gap_code,
                              msa.dtype)
                blk = np.concatenate([blk, pad], axis=0)
            if mesh_fn is not None:
                match, valid = mesh_fn(jnp.asarray(blk), S)
                strip = self._distance(match[:, :m], valid[:, :m])
            else:
                strip = self.block(blk, cols_arr)
            strip = strip[: stop - start, :m]
            nbytes = self.accountant.alloc(rb * m * 4)   # what was computed
            try:
                yield start, stop, strip
            finally:
                self.accountant.free(nbytes)

    def _mesh_strip_fn(self, msa: np.ndarray):
        from ..dist import mapreduce, sharding as sh
        n_shards = sh.axis_size(self.mesh, self.data_axis)
        padded, _ = mapreduce.pad_rows(msa, n_shards, fill=self.gap_code)
        S = sh.shard_rows(padded, self.mesh, self.data_axis)
        fn = mapreduce.distance_strip_over_mesh(
            self.mesh, gap_code=self.gap_code, n_chars=self.n_chars,
            use_kernel=self.use_kernel, data_axis=self.data_axis)
        return fn, S

    def row_sums(self, msa) -> np.ndarray:
        """Streamed row-sum reduction over the implicit (N, N) matrix."""
        msa = np.asarray(msa)
        out = np.zeros((msa.shape[0],), np.float32)
        for start, stop, strip in self.strips(msa):
            out[start:stop] = strip.sum(axis=1)
        return out

    def greedy_k_center(self, msa, k: int) -> np.ndarray:
        """Streamed farthest-point medoid selection.

        Same picks as ``core.cluster.farthest_point_medoids`` on the dense
        sample matrix: the seed is the max-row-sum point (streamed), then
        each round adds the point farthest from the chosen set, maintaining
        the (m,) min-distance vector with one single-column tile per round.
        """
        msa = np.asarray(msa)
        m = msa.shape[0]
        first = int(np.argmax(self.row_sums(msa)))
        chosen = [first]
        mind = self.block(msa, msa[first: first + 1])[:, 0]
        for _ in range(1, min(k, m)):
            nxt = int(np.argmax(mind))
            chosen.append(nxt)
            mind = np.minimum(mind, self.block(msa, msa[nxt: nxt + 1])[:, 0])
        return np.asarray(chosen)

    def nearest(self, msa, anchors) -> np.ndarray:
        """(N, k) distances to ``anchors``.

        Strip-streamed on one host; with a mesh the rows are sharded and
        every device computes its rows against the replicated anchors in
        one shard-mapped call (``dist.mapreduce.nearest_anchor_over_mesh``)
        — this is the pipeline's N-scale assignment stage. The result is
        tracked by the accountant; the caller releases it (``ctx.release``)
        once the assignment stage is done with it.
        """
        msa = np.asarray(msa)
        anchors = np.asarray(anchors)
        n = msa.shape[0]
        if self.mesh is not None:
            from ..dist import mapreduce, sharding as sh
            n_shards = sh.axis_size(self.mesh, self.data_axis)
            padded, _ = mapreduce.pad_rows(msa, n_shards, fill=self.gap_code)
            fn = mapreduce.nearest_anchor_over_mesh(
                self.mesh, gap_code=self.gap_code, n_chars=self.n_chars,
                use_kernel=self.use_kernel, data_axis=self.data_axis)
            match, valid = fn(sh.shard_rows(padded, self.mesh, self.data_axis),
                              sh.broadcast(jnp.asarray(anchors), self.mesh))
            return self.track(self._distance(match[:n], valid[:n]).copy())
        out = self.track(np.empty((n, anchors.shape[0]), np.float32))
        for start, stop, strip in self.strips(msa, cols=anchors):
            out[start:stop] = strip
        return out

    # ------------------------------------------------------------- assembly

    def full(self, msa) -> np.ndarray:
        """Assemble the complete (N, N) matrix from tiles.

        Parity/debug path plus the tiled backend's small-N exact route
        (N <= row_block, where the whole matrix is one strip). Bitwise
        equal to ``core.distance.distance_matrix``.
        """
        msa = np.asarray(msa)
        n = msa.shape[0]
        cb = self.col_block or self.row_block
        out = self.track(np.zeros((n, n), np.float32))
        for rs in range(0, n, self.row_block):
            re_ = min(rs + self.row_block, n)
            for cs in range(0, n, cb):
                ce = min(cs + cb, n)
                nbytes = self.accountant.alloc((re_ - rs) * (ce - cs) * 4)
                out[rs:re_, cs:ce] = self.block(msa[rs:re_], msa[cs:ce])
                self.accountant.free(nbytes)
        np.fill_diagonal(out, 0.0)
        return out

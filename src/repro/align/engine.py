"""AlignEngine: the single entry point for HAlign-II's map(1) stage.

The three historical alignment paths (the jnp scan oracle, the Pallas SW
kernel, and the k-mer fallback re-alignment) dispatch through this
engine. It owns:

  * backend selection (``jnp`` | ``pallas`` | ``banded`` |
    ``banded-pallas``, ``auto`` resolves per platform — see
    ``backends.resolve_backend``),
  * length-bucketed batching (``bucketing.bucket_plan``): each bucket
    runs at its own power-of-two width instead of the global Lmax; with
    ``band_policy="adaptive"`` the pairs path additionally buckets on
    the pow2 band width each pair's length skew needs
    (``bucketing.band_bucket_plan``), so banded kernels compile once
    per W instead of overflowing thin bands into full-DP fallbacks,
  * the per-pair full-DP fallback shared by the banded backends
    (band overflow) and the k-mer chaining path (chain failure) — the
    merge happens device-side, no host round-trip of the row buffers.

``batch_fn`` exposes the raw jit-compatible backend primitive for use
inside jitted pipelines (``dist.mapreduce`` calls it under shard_map,
where host-side bucketing and fallback control flow are impossible).

Two host batch APIs:

  ``align_to_center``  one broadcast target — the MSA map(1) stage
  ``align_pairs``      per-pair targets — the batch-entry API that lets
                       ``repro.serve`` coalesce pre-encoded requests from
                       many callers (each with its own center) into pow2
                       (q_width, t_width) buckets, one jitted call per
                       bucket (``PairsResult.n_calls`` reports how many)
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp
import numpy as np

from . import backends, bucketing
from ..kernels import round_up
from ..kernels.sw.ops import PAIRS_PER_PROGRAM
from ..obs import metrics as _obs

_M_CALLS = _obs.counter(
    "repro_align_calls_total",
    "backend invocations (buckets + fallback batches)", ("api", "backend"))
_M_PAIRS = _obs.counter(
    "repro_align_pairs_total", "pairs aligned", ("api", "backend"))
_M_FALLBACK = _obs.counter(
    "repro_align_fallback_pairs_total",
    "pairs re-aligned with full DP after band overflow", ("backend",))
_M_CELLS = _obs.counter(
    "repro_align_cells_total",
    "useful DP cells: each real pair's query length x target length",
    ("api",))
_M_PAD_CELLS = _obs.counter(
    "repro_align_pad_cells_total",
    "DP cells dispatched beyond the useful ones: width padding, chunk "
    "duplicates, empty SW kernel slots and full-DP fallback rows", ("api",))


def _record_dispatch(api: str, backend: str, n_calls: int, n_pairs: int,
                     useful: int, dispatched: int) -> None:
    """Cells count as the calls were shaped: ``dispatched`` is the sum over
    every row handed to a backend of its (query width x target width)
    rectangle (a banded call fills only its band of it; a ``pallas`` call's
    rows include its empty group slots, ``AlignEngine._slots``), ``useful``
    the part of it that real pairs need, counted once per pair."""
    _M_CALLS.labels(api=api, backend=backend).inc(n_calls)
    _M_PAIRS.labels(api=api, backend=backend).inc(n_pairs)
    _M_CELLS.labels(api=api).inc(useful)
    _M_PAD_CELLS.labels(api=api).inc(dispatched - useful)


class EngineResult(NamedTuple):
    score: jnp.ndarray      # (B,) f32
    a_row: jnp.ndarray      # (B, P) int8 gap-padded aligned queries
    b_row: jnp.ndarray      # (B, P) int8 aligned target rows
    aln_len: jnp.ndarray    # (B,) i32
    n_fallback: int         # pairs re-aligned with full DP (banded only)


class PairsResult(NamedTuple):
    score: jnp.ndarray      # (B,) f32
    a_row: jnp.ndarray      # (B, P) int8 gap-padded aligned queries
    b_row: jnp.ndarray      # (B, P) int8 aligned per-pair targets
    aln_len: jnp.ndarray    # (B,) i32
    n_fallback: int         # pairs re-aligned with full DP (banded only)
    n_calls: int            # backend invocations (buckets + fallbacks) —
                            # the coalescing metric repro.serve reports


# A full-DP call (jnp / pallas) holds an O(n·m) int8 direction matrix per
# pair on the device — about 275 MB per pair at mtDNA length. Host
# dispatch splits every full-DP batch so one call holds at most this many
# direction bytes.
DIRS_BUDGET_BYTES = 2 << 30


def _pad_cols(x, width: int, fill):
    if x.shape[-1] >= width:
        return x
    cfg = [(0, 0)] * (x.ndim - 1) + [(0, width - x.shape[-1])]
    return jnp.pad(x, cfg, constant_values=fill)


@dataclasses.dataclass(frozen=True)
class AlignEngine:
    """One configured map(1) engine; construction is cheap, jit caches are
    module-level (keyed on shapes + the static params below), so building
    an engine per MSA call does not recompile."""
    sub: jnp.ndarray
    gap_open: int
    gap_extend: int
    gap_code: int = 5
    backend: str = "auto"
    band: int = 64
    band_policy: str = "fixed"   # "fixed" | "adaptive" (pairs path only)
    local: bool = False
    block_rows: int = 128
    interpret: Optional[bool] = None
    bucket: bool = True
    min_bucket: int = 32

    def __post_init__(self):
        object.__setattr__(self, "backend",
                           backends.resolve_backend(self.backend))
        if self._is_banded and self.local:
            # a diagonal band cannot host an anywhere-start local path
            object.__setattr__(self, "backend", "jnp")
        if self.band_policy not in ("fixed", "adaptive"):
            raise ValueError(f"unknown band_policy {self.band_policy!r}; "
                             "expected 'fixed' or 'adaptive'")

    @property
    def _is_banded(self) -> bool:
        return self.backend in ("banded", "banded-pallas")

    def _slots(self, rows: int) -> int:
        """Rows a call's DP work covers. The ``pallas`` kernel runs pairs
        in groups of ``PAIRS_PER_PROGRAM``, one per sublane, and a group's
        vector work is the same however many of its slots are filled, so
        a call counts its rows rounded up to whole groups."""
        if self.backend != "pallas":
            return rows
        return round_up(rows, PAIRS_PER_PROGRAM)

    def batch_fn(self, *, local: Optional[bool] = None):
        """(Q, lens, b, lb) -> BatchAlignment, safe inside jit/shard_map.

        ``local`` overrides the engine's local mode for this primitive
        (the k-mer fallback is always global even under a local engine);
        a local override still routes ``banded`` to ``jnp``.
        """
        be = self.backend
        loc = self.local if local is None else local
        if be in ("banded", "banded-pallas") and loc:
            be = "jnp"

        def fn(Q, lens, b, lb):
            if be == "pallas":
                return backends.pallas_align_batch(
                    Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, local=loc,
                    gap_code=self.gap_code, block_rows=self.block_rows,
                    interpret=self.interpret)
            if be == "banded":
                return backends.banded_align_batch(
                    Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, band=self.band,
                    gap_code=self.gap_code)
            if be == "banded-pallas":
                return backends.banded_pallas_align_batch(
                    Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, band=self.band,
                    gap_code=self.gap_code, block_rows=self.block_rows,
                    interpret=self.interpret)
            return backends.jnp_align_batch(
                Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                gap_extend=self.gap_extend, local=loc,
                gap_code=self.gap_code)
        return fn

    def _full_dp_fn(self):
        """The full-DP global primitive used for per-pair fallbacks."""
        def fn(Q, lens, b, lb):
            if self.backend == "pallas":
                return backends.pallas_align_batch(
                    Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, local=False,
                    gap_code=self.gap_code, block_rows=self.block_rows,
                    interpret=self.interpret)
            return backends.jnp_align_batch(
                Q, lens, b, lb, self.sub, gap_open=self.gap_open,
                gap_extend=self.gap_extend, local=False,
                gap_code=self.gap_code)
        return fn

    # ------------------------------------------------------------- host API

    def align_to_center(self, Q, lens, b, lb) -> EngineResult:
        """Bucketed, fallback-handling map(1): every query against ``b``.

        Q: (B, Lmax) int8, lens: (B,), b: (m,), lb scalar. Output rows are
        (B, Lmax + m) — trailing (gap,gap) columns are dead padding the
        center-star assembly ignores.
        """
        Q = jnp.asarray(Q)
        lens = jnp.asarray(lens, jnp.int32)
        b = jnp.asarray(b)
        B, Lmax = Q.shape
        m = b.shape[0]
        P = Lmax + m
        fn = self.batch_fn()

        if not self.bucket or B == 0:
            self.record_to_center(1 if B else 0, B, lens, lb, Lmax, m)
            out = fn(Q, lens, b, lb)
            return self._apply_fallback(out, Q, lens, b, lb, P)

        lens_np = np.asarray(lens)
        plan = bucketing.bucket_plan(lens_np, Lmax,
                                     min_bucket=self.min_bucket)
        calls = []
        for width, idx in plan:
            chunks = self._chunks(idx, width, m, full_dp=not self._is_banded)
            self.record_to_center(len(chunks), len(chunks[0]), lens_np[idx],
                                  lb, width, m)
            calls += [(width, chunk) for chunk in chunks]
        if len(calls) == 1:
            width, _ = calls[0]
            out = fn(Q[:, :width], lens, b, lb)
            return self._apply_fallback(out, Q, lens, b, lb, P)

        score = jnp.zeros((B,), jnp.float32)
        a_rows = jnp.full((B, P), self.gap_code, jnp.int8)
        b_rows = jnp.full((B, P), self.gap_code, jnp.int8)
        aln_len = jnp.zeros((B,), jnp.int32)
        ok = np.ones((B,), bool)
        for width, idx in calls:
            ix = jnp.asarray(idx)
            out = fn(Q[ix, :width], lens[ix], b, lb)
            score = score.at[ix].set(out.score)
            a_rows = a_rows.at[ix].set(_pad_cols(out.a_row, P, self.gap_code))
            b_rows = b_rows.at[ix].set(_pad_cols(out.b_row, P, self.gap_code))
            aln_len = aln_len.at[ix].set(out.aln_len)
            ok[idx] = np.asarray(out.ok)
        merged = backends.BatchAlignment(score, a_rows, b_rows, aln_len,
                                         jnp.asarray(ok))
        return self._apply_fallback(merged, Q, lens, b, lb, P)

    def record_to_center(self, n_calls: int, rows: int, lens, lb,
                         width: int, m: int) -> None:
        """Count ``n_calls`` map(1) calls of ``rows`` rows each, every row
        a (``width`` x ``m``) rectangle, that align the real queries of
        lengths ``lens`` to a target of length ``lb``: the host's calls of
        one bucket, or every shard's every chunk on the mesh."""
        _record_dispatch("to_center", self.backend, n_calls, len(lens),
                         self._useful_cells(lens, lb),
                         n_calls * self._slots(rows) * width * m)

    @staticmethod
    def _useful_cells(lens, lb) -> int:
        """Σ query length x center length: the DP cells real pairs need."""
        return (int(np.asarray(lens, np.int64).sum())
                * int(np.asarray(lb)))

    @staticmethod
    def _chunks(idx: np.ndarray, n: int, m: int, *, full_dp: bool) -> list:
        """Split one bucket's pair indices so no full-DP call holds more
        than ``DIRS_BUDGET_BYTES`` of (n, m+1) direction matrices. Every
        chunk has the same size (the last repeats its final index, whose
        duplicate writes are identical) so one compiled shape serves all.
        Banded calls hold O(n·W) and are never split."""
        per = max(1, DIRS_BUDGET_BYTES // max(n * (m + 1), 1))
        if not full_dp or len(idx) <= per:
            return [idx]
        chunks = [idx[i:i + per] for i in range(0, len(idx), per)]
        last = chunks[-1]
        chunks[-1] = np.concatenate(
            [last, np.full(per - len(last), last[-1], last.dtype)])
        return chunks

    def _apply_fallback(self, out: backends.BatchAlignment, Q, lens, b, lb,
                        P: int) -> EngineResult:
        """Re-align pairs the backend flagged (band overflow) with full DP.

        Every fallback row's cells count as padding: the pair's useful
        cells were counted at its first dispatch."""
        bad = np.flatnonzero(~np.asarray(out.ok))
        score = out.score
        a_rows = _pad_cols(out.a_row, P, self.gap_code)
        b_rows = _pad_cols(out.b_row, P, self.gap_code)
        aln_len = out.aln_len
        if len(bad):
            _M_FALLBACK.labels(backend=self.backend).inc(len(bad))
            n, m = Q.shape[1], b.shape[0]
            for chunk in self._chunks(bad, n, m, full_dp=True):
                _record_dispatch("to_center", self.backend, 1, 0, 0,
                                 len(chunk) * n * m)
                ix = jnp.asarray(chunk)
                res = self._full_dp_fn()(Q[ix], lens[ix], b, lb)
                score = score.at[ix].set(res.score)
                a_rows = a_rows.at[ix].set(
                    _pad_cols(res.a_row, P, self.gap_code))
                b_rows = b_rows.at[ix].set(
                    _pad_cols(res.b_row, P, self.gap_code))
                aln_len = aln_len.at[ix].set(res.aln_len)
        return EngineResult(score, a_rows, b_rows, aln_len, len(bad))

    def pairs_fn(self, *, local: Optional[bool] = None,
                 band: Optional[int] = None):
        """(Q, qlens, T, tlens) -> BatchAlignment with per-pair targets.

        The batch-entry primitive: every row carries its own target, so a
        single jitted call can serve pre-encoded requests from many
        callers — each request's center becomes that row's target
        (``repro.serve.queue`` builds such batches). Safe inside
        jit/shard_map; ``local`` overrides as in ``batch_fn``; ``band``
        overrides the engine band for one primitive (the adaptive band
        planner builds one pairs_fn per bucket W).
        """
        be = self.backend
        loc = self.local if local is None else local
        if be in ("banded", "banded-pallas") and loc:
            be = "jnp"
        W = self.band if band is None else band

        def fn(Q, qlens, T, tlens):
            if be == "pallas":
                return backends.pallas_align_pairs(
                    Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, local=loc,
                    gap_code=self.gap_code, block_rows=self.block_rows,
                    interpret=self.interpret)
            if be == "banded":
                return backends.banded_align_pairs(
                    Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, band=W,
                    gap_code=self.gap_code)
            if be == "banded-pallas":
                return backends.banded_pallas_align_pairs(
                    Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, band=W,
                    gap_code=self.gap_code, interpret=self.interpret)
            return backends.jnp_align_pairs(
                Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                gap_extend=self.gap_extend, local=loc,
                gap_code=self.gap_code)
        return fn

    def _full_dp_pairs_fn(self):
        """Full-DP global pairs primitive for per-pair fallbacks."""
        def fn(Q, qlens, T, tlens):
            if self.backend == "pallas":
                return backends.pallas_align_pairs(
                    Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                    gap_extend=self.gap_extend, local=False,
                    gap_code=self.gap_code, block_rows=self.block_rows,
                    interpret=self.interpret)
            return backends.jnp_align_pairs(
                Q, qlens, T, tlens, self.sub, gap_open=self.gap_open,
                gap_extend=self.gap_extend, local=False,
                gap_code=self.gap_code)
        return fn

    def align_pairs(self, Q, qlens, T, tlens) -> PairsResult:
        """Bucketed batch-entry map(1): row i of ``Q`` against row i of ``T``.

        Q: (B, Lq) int8, T: (B, Lt) int8, qlens/tlens: (B,). Pairs are
        grouped into pow2 (q_width, t_width) buckets
        (``bucketing.pair_bucket_plan``) so one jitted call per bucket
        serves every caller whose request landed in it; output rows are
        (B, Lq + Lt) with trailing (gap, gap) dead padding. ``n_calls``
        counts backend invocations — the coalescing win is B requests
        serviced in <= log2(Lq)·log2(Lt) calls.
        """
        Q = jnp.asarray(Q)
        T = jnp.asarray(T)
        qlens = jnp.asarray(qlens, jnp.int32)
        tlens = jnp.asarray(tlens, jnp.int32)
        B, Lq = Q.shape
        Lt = T.shape[1]
        P = Lq + Lt
        if B == 0:
            z = jnp.zeros((0,), jnp.float32)
            r = jnp.zeros((0, P), jnp.int8)
            return PairsResult(z, r, r, jnp.zeros((0,), jnp.int32), 0, 0)
        fn = self.pairs_fn()

        qlens_np = np.asarray(qlens)
        tlens_np = np.asarray(tlens)
        real_cells = int((qlens_np.astype(np.int64)
                          * tlens_np.astype(np.int64)).sum())

        if not self.bucket:
            _record_dispatch("pairs", self.backend, 1, B, real_cells,
                             self._slots(B) * Lq * Lt)
            out = fn(Q, qlens, T, tlens)
            return self._apply_pairs_fallback(out, Q, qlens, T, tlens, P,
                                              n_calls=1)

        if self.band_policy == "adaptive" and self._is_banded:
            # Band-aware buckets: pairs sharing (wq, wt, W) share one
            # jitted kernel instance; skewed pairs get a band wide enough
            # to not overflow instead of a guaranteed full-DP fallback.
            plan = bucketing.band_bucket_plan(
                qlens_np, tlens_np, Lq, Lt,
                band=self.band, min_bucket=self.min_bucket)
            _record_dispatch(
                "pairs", self.backend, len(plan), B, real_cells,
                sum(wq * wt * len(idx) for wq, wt, _, idx in plan))
            score = jnp.zeros((B,), jnp.float32)
            a_rows = jnp.full((B, P), self.gap_code, jnp.int8)
            b_rows = jnp.full((B, P), self.gap_code, jnp.int8)
            aln_len = jnp.zeros((B,), jnp.int32)
            ok = np.ones((B,), bool)
            for wq, wt, W, idx in plan:
                ix = jnp.asarray(idx)
                out = self.pairs_fn(band=W)(Q[ix, :wq], qlens[ix],
                                            T[ix, :wt], tlens[ix])
                score = score.at[ix].set(out.score)
                a_rows = a_rows.at[ix].set(
                    _pad_cols(out.a_row, P, self.gap_code))
                b_rows = b_rows.at[ix].set(
                    _pad_cols(out.b_row, P, self.gap_code))
                aln_len = aln_len.at[ix].set(out.aln_len)
                ok[idx] = np.asarray(out.ok)
            merged = backends.BatchAlignment(score, a_rows, b_rows, aln_len,
                                             jnp.asarray(ok))
            return self._apply_pairs_fallback(merged, Q, qlens, T, tlens, P,
                                              n_calls=len(plan))

        plan = bucketing.pair_bucket_plan(qlens_np, tlens_np, Lq, Lt,
                                          min_bucket=self.min_bucket)
        _record_dispatch("pairs", self.backend, len(plan), B, real_cells,
                         sum(wq * wt * self._slots(len(idx))
                             for wq, wt, idx in plan))
        if len(plan) == 1:
            wq, wt, _ = plan[0]
            out = fn(Q[:, :wq], qlens, T[:, :wt], tlens)
            return self._apply_pairs_fallback(out, Q, qlens, T, tlens, P,
                                              n_calls=1)

        score = jnp.zeros((B,), jnp.float32)
        a_rows = jnp.full((B, P), self.gap_code, jnp.int8)
        b_rows = jnp.full((B, P), self.gap_code, jnp.int8)
        aln_len = jnp.zeros((B,), jnp.int32)
        ok = np.ones((B,), bool)
        for wq, wt, idx in plan:
            ix = jnp.asarray(idx)
            out = fn(Q[ix, :wq], qlens[ix], T[ix, :wt], tlens[ix])
            score = score.at[ix].set(out.score)
            a_rows = a_rows.at[ix].set(_pad_cols(out.a_row, P, self.gap_code))
            b_rows = b_rows.at[ix].set(_pad_cols(out.b_row, P, self.gap_code))
            aln_len = aln_len.at[ix].set(out.aln_len)
            ok[idx] = np.asarray(out.ok)
        merged = backends.BatchAlignment(score, a_rows, b_rows, aln_len,
                                         jnp.asarray(ok))
        return self._apply_pairs_fallback(merged, Q, qlens, T, tlens, P,
                                          n_calls=len(plan))

    def _apply_pairs_fallback(self, out: backends.BatchAlignment, Q, qlens,
                              T, tlens, P: int, *, n_calls: int
                              ) -> PairsResult:
        """Full-DP re-alignment of pairs the backend flagged (band
        overflow); its rows' cells count as padding, as in
        ``_apply_fallback``."""
        bad = np.flatnonzero(~np.asarray(out.ok))
        score = out.score
        a_rows = _pad_cols(out.a_row, P, self.gap_code)
        b_rows = _pad_cols(out.b_row, P, self.gap_code)
        aln_len = out.aln_len
        if len(bad):
            _M_FALLBACK.labels(backend=self.backend).inc(len(bad))
            _record_dispatch("pairs", self.backend, 1, 0, 0,
                             len(bad) * Q.shape[1] * T.shape[1])
            ix = jnp.asarray(bad)
            res = self._full_dp_pairs_fn()(Q[ix], qlens[ix], T[ix], tlens[ix])
            score = score.at[ix].set(res.score)
            a_rows = a_rows.at[ix].set(_pad_cols(res.a_row, P, self.gap_code))
            b_rows = b_rows.at[ix].set(_pad_cols(res.b_row, P, self.gap_code))
            aln_len = aln_len.at[ix].set(res.aln_len)
            n_calls += 1
        return PairsResult(score, a_rows, b_rows, aln_len, len(bad), n_calls)

    def realign_failed(self, Q, lens, b, lb, a_rows, b_rows, ok):
        """Full-DP re-alignment of k-mer chain failures, merged device-side.

        This replaces the old host-numpy round-trip in ``core.msa``: the
        assembled k-mer rows stay on device; only the (B,) ok flags cross
        to host to pick the failed subset (``core.msa`` reads them first,
        at the end of its ``map1.chain`` span, and passes them as NumPy).

        Returns (a_rows, b_rows, n_fallback); widths grow to fit the DP
        rows if needed.
        """
        bad = np.flatnonzero(~np.asarray(ok))
        if len(bad) == 0:
            return jnp.asarray(a_rows), jnp.asarray(b_rows), 0
        Q = jnp.asarray(Q)
        lens = jnp.asarray(lens, jnp.int32)
        ix = jnp.asarray(bad)
        # the k-mer assembly is global, so its fallback must be too — even
        # under a local (Smith-Waterman) engine
        eng = (self if not self.local
               else dataclasses.replace(self, local=False))
        res = eng.align_to_center(Q[ix], lens[ix], b, lb)
        P = max(int(a_rows.shape[1]), int(res.a_row.shape[1]))
        a_rows = _pad_cols(jnp.asarray(a_rows), P, self.gap_code)
        b_rows = _pad_cols(jnp.asarray(b_rows), P, self.gap_code)
        a_rows = a_rows.at[ix].set(_pad_cols(res.a_row, P, self.gap_code))
        b_rows = b_rows.at[ix].set(_pad_cols(res.b_row, P, self.gap_code))
        return a_rows, b_rows, len(bad)

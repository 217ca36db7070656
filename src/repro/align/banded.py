"""Banded Gotoh DP: O(n·W) direction storage instead of O(n·m).

The full Gotoh forward in ``core.pairwise`` materializes an
(La+1)×(Lb+1) packed-direction matrix per pair — the memory wall for
ultra-long sequences. HAlign-II's inputs are highly similar, so the
optimal path hugs the (0,0)→(la,lb) diagonal; this module keeps only a
width-W band of cells around that diagonal per row.

Band geometry: row ``i`` stores absolute columns ``j ∈ [lo_i, lo_i+W)``
with ``lo_i = floor(i·lb/la) - W//2`` (for ``la == 0`` the band parks on
``j = lb`` so the all-insert traceback start stays addressable). The band
center follows the straight line to ``(la, lb)``, so unequal lengths are
handled by construction and the global end cell ``(la, lb)`` is always at
offset ``W//2``. Cells outside the band are NEG, exactly like the
out-of-matrix boundary of the full DP — with a band wide enough to cover
every column (``W ≥ 2·lb + 2``) the recurrence is bit-identical to
``pairwise.gotoh_forward``.

Band overflow: a clipped band can only *underestimate* scores, and the
returned path need not touch the band edge for a better out-of-band path
to exist — so path-touches-edge alone is not enough. Detection is
forward "edge pressure": a pair is flagged when any live DP row has a
*competitive* cell (within ``margin = max(sub)`` of the row's best) in
an exit zone — offset 0 or the slide-clipped right rim
``o >= W - max(s, 1)`` of the current row, or a previous-row cell about
to be slid out of storage (``o < s``, the bottom-left exit) — i.e. a
near-dominant path is pushing against the band. The traceback
additionally flags walks that touch a band-edge cell with a real
missing neighbour or leave the band, and NEG-degenerate scores (bands
thinner than the length-difference slope).

This is a heuristic (only a full DP can certify optimality), but
empirically it has no escapes where it matters and beyond: on random
*unrelated* 24-mers at band=8 — adversarial for banding — 0/3000
unflagged pairs scored below the full DP across 10 seeds, while similar
families (HAlign's regime) at band=16 flag 0/200 with exact scores.
Flagged pairs are re-aligned with the full DP by the engine — the same
per-pair fallback contract as the k-mer chaining path.

Row 0 and column 0 direction bytes are closed-form (pure gap runs), so
they are never stored and the direction buffer is exactly (n, W) int8.
Global alignment only: the local (Smith-Waterman) start cell can sit
anywhere, which defeats a diagonal band; the engine routes ``local=True``
to the full-DP backends.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.pairwise import NEG, M_ST, IX_ST, IY_ST, AlignResult
# The pure band recurrence lives in kernels.banded.ref so the native
# Pallas kernels and this jnp scan call the *same* math (bit-identical
# parity by construction); re-exported here as the historical home.
from ..kernels.banded.ref import (BandedForward, band_lo, band_row_init,
                                  band_row_update, edge_pressure, end_state,
                                  trace_step_math)

__all__ = ["BandedForward", "band_lo", "band_row_init", "band_row_update",
           "edge_pressure", "trace_step_math", "banded_forward",
           "banded_traceback", "banded_align_pair"]


def banded_forward(a, la, b, lb, sub, gap_open, gap_extend, *, band: int):
    """Banded Gotoh forward; mirrors ``pairwise.gotoh_forward`` (global).

    a: (n,) int8 codes, la: actual length; b: (m,) int8, lb; sub: (S,S).
    Returns a BandedForward whose dirs buffer is (n, band) — never the
    full (n+1)×(m+1) matrix.
    """
    m = b.shape[0]
    W = band
    go = jnp.float32(gap_open)
    ge = jnp.float32(gap_extend)
    sub = sub.astype(jnp.float32)
    la = la.astype(jnp.int32)
    lb = lb.astype(jnp.int32)
    mid = W // 2
    offs = jnp.arange(W, dtype=jnp.int32)[None]

    m0, ix0, iy0, hb0 = band_row_init(la, lb, go, ge, band=W)
    # end-cell capture init covers la == 0 (j = lb sits at offset W//2)
    cap0 = jnp.stack([m0[0, mid], ix0[0, mid], iy0[0, mid]])
    lo0 = band_lo(jnp.int32(0), la, lb, W)
    margin = jnp.max(sub)                  # one diagonal step of headroom

    def row_step(carry, inp):
        m_prev, ix_prev, iy_prev, lo_prev, cap, edge, hb_prev = carry
        a_i, i = inp                       # i: 1-based DP row
        lo_i = band_lo(i, la, lb, W)
        s_row = sub[a_i.astype(jnp.int32),
                    b[jnp.clip(lo_i + offs - 1, 0, m - 1)].astype(jnp.int32)]
        m_new, ix_new, iy_new, dirs, h_new, h_prev, s = band_row_update(
            m_prev, ix_prev, iy_prev, s_row, lo_prev, lo_i, go, ge, lb,
            band=W)

        hit = i == la                      # end cell (la, lb) sits at mid
        cap = jnp.where(hit, jnp.stack([m_new[0, mid], ix_new[0, mid],
                                        iy_new[0, mid]]), cap)

        # Edge pressure: a competitive cell in an exit zone means a
        # near-dominant path is fighting the band — a wider band could
        # beat this alignment, so flag the pair for full-DP fallback.
        live = i <= la
        comp, hb = edge_pressure(h_new, h_prev, hb_prev, s, margin, band=W)
        edge = edge | (live & comp[0, 0])
        hb_prev = jnp.where(live, hb, hb_prev)
        return ((m_new, ix_new, iy_new, lo_i, cap, edge, hb_prev),
                dirs[0].astype(jnp.int8))

    rows_i = jnp.arange(1, a.shape[0] + 1, dtype=jnp.int32)
    (_, _, _, _, cap, edge, _), dirs = jax.lax.scan(
        row_step, (m0, ix0, iy0, lo0, cap0, jnp.bool_(False), hb0),
        (a, rows_i))
    score, st = end_state(cap[0], cap[1], cap[2])
    return BandedForward(dirs, score, la, lb, st.astype(jnp.int32), edge)


def banded_traceback(a, b, fwd: BandedForward, gap_code: int, *, band: int):
    """Walk the banded directions back to an aligned pair.

    Same output contract as ``pairwise.traceback`` plus an ``ok`` flag:
    False when the path left the band, touched a band edge adjacent to
    real (un-stored) DP cells, or the score is NEG-degenerate.
    """
    n, m = a.shape[0], b.shape[0]
    W = band
    la, lb = fwd.start_i, fwd.start_j
    out_len = n + m
    dirf = fwd.dirs.reshape(-1)

    def step(t, carry):
        i, j, st, done, edge, oob, out_a, out_b, k = carry
        lo_i = band_lo(i, la, lb, W)
        o = j - lo_i
        byte_band = dirf[jnp.clip((i - 1) * W + o, 0, n * W - 1)].astype(
            jnp.int32)
        ni, nj, nst, done, ndone, lost, edge_hit = trace_step_math(
            i, j, o, st, done, byte_band, lb, W)
        is_m = st == M_ST
        ca = jnp.where(is_m | (st == IX_ST), a[jnp.maximum(i - 1, 0)],
                       gap_code).astype(jnp.int8)
        cb = jnp.where(is_m | (st == IY_ST), b[jnp.maximum(j - 1, 0)],
                       gap_code).astype(jnp.int8)
        oob = oob | lost
        edge = edge | edge_hit
        out_a = out_a.at[k].set(jnp.where(done, out_a[k], ca))
        out_b = out_b.at[k].set(jnp.where(done, out_b[k], cb))
        k = jnp.where(done, k, k + 1)
        i = jnp.where(done, i, ni)
        j = jnp.where(done, j, nj)
        st = jnp.where(done, st, nst)
        return (i, j, st, ndone, edge, oob, out_a, out_b, k)

    out_a = jnp.full((out_len,), gap_code, jnp.int8)
    out_b = jnp.full((out_len,), gap_code, jnp.int8)
    init = (fwd.start_i, fwd.start_j, fwd.start_state,
            (fwd.start_i == 0) & (fwd.start_j == 0),
            jnp.bool_(False), jnp.bool_(False), out_a, out_b, jnp.int32(0))
    (_, _, _, _, edge, oob, out_a, out_b, k) = jax.lax.fori_loop(
        0, out_len, step, init)

    ok = (~edge) & (~oob) & (~fwd.edge) & (fwd.score > NEG / 2)

    def unrev(x):
        return jnp.roll(jnp.flip(x), k - out_len)
    return unrev(out_a), unrev(out_b), k, ok


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code"))
def banded_align_pair(a, la, b, lb, sub, *, gap_open, gap_extend, band,
                      gap_code=5):
    """Banded counterpart of ``pairwise.align_pair``; extra ``ok`` output."""
    fwd = banded_forward(a, la, b, lb, sub, gap_open, gap_extend, band=band)
    a_row, b_row, k, ok = banded_traceback(a, b, fwd, gap_code, band=band)
    return AlignResult(fwd.score, a_row, b_row, k, fwd.start_i,
                       fwd.start_j), ok

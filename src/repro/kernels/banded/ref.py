"""The banded Gotoh recurrence as pure shared math.

These functions are THE band recurrence: ``align.banded`` scans them on
the jnp path and ``banded_kernel`` calls them per row with VMEM-resident
state, so the two implementations are bit-identical by construction (same
op order, same dtypes, same NEG boundary). They depend only on
``core.pairwise`` constants — no align imports — so the kernel package
never cycles back into the backend registry.

Band vectors are 2-D ``(1, Wp)`` lane rows, ``Wp >= band``: the jnp path
uses ``Wp == band``; the kernel pads to whole 128-lane vregs, and every
lane at or past ``band`` is held at NEG so it never reaches a real cell.
All in-row data movement is a lane roll (``roll``: ``jnp.roll`` on the
jnp path, ``pltpu.roll`` in the kernel — same semantics) plus a mask, and
every reduction keeps its dims, so the same code lowers to the TPU's
vector unit without gathers, 1-D vectors or scalar extraction.

Band geometry and the edge-pressure overflow heuristic are documented in
``align/banded.py`` (the module docstring is the spec) and
``docs/KERNELS.md`` (the kernel-schedule view).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...core.pairwise import NEG, M_ST, IX_ST, IY_ST, FRESH


class BandedForward(NamedTuple):
    dirs: jnp.ndarray       # (n, W) int8 packed bytes for DP rows 1..n
    score: jnp.ndarray      # f32 global score at (la, lb)
    start_i: jnp.ndarray    # i32 == la
    start_j: jnp.ndarray    # i32 == lb
    start_state: jnp.ndarray
    edge: jnp.ndarray       # bool: some row's best cell hit the band edge


def band_lo(i, la, lb, band: int):
    """Leftmost absolute column stored for DP row ``i``."""
    c = jnp.where(la == 0, lb, (i * lb) // jnp.maximum(la, 1))
    return (c - band // 2).astype(jnp.int32)


def _offs(like):
    return jax.lax.broadcasted_iota(jnp.int32, like.shape, like.ndim - 1)


def _shifted(v, sh, fill, offs, band: int, roll):
    """Lane o takes v[o + sh]; ``fill`` where o + sh leaves [0, band)."""
    Wp = v.shape[-1]
    idx = offs + sh
    ok = (idx >= 0) & (idx < band)
    return jnp.where(ok, roll(v, (-sh) % Wp, v.ndim - 1), fill)


def lane_cummax(x, offs, roll):
    """Inclusive running max along lanes as a log-step roll/max scan."""
    k = 1
    while k < x.shape[-1]:
        x = jnp.maximum(x, jnp.where(offs >= k, roll(x, k, x.ndim - 1), x))
        k *= 2
    return x


def lane_max(x):
    return jnp.max(x, axis=-1, keepdims=True)


def lane_any(mask):
    return lane_max(mask.astype(jnp.int32)) > 0


def band_row_init(la, lb, go, ge, *, band: int, width: int | None = None):
    """Row-0 band state (m0, ix0, iy0) as (1, width) rows, and the row
    best (1, 1). The end-cell capture init is lane ``band // 2`` of the
    three rows (it covers la == 0, where j = lb sits at offset band//2)."""
    W = band
    Wp = width or W
    offs = jax.lax.broadcasted_iota(jnp.int32, (1, Wp), 1)
    lo0 = band_lo(jnp.int32(0), la, lb, W)
    j0 = lo0 + offs
    real = offs < W
    m0 = jnp.where((j0 == 0) & real, 0.0, NEG).astype(jnp.float32)
    ix0 = jnp.full((1, Wp), NEG, jnp.float32)
    iy0 = jnp.where((j0 >= 1) & (j0 <= lb) & real,
                    -(go + (j0.astype(jnp.float32) - 1.0) * ge), NEG)
    h0 = jnp.where((j0 >= 0) & (j0 <= lb) & real, jnp.maximum(m0, iy0), NEG)
    return m0, ix0, iy0, lane_max(h0)


def band_row_update(m_prev, ix_prev, iy_prev, s_row, lo_prev, lo_i, go, ge,
                    lb, *, band: int, roll=jnp.roll):
    """One banded Gotoh DP row — the pure recurrence.

    ``s_row`` is the substitution score of each band cell,
    ``sub[a_i, b[j-1]]`` at absolute column ``j = lo_i + o`` (any finite
    value outside the matrix: those cells are masked). Within a row every
    dependency is elementwise or a running max (Iy via cummax), so the W
    band cells advance together as one anti-diagonal wavefront on the
    vector lanes.

    Returns (m_new, ix_new, iy_new, dirs, h_new, h_prev, s): ``dirs`` is
    the packed direction row as int32; ``h_new``/``h_prev``/``s`` feed the
    edge-pressure detector.
    """
    W = band
    offs = _offs(m_prev)
    offs_f = offs.astype(jnp.float32)
    s = lo_i - lo_prev                 # band slide (>= 0)
    j = lo_i + offs                    # absolute columns this row
    real = offs < W

    def shifted(v, sh, fill):
        return _shifted(v, sh, fill, offs, W, roll)

    h_prev = jnp.maximum(m_prev, jnp.maximum(ix_prev, iy_prev))
    amax = jnp.where(m_prev >= h_prev, M_ST,
                     jnp.where(ix_prev >= h_prev, IX_ST, IY_ST))
    h_diag = shifted(h_prev, s - 1, NEG)
    amax_diag = shifted(amax.astype(jnp.int32), s - 1, jnp.int32(M_ST))
    m_up = shifted(m_prev, s, NEG)
    ix_up = shifted(ix_prev, s, NEG)

    in_mat = (j >= 1) & (j <= lb) & real
    in_row = (j >= 0) & (j <= lb) & real
    m_new = jnp.where(in_mat, h_diag + s_row, NEG)
    dir_m = amax_diag

    ix_open = m_up - go
    ix_ext = ix_up - ge
    ix_new = jnp.where(in_row, jnp.maximum(ix_open, ix_ext), NEG)
    dir_ix = (ix_ext > ix_open).astype(jnp.int32)

    # Iy running max within the row; band offsets stand in for absolute
    # columns (the lo_i·ge term cancels exactly in f32 integer range).
    cm = lane_cummax(m_new + offs_f * ge, offs, roll)
    iy_new = jnp.where(offs == 0, NEG,
                       roll(cm, 1, cm.ndim - 1) - go - (offs_f - 1.0) * ge)
    iy_new = jnp.where(in_mat, iy_new, NEG)
    m_left = shifted(m_new, -1, NEG)
    iy_left = shifted(iy_new, -1, NEG)
    dir_iy = (iy_left - ge > m_left - go).astype(jnp.int32)

    dirs = dir_m | (dir_ix << 2) | (dir_iy << 3)
    h_new = jnp.where(in_row, jnp.maximum(m_new, jnp.maximum(ix_new, iy_new)),
                      NEG)
    return m_new, ix_new, iy_new, dirs, h_new, h_prev, s


def edge_pressure(h_new, h_prev, hb_prev, s, margin, *, band: int):
    """Band-overflow detector for one row (see ``align/banded.py``).

    A competitive cell (within ``margin`` of the row best) in an exit
    zone — offset 0, the slide-clipped right rim, or a previous-row cell
    about to slide out of storage — means a near-dominant path is
    fighting the band. Returns (comp, hb), both (1, 1): flag this row +
    the row best.
    """
    W = band
    offs = _offs(h_new)
    real = offs < W
    hb = lane_max(h_new)
    zone = real & ((offs == 0) | (offs >= W - jnp.maximum(s, 1)))
    comp_cur = lane_any(zone & (h_new >= hb - margin)) & (hb > NEG / 2)
    # bottom-left exit: previous-row cells slid out of storage this row
    comp_prev = (lane_any(real & (offs < s) & (h_prev >= hb_prev - margin))
                 & (hb_prev > NEG / 2))
    return comp_cur | comp_prev, hb


def end_state(cm, cx, cy):
    """jnp.argmax over the end cell's (M, Ix, Iy): the first maximal state
    wins ties. Returns (score, state) with the operands' shape."""
    s_m = (cm >= cx) & (cm >= cy)
    s_x = cx >= cy
    state = jnp.where(s_m, M_ST, jnp.where(s_x, IX_ST, IY_ST))
    return jnp.where(s_m, cm, jnp.where(s_x, cx, cy)), state


def trace_step_math(i, j, o, st, done, byte_band, lb, band: int):
    """One traceback step — the pure walk logic.

    The caller fetches the band direction byte (HBM dirs on the jnp path,
    VMEM dirs in the fused kernel) and emits the characters of state
    ``st`` (M consumes a and b, Ix a, Iy b); this function decides the
    move. Returns (ni, nj, nst, done, ndone, lost, edge_hit) where
    ``done`` is the post-``lost`` write gate for this step and ``ndone``
    the carry.
    """
    W = band
    in_band = (o >= 0) & (o < W) & (i >= 1)
    # Boundary cells are pure gap runs with closed-form directions;
    # they are not stored in the band (and for la==0 / lb==0 the whole
    # walk happens here).
    byte_row0 = FRESH | (jnp.where(j == 1, 0, 1) << 3)
    byte_col0 = M_ST | (jnp.where(i == 1, 0, 1) << 2)
    byte = jnp.where(i == 0, byte_row0,
                     jnp.where(j == 0, byte_col0, byte_band))

    interior = (i > 0) & (j > 0)
    lost = (~done) & interior & (~in_band)
    # Edge cells whose clipped neighbour would be a real DP cell mean
    # a wider band could score higher: flag for full-DP fallback.
    edge_hit = ((~done) & interior & in_band &
                ((o == 0) | ((o == W - 1) & (j < lb))))
    done = done | lost

    dir_m = byte & 3
    dir_ix = (byte >> 2) & 1
    dir_iy = (byte >> 3) & 1
    is_m = st == M_ST
    is_ix = st == IX_ST

    ni = jnp.where(is_m | is_ix, i - 1, i)
    nj = jnp.where(is_m | (st == IY_ST), j - 1, j)
    nst = jnp.where(is_m, dir_m,
                    jnp.where(is_ix, jnp.where(dir_ix == 1, IX_ST, M_ST),
                              jnp.where(dir_iy == 1, IY_ST, M_ST)))
    ndone = done | ((ni == 0) & (nj == 0))
    return ni, nj, nst.astype(jnp.int32), done, ndone, lost, edge_hit

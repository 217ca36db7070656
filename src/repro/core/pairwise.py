"""Pairwise alignment: Needleman-Wunsch / Smith-Waterman with affine gaps (Gotoh).

This is the paper's Eq. (1)-(2) engine, vectorized the TPU way: the classic
cell-by-cell DP is re-expressed as a scan over rows where every in-row
dependency is either elementwise (M, Ix) or a running max (Iy via cummax), so
each row is one fused vector op. The Pallas kernel in ``repro.kernels.sw``
implements the same recurrences with explicit VMEM tiling, and its traceback
kernel walks the same directions; this module is the jnp oracle and the
small-problem workhorse.

State convention (shared with the kernel and the traceback):
  M  = 0  a[i-1] aligned to b[j-1]            (diagonal move)
  IX = 1  a[i-1] aligned to a gap in b        (up move, consumes a)
  IY = 2  b[j-1] aligned to a gap in a        (left move, consumes b)
  FRESH = 3  local-alignment fresh start / origin marker

Direction byte = dirM | dirIx << 2 | dirIy << 3, where
  dirM  in {0,1,2,3}: which state the diagonal max came from (3 = fresh)
  dirIx in {0,1}: 0 = opened from M above, 1 = extended Ix above
  dirIy in {0,1}: 0 = opened from M left,  1 = extended Iy left

Row 0 of the direction matrix is closed-form (FRESH, Iy opened at j = 1 and
extended after), so it is never stored: ``ForwardResult.dirs`` holds DP
rows 1..n, and ``traceback`` computes row-0 bytes itself.

All scores are integer-valued float32 (exact up to 2^24), NEG = -1e7.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG = -1.0e7
M_ST, IX_ST, IY_ST, FRESH = 0, 1, 2, 3


class AlignResult(NamedTuple):
    score: jnp.ndarray      # f32 scalar
    a_row: jnp.ndarray      # (La+Lb,) int8 aligned a with gaps (gap-padded)
    b_row: jnp.ndarray      # (La+Lb,) int8 aligned b with gaps
    aln_len: jnp.ndarray    # i32 scalar: number of valid leading columns
    start_i: jnp.ndarray    # i32: row where traceback started (local end in a)
    start_j: jnp.ndarray    # i32


class ForwardResult(NamedTuple):
    dirs: jnp.ndarray       # (>=La, >=Lb+1) int8 packed bytes, DP rows 1..
                            # (row 0 is closed-form; padding is never read)
    score: jnp.ndarray      # f32
    start_i: jnp.ndarray
    start_j: jnp.ndarray
    start_state: jnp.ndarray


def _pack(dir_m, dir_ix, dir_iy):
    return (dir_m | (dir_ix << 2) | (dir_iy << 3)).astype(jnp.int8)


def gotoh_forward(a, la, b, lb, sub, gap_open, gap_extend, *, local=False):
    """Fill the DP, returning packed directions + traceback start.

    a: (n,) int8 codes, la: actual length; b: (m,) int8, lb; sub: (S,S) f32.
    """
    n, m = a.shape[0], b.shape[0]
    go = jnp.float32(gap_open)
    ge = jnp.float32(gap_extend)
    sub = sub.astype(jnp.float32)
    jcol = jnp.arange(m + 1, dtype=jnp.float32)
    col_valid = jnp.arange(m + 1) <= lb

    # Row 0 boundary.
    m0 = jnp.full((m + 1,), NEG).at[0].set(0.0)
    ix0 = jnp.full((m + 1,), NEG)
    iy0 = jnp.where(jnp.arange(m + 1) >= 1, -(go + (jcol - 1.0) * ge), NEG)

    def row_step(carry, a_i):
        m_prev, ix_prev, iy_prev, at_la_m, at_la_ix, at_la_iy, best, i = carry
        i = i + 1
        s_row = sub[a_i.astype(jnp.int32), b.astype(jnp.int32)]       # (m,)
        s_full = jnp.concatenate([jnp.zeros((1,), jnp.float32), s_row])

        h_prev = jnp.maximum(m_prev, jnp.maximum(ix_prev, iy_prev))
        amax = jnp.where(m_prev >= h_prev, M_ST,
                         jnp.where(ix_prev >= h_prev, IX_ST, IY_ST))
        h_diag = jnp.concatenate([jnp.full((1,), NEG), h_prev[:-1]])
        amax_diag = jnp.concatenate([jnp.full((1,), M_ST, amax.dtype), amax[:-1]])

        m_new = h_diag + s_full
        dir_m = amax_diag
        if local:
            # Starting fresh (empty prefix, value 0) beats extending whenever
            # the incoming diagonal is <= 0; ties prefer fresh so traceback
            # stops at zero-valued cells (score-consistency).
            fresh = h_diag <= 0.0
            m_new = jnp.where(fresh, s_full, m_new)
            dir_m = jnp.where(fresh, FRESH, dir_m)
        m_new = m_new.at[0].set(NEG)

        ix_open = m_prev - go
        ix_ext = ix_prev - ge
        ix_new = jnp.maximum(ix_open, ix_ext)
        dir_ix = (ix_ext > ix_open).astype(jnp.int32)

        # Iy via running max:  Iy[j] = -go-(j-1)ge + max_{k<=j-1}(M[k]+k*ge)
        cm = jax.lax.cummax(m_new + jcol * ge)
        iy_new = jnp.concatenate([jnp.full((1,), NEG),
                                  cm[:-1] - go - (jcol[1:] - 1.0) * ge])
        m_left = jnp.concatenate([jnp.full((1,), NEG), m_new[:-1]])
        iy_left = jnp.concatenate([jnp.full((1,), NEG), iy_new[:-1]])
        dir_iy = (iy_left - ge > m_left - go).astype(jnp.int32)

        dirs = _pack(dir_m.astype(jnp.int32), dir_ix, dir_iy)

        # Capture the row i == la for global traceback start.
        hit = (i == la)
        at_la_m = jnp.where(hit, m_new, at_la_m)
        at_la_ix = jnp.where(hit, ix_new, at_la_ix)
        at_la_iy = jnp.where(hit, iy_new, at_la_iy)

        # Track the best local cell (M state only), masked to valid region.
        row_masked = jnp.where(col_valid & (i <= la), m_new, NEG)
        j_best = jnp.argmax(row_masked)
        v_best = row_masked[j_best]
        best_v, best_i, best_j = best
        upd = v_best > best_v
        best = (jnp.where(upd, v_best, best_v),
                jnp.where(upd, i, best_i),
                jnp.where(upd, j_best.astype(jnp.int32), best_j))

        return (m_new, ix_new, iy_new, at_la_m, at_la_ix, at_la_iy, best, i), dirs

    best0 = (jnp.float32(NEG), jnp.int32(0), jnp.int32(0))
    init = (m0, ix0, iy0, m0, ix0, iy0, best0, jnp.int32(0))
    (_, _, _, fm, fx, fy, best, _), dirs = jax.lax.scan(row_step, init, a)

    if local:
        score, bi, bj = best
        return ForwardResult(dirs, score, bi, bj, jnp.int32(M_ST))
    end_scores = jnp.stack([fm[lb], fx[lb], fy[lb]])
    st = jnp.argmax(end_scores).astype(jnp.int32)
    return ForwardResult(dirs, end_scores[st], la.astype(jnp.int32),
                         lb.astype(jnp.int32), st)


def traceback(a, b, fwd: ForwardResult, gap_code: int):
    """Walk packed directions back to an aligned pair (gap-padded rows).

    ``fwd.dirs`` rows are DP rows 1..; it is indexed in place (2-D, no
    reshape), so a padded kernel buffer is read without a relayout copy.
    """
    n, m = a.shape[0], b.shape[0]
    out_len = n + m

    def step(t, carry):
        i, j, st, done, out_a, out_b, k = carry
        byte = FRESH | (jnp.where(j == 1, 0, 1) << 3)        # row 0
        if fwd.dirs.shape[0]:
            byte = jnp.where(i == 0, byte, fwd.dirs[jnp.maximum(i - 1, 0),
                                                    j].astype(jnp.int32))
        dir_m = byte & 3
        dir_ix = (byte >> 2) & 1
        dir_iy = (byte >> 3) & 1

        is_m = (st == M_ST)
        is_ix = (st == IX_ST)
        # emit characters for this step
        ca = jnp.where(is_m | is_ix, a[jnp.maximum(i - 1, 0)], gap_code).astype(jnp.int8)
        cb = jnp.where(is_m | (st == IY_ST), b[jnp.maximum(j - 1, 0)], gap_code).astype(jnp.int8)
        # O(1) in-place-friendly writes: when done, rewrite the existing value.
        out_a = out_a.at[k].set(jnp.where(done, out_a[k], ca))
        out_b = out_b.at[k].set(jnp.where(done, out_b[k], cb))

        ni = jnp.where(is_m | is_ix, i - 1, i)
        nj = jnp.where(is_m | (st == IY_ST), j - 1, j)
        nst = jnp.where(is_m, dir_m,
                        jnp.where(is_ix, jnp.where(dir_ix == 1, IX_ST, M_ST),
                                  jnp.where(dir_iy == 1, IY_ST, M_ST)))
        fresh_stop = is_m & (dir_m == FRESH)
        ndone = done | fresh_stop | ((ni == 0) & (nj == 0))
        k = jnp.where(done, k, k + 1)
        i = jnp.where(done, i, ni)
        j = jnp.where(done, j, nj)
        st = jnp.where(done, st, nst.astype(jnp.int32))
        return (i, j, st, ndone, out_a, out_b, k)

    out_a = jnp.full((out_len,), gap_code, jnp.int8)
    out_b = jnp.full((out_len,), gap_code, jnp.int8)
    init = (fwd.start_i, fwd.start_j, fwd.start_state,
            (fwd.start_i == 0) & (fwd.start_j == 0),
            out_a, out_b, jnp.int32(0))
    i, j, st, done, out_a, out_b, k = jax.lax.fori_loop(0, out_len, step, init)

    # The walk emitted columns in reverse; un-reverse the first k entries.
    def unrev(x):
        return jnp.roll(jnp.flip(x), k - out_len)
    return unrev(out_a), unrev(out_b), k


def rows_from_codes(a, b, start_i, start_j, codes, k, gap_code: int):
    """Gapped rows of walks recorded as one state code per column.

    a: (B, n), b: (B, m); codes: (B, >= n + m) int, column t (t < k) the
    state of the walk's t-th step back from (start_i, start_j): M consumes
    a and b, Ix a, Iy b. Column t of the reversed row carries
    x[start - 1 - (# earlier columns consuming x)] where its state consumes
    x, else a gap; un-reversed like ``traceback``, whose rows these equal
    byte for byte. Returns (a_row, b_row), each (B, n + m) int8.
    """
    out_len = a.shape[1] + b.shape[1]
    codes = codes[:, :out_len].astype(jnp.int32)
    t = jnp.arange(out_len)

    def row(x, lx, consumes, kk):
        use = (t < kk) & consumes
        cnt = jnp.cumsum(use.astype(jnp.int32)) - use
        idx = jnp.clip(lx - 1 - cnt, 0, x.shape[0] - 1)
        rev = jnp.where(use, x[idx], gap_code).astype(jnp.int8)
        return jnp.roll(jnp.flip(rev), kk - out_len)

    is_m = codes == M_ST
    rows = jax.vmap(row)
    return (rows(a, start_i, is_m | (codes == IX_ST), k),
            rows(b, start_j, is_m | (codes == IY_ST), k))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend", "local", "gap_code"))
def align_pair(a, la, b, lb, sub, *, gap_open, gap_extend, local=False, gap_code=5):
    """Align one pair; returns AlignResult with gap-padded aligned rows."""
    fwd = gotoh_forward(a, la, b, lb, sub, gap_open, gap_extend, local=local)
    a_row, b_row, k = traceback(a, b, fwd, gap_code)
    return AlignResult(fwd.score, a_row, b_row, k, fwd.start_i, fwd.start_j)


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend", "local", "gap_code"))
def align_many_to_one(A, lens, b, lb, sub, *, gap_open, gap_extend,
                      local=False, gap_code=5):
    """vmap of align_pair over queries A (N, La) against one target b.

    This is HAlign-II's map(1) stage: the center sequence b is the broadcast
    variable, every worker aligns its shard of A against it.
    """
    f = lambda a, la: align_pair(a, la, b, lb, sub, gap_open=gap_open,
                                 gap_extend=gap_extend, local=local,
                                 gap_code=gap_code)
    return jax.vmap(f)(A, lens)


def score_only(a, la, b, lb, sub, *, gap_open, gap_extend, local=False):
    """Alignment score without materializing directions (O(m) memory)."""
    fwd = gotoh_forward(a, la, b, lb, sub, gap_open, gap_extend, local=local)
    return fwd.score

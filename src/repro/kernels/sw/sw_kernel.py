"""Pallas TPU kernel: batched Gotoh DP forward (scores + packed directions).

TPU adaptation of the paper's Smith-Waterman engine. The 2D DP is blocked by
query rows: grid = (pair_groups, row_blocks). One program runs G pairs at
once, one per sublane of an f32 vreg (G = min(8, B)): every DP row is a
(G, Mp) array, so each vector op of the row recurrence advances G pairs.
The kernel keeps the previous DP rows (M/Ix/Iy) in VMEM scratch that
persists across the sequential row-block grid dimension, so HBM traffic is
exactly one int8 direction row per pair per DP row (the score rows never
leave VMEM). Within a row the horizontal affine-gap recurrence
Iy[j] = max(M[j-1]-go, Iy[j-1]-ge) is re-expressed as a running max
(cummax) over M[k]+k*ge — the same trick as the jnp oracle — so every row
is pure vector work on the VPU with no sequential-in-j loop.

Layout for the TPU target (what Mosaic accepts):
  * columns are padded to Mp = round_up(m+1, 128) lanes; padded columns sit
    right of every real one and the recurrence only flows left to right,
    so they never touch a real cell;
  * each pair's per-target profile ``prof[c, j] = sub[c, b[j-1]]`` (column
    0 = 0, built by ops.py) sits in its sublane of a (C, G, Mp) group
    block; the substitution row takes, for each sublane, the profile tile
    of that pair's query residue (a dynamic leading index) — no gather;
  * the query residues of each row and the per-pair lengths are scalars in
    SMEM (a (G, block_rows) row block, and a scalar-prefetch operand); the
    lengths become (G, 1) vectors once per program;
  * in-row shifts are lane rolls plus a lane-0 fill, the running max is a
    log-step roll/max scan, argmax is max + first-matching-lane min, all
    per sublane;
  * direction rows are packed 2+1+1 bits and staged as int32 in VMEM, one
    (block_rows, Mp) slab per pair, by a sublane-strided store per row;
    each slab is cast to one int8 tile per row block.

A batch that is not a multiple of G is padded with empty slots (length 0,
never live); their direction rows fall outside the (B, n, Mp) output and
are dropped. Row 0 of the DP is closed-form (``core.pairwise`` documents
it), so the kernel writes DP rows 1..n only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, kernel_call, round_up
from ...core.pairwise import NEG, M_ST, IX_ST, IY_ST, FRESH

# pairs a program runs, one per sublane of an (8, 128) f32 vreg
PAIRS_PER_PROGRAM = 8

# state rows of the (6, G, Mp) f32 scratch
_M, _IX, _IY, _CAP_M, _CAP_IX, _CAP_IY = range(6)


def group_size(B: int) -> int:
    """Pairs per program for a batch of B: up to one per sublane."""
    return max(1, min(PAIRS_PER_PROGRAM, B))


def _shift_right(x, fill, lane):
    """Lane j takes x[j-1]; lane 0 takes ``fill`` (concat([fill], x[:-1]))."""
    return jnp.where(lane == 0, fill, pltpu.roll(x, 1, 1))


def _cummax(x, lane):
    """Inclusive running max along lanes as a log-step roll/max scan."""
    k = 1
    while k < x.shape[1]:
        x = jnp.maximum(x, jnp.where(lane >= k, pltpu.roll(x, k, 1), x))
        k *= 2
    return x


def _row_update(m_prev, ix_prev, iy_prev, s_full, go, ge, jcol, lane,
                local: bool):
    """One DP row of every pair in the group; each sublane mirrors
    pairwise.row_step lane for lane."""
    h_prev = jnp.maximum(m_prev, jnp.maximum(ix_prev, iy_prev))
    amax = jnp.where(m_prev >= h_prev, M_ST,
                     jnp.where(ix_prev >= h_prev, IX_ST, IY_ST))
    h_diag = _shift_right(h_prev, NEG, lane)
    dir_m = _shift_right(amax, M_ST, lane)

    m_new = h_diag + s_full
    if local:
        fresh = h_diag <= 0.0
        m_new = jnp.where(fresh, s_full, m_new)
        dir_m = jnp.where(fresh, FRESH, dir_m)
    m_new = jnp.where(lane == 0, NEG, m_new)

    ix_open = m_prev - go
    ix_ext = ix_prev - ge
    ix_new = jnp.maximum(ix_open, ix_ext)
    dir_ix = (ix_ext > ix_open).astype(jnp.int32)

    cm = _cummax(m_new + jcol * ge, lane)
    iy_new = jnp.where(lane == 0, NEG,
                       pltpu.roll(cm, 1, 1) - go - (jcol - 1.0) * ge)
    m_left = _shift_right(m_new, NEG, lane)
    iy_left = _shift_right(iy_new, NEG, lane)
    dir_iy = (iy_left - ge > m_left - go).astype(jnp.int32)

    packed = dir_m | (dir_ix << 2) | (dir_iy << 3)
    return m_new, ix_new, iy_new, packed


def _kernel(lens_ref, a_ref, prof_ref, dirs_ref, out_ref, st, best, drow, *,
            block_rows: int, local: bool, gap_open: float, gap_extend: float):
    g = pl.program_id(0)
    rb = pl.program_id(1)
    n_rb = pl.num_programs(1)
    G, Mp = st.shape[1], st.shape[2]
    go = jnp.float32(gap_open)
    ge = jnp.float32(gap_extend)
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, Mp), 1)
    jcol = lane.astype(jnp.float32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (G, 1), 0)

    def per_pair(col):
        """(G, 1) vector of column ``col`` of the group's lengths."""
        v = jnp.full((G, 1), lens_ref[g * G, col], jnp.int32)
        for k in range(1, G):
            v = jnp.where(sub == k, lens_ref[g * G + k, col], v)
        return v

    la = per_pair(0)
    lb = per_pair(1)

    @pl.when(rb == 0)
    def _init():
        m0 = jnp.where(lane == 0, 0.0, NEG).astype(jnp.float32)
        ix0 = jnp.full((G, Mp), NEG, jnp.float32)
        iy0 = jnp.where(lane >= 1, -(go + (jcol - 1.0) * ge), NEG)
        for row, v in ((_M, m0), (_IX, ix0), (_IY, iy0),
                       (_CAP_M, m0), (_CAP_IX, ix0), (_CAP_IY, iy0)):
            st[row] = v
        best[...] = jnp.where(slot == 0, NEG, 0.0).astype(jnp.float32)

    def row(l, _):
        r = rb * block_rows + l + 1          # DP row index (1-based)
        # sublane k takes pair k's profile row of its query residue
        s_full = prof_ref[0, a_ref[0, 0, 0, l]]
        for k in range(1, G):
            s_full = jnp.where(sub == k, prof_ref[0, a_ref[0, 0, k, l]],
                               s_full)
        m_new, ix_new, iy_new, packed = _row_update(
            st[_M], st[_IX], st[_IY], s_full, go, ge, jcol, lane, local)
        # pair k's row l lands at drow[t, k * block_rows + l] for each
        # lane tile t: one sublane-strided store per vreg
        for t in range(Mp // LANES):
            drow[t, pl.ds(l, G, stride=block_rows if G > 1 else 1), :] = (
                packed[:, t * LANES:(t + 1) * LANES])
        live = r <= la
        hit = r == la
        for src, dst, v in ((_M, _CAP_M, m_new), (_IX, _CAP_IX, ix_new),
                            (_IY, _CAP_IY, iy_new)):
            st[dst] = jnp.where(hit, v, st[dst])
            st[src] = jnp.where(live, v, st[src])
        if local:
            row_masked = jnp.where((lane <= lb) & live, m_new, NEG)
            vb = jnp.max(row_masked, axis=1, keepdims=True)
            jb = jnp.min(jnp.where(row_masked == vb, lane, Mp), axis=1,
                         keepdims=True)
            old = best[...]
            upd = (vb > old[:, 0:1]) & (slot < 3)
            new = jnp.where(slot == 0, vb,
                            jnp.where(slot == 1, jnp.full_like(vb, r).astype(
                                jnp.float32), jb.astype(jnp.float32)))
            best[...] = jnp.where(upd, new, old)
        return 0

    jax.lax.fori_loop(0, block_rows, row, 0)
    for k in range(G):
        rows = slice(k * block_rows, (k + 1) * block_rows)
        for t in range(Mp // LANES):
            dirs_ref[k, :, t * LANES:(t + 1) * LANES] = (
                drow[t, rows, :].astype(jnp.int8))

    @pl.when(rb == n_rb - 1)
    def _fin():
        if local:
            res = jnp.where(slot == 3, jnp.float32(M_ST), best[...])
        else:
            at_lb = lane == lb

            def end(row):
                return jnp.sum(jnp.where(at_lb, st[row], 0.0), axis=1,
                               keepdims=True)
            em, ex, ey = end(_CAP_M), end(_CAP_IX), end(_CAP_IY)
            # jnp.argmax over (M, Ix, Iy): first maximal state wins ties
            s_m = (em >= ex) & (em >= ey)
            s_x = ex >= ey
            state = jnp.where(s_m, M_ST, jnp.where(s_x, IX_ST, IY_ST))
            score = jnp.where(s_m, em, jnp.where(s_x, ex, ey))
            res = jnp.where(slot == 0, score,
                  jnp.where(slot == 1, la.astype(jnp.float32),
                  jnp.where(slot == 2, lb.astype(jnp.float32),
                  jnp.where(slot == 3, state.astype(jnp.float32), 0.0))))
        out_ref[0] = res


def gotoh_forward_kernel(a, prof, lens, *, gap_open: float, gap_extend: float,
                         local: bool, block_rows: int = 128,
                         interpret: bool | None = None):
    """a: (B, n) int32 (n % block_rows == 0), prof: (B, C, Mp) f32 with
    Mp % 128 == 0, lens: (B, 2) i32.

    Returns dirs (B, n, Mp) int8 (DP rows 1..n) and out (B, 1, 128) f32
    [score, start_i, start_j, start_state, 0...].
    """
    B, n = a.shape
    C, Mp = prof.shape[1], prof.shape[2]
    assert n % block_rows == 0, (n, block_rows)
    assert Mp % LANES == 0, Mp
    G = group_size(B)
    n_groups = -(-B // G)
    pad = n_groups * G - B
    # empty slots: length 0, so no row of theirs is ever live
    lens = jnp.pad(lens, ((0, pad), (0, 0)))
    a = jnp.pad(a, ((0, pad), (0, 0))).reshape(
        n_groups, G, n // block_rows, block_rows).transpose(0, 2, 1, 3)
    prof = jnp.pad(prof, ((0, pad), (0, 0), (0, 0))).reshape(
        n_groups, G, C, Mp).transpose(0, 2, 1, 3)
    kern = functools.partial(_kernel, block_rows=block_rows, local=local,
                             gap_open=gap_open, gap_extend=gap_extend)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups, n // block_rows),
        in_specs=[
            pl.BlockSpec((1, 1, G, block_rows),
                         lambda g, r, lens: (g, r, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, C, G, Mp), lambda g, r, lens: (g, 0, 0, 0)),
        ],
        out_specs=[
            # the last group's empty slots fall past B and are dropped
            pl.BlockSpec((G, block_rows, Mp), lambda g, r, lens: (g, r, 0)),
            pl.BlockSpec((1, G, LANES), lambda g, r, lens: (g, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((6, G, Mp), jnp.float32),
            pltpu.VMEM((G, LANES), jnp.float32),
            pltpu.VMEM((Mp // LANES, G * block_rows, LANES), jnp.int32),
        ],
    )
    dirs, out = kernel_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n, Mp), jnp.int8),
            jax.ShapeDtypeStruct((n_groups, G, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(G, block_rows, C, Mp)),
        interpret=interpret,
    )(lens, a, prof)
    return dirs, out.reshape(n_groups * G, 1, LANES)[:B]


def _vmem_limit(G: int, block_rows: int, C: int, Mp: int) -> int:
    """Scoped VMEM for one program: int32 staging + double-buffered int8
    output tile and profile + the (6, G, Mp) state, with 2x headroom."""
    Gp = round_up(G, 8)
    need = (G * block_rows * Mp * 4 + 2 * G * block_rows * Mp
            + 2 * C * Gp * Mp * 4 + 6 * Gp * Mp * 4)
    return int(min(max(2 * need, 16 << 20), 100 << 20))

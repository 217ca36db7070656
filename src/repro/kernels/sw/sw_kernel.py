"""Pallas TPU kernel: batched Gotoh DP forward (scores + packed directions).

TPU adaptation of the paper's Smith-Waterman engine. The 2D DP is blocked by
query rows: grid = (batch, row_blocks); the kernel keeps the previous DP row
(M/Ix/Iy, each one (1, Mp) f32 lane vector) in VMEM scratch that persists
across the sequential row-block grid dimension, so HBM traffic is exactly
one int8 direction row per DP row (the score rows never leave VMEM). Within
a row the horizontal affine-gap recurrence Iy[j] = max(M[j-1]-go, Iy[j-1]-ge)
is re-expressed as a running max (cummax) over M[k]+k*ge — the same trick as
the jnp oracle — so every row is pure vector work on the VPU with no
sequential-in-j loop.

Layout for the TPU target (what Mosaic accepts):
  * columns are padded to Mp = round_up(m+1, 128) lanes; padded columns sit
    right of every real one and the recurrence only flows left to right,
    so they never touch a real cell;
  * the substitution row is a dynamic sublane slice of a per-target
    profile ``prof[c, j] = sub[c, b[j-1]]`` (column 0 = 0) built by ops.py
    — no gather in the kernel;
  * the query residue of each row and the per-pair lengths are scalars in
    SMEM (a row block, and a scalar-prefetch operand);
  * in-row shifts are lane rolls plus a lane-0 fill, the running max is a
    log-step roll/max scan, argmax is max + first-matching-lane min;
  * direction rows are packed 2+1+1 bits, staged as int32 rows in VMEM and
    cast to one int8 (block_rows, Mp) tile per row block.

Row 0 of the DP is closed-form (``core.pairwise`` documents it), so the
kernel writes DP rows 1..n only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, kernel_call, round_up
from ...core.pairwise import NEG, M_ST, IX_ST, IY_ST, FRESH

# state rows of the (8, Mp) f32 scratch
_M, _IX, _IY, _CAP_M, _CAP_IX, _CAP_IY = range(6)


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
    """One DP row; mirrors pairwise.row_step lane for lane."""
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
    p = pl.program_id(0)
    rb = pl.program_id(1)
    n_rb = pl.num_programs(1)
    la = lens_ref[p, 0]
    lb = lens_ref[p, 1]
    Mp = st.shape[1]
    go = jnp.float32(gap_open)
    ge = jnp.float32(gap_extend)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, Mp), 1)
    jcol = lane.astype(jnp.float32)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when(rb == 0)
    def _init():
        m0 = jnp.where(lane == 0, 0.0, NEG).astype(jnp.float32)
        ix0 = jnp.full((1, Mp), NEG, jnp.float32)
        iy0 = jnp.where(lane >= 1, -(go + (jcol - 1.0) * ge), NEG)
        for row, v in ((_M, m0), (_IX, ix0), (_IY, iy0),
                       (_CAP_M, m0), (_CAP_IX, ix0), (_CAP_IY, iy0)):
            st[row:row + 1, :] = v
        best[...] = jnp.where(slot == 0, NEG, 0.0).astype(jnp.float32)

    def row(l, _):
        r = rb * block_rows + l + 1          # DP row index (1-based)
        s_full = prof_ref[0, pl.ds(a_ref[0, 0, 0, l], 1), :]
        m_new, ix_new, iy_new, packed = _row_update(
            st[_M:_M + 1, :], st[_IX:_IX + 1, :], st[_IY:_IY + 1, :],
            s_full, go, ge, jcol, lane, local)
        drow[pl.ds(l, 1), :] = packed
        live = r <= la
        hit = r == la
        for src, dst, v in ((_M, _CAP_M, m_new), (_IX, _CAP_IX, ix_new),
                            (_IY, _CAP_IY, iy_new)):
            st[dst:dst + 1, :] = jnp.where(hit, v, st[dst:dst + 1, :])
            st[src:src + 1, :] = jnp.where(live, v, st[src:src + 1, :])
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
    dirs_ref[0] = drow[...].astype(jnp.int8)

    @pl.when(rb == n_rb - 1)
    def _fin():
        if local:
            res = jnp.where(slot == 3, jnp.float32(M_ST), best[...])
        else:
            at_lb = lane == lb

            def end(row):
                return jnp.sum(jnp.where(at_lb, st[row:row + 1, :], 0.0),
                               axis=1, keepdims=True)
            em, ex, ey = end(_CAP_M), end(_CAP_IX), end(_CAP_IY)
            # jnp.argmax over (M, Ix, Iy): first maximal state wins ties
            s_m = (em >= ex) & (em >= ey)
            s_x = ex >= ey
            state = jnp.where(s_m, M_ST, jnp.where(s_x, IX_ST, IY_ST))
            score = jnp.where(s_m, em, jnp.where(s_x, ex, ey))
            la_f = jnp.full_like(score, la)
            lb_f = jnp.full_like(score, lb)
            res = jnp.where(slot == 0, score,
                  jnp.where(slot == 1, la_f,
                  jnp.where(slot == 2, lb_f,
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
    kern = functools.partial(_kernel, block_rows=block_rows, local=local,
                             gap_open=gap_open, gap_extend=gap_extend)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, n // block_rows),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_rows),
                         lambda p, r, lens: (p, r, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, C, Mp), lambda p, r, lens: (p, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_rows, Mp), lambda p, r, lens: (p, r, 0)),
            pl.BlockSpec((1, 1, LANES), lambda p, r, lens: (p, 0, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((8, Mp), jnp.float32),
            pltpu.VMEM((1, LANES), jnp.float32),
            pltpu.VMEM((block_rows, Mp), jnp.int32),
        ],
    )
    return kernel_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, n, Mp), jnp.int8),
            jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(block_rows, C, Mp)),
        interpret=interpret,
    )(lens, a.reshape(B, n // block_rows, 1, block_rows), prof)


def _vmem_limit(block_rows: int, C: int, Mp: int) -> int:
    """Scoped VMEM for one program: int32 staging + double-buffered int8
    output tile and profile + the (8, Mp) state, with 2x headroom."""
    need = (block_rows * Mp * 4 + 2 * block_rows * Mp
            + 2 * round_up(C, 8) * Mp * 4 + 8 * Mp * 4)
    return int(min(max(2 * need, 16 << 20), 100 << 20))

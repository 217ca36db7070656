"""Pallas TPU kernel: SW/Gotoh traceback over the forward kernel's
direction matrix.

The forward kernel (``sw_kernel``) leaves a (B, n_pad, Mp) int8 matrix of
packed direction bytes in HBM. This kernel walks it back from each pair's
start cell, G = min(8, B) pairs a program in lockstep (the forward
kernel's grouping), and emits one state code per alignment column (M /
Ix / Iy: which of a and b the column consumes) plus the column count k;
``ops`` builds the gapped rows from the codes with a prefix sum
(``core.pairwise.rows_from_codes``). The walk stops once every pair of
the group is done (or after n + m steps), not after a fixed n + m.

Layout for the TPU target (what Mosaic accepts):
  * the direction matrix stays in HBM (``memory_space=pl.ANY``); each
    pair holds a window of R full-width rows in VMEM, two slots of a
    (G, 2, R, Mp) int8 scratch. The walk only moves up (and left), so a
    window's successor is known when the walk enters it: it is DMA'd into
    the other slot at once and is in place when the walk crosses into it;
  * windows start on 32-row boundaries (the int8 tile height). The walk
    checks its window every ``STEPS`` = 32 steps and moves at most one
    row a step, so a window needs 32 rows of room above the walk at a
    check, and successive windows overlap by 32 rows: R >= 64 rows, as
    many as the ``TRACE_BYTES`` budget holds at this Mp;
  * a step reads each pair's byte with an aligned (32, 128) tile load at
    a dynamic row block and lane block, then a sublane/lane select and a
    max: the same read as the banded fused traceback. Row 0 of the DP is
    closed-form, exactly as ``core.pairwise.traceback`` computes it, and
    a local path stops on a FRESH byte;
  * the walk state (i, j, state, done, k) is scalar per pair; each step
    writes the group's G state codes into one (G, 128) vreg, which goes
    to the (kr, G, 128) int32 code block once per 32 steps;
  * the per-pair start cells and states are scalar-prefetch operands.

Every index the walk reads is the one ``core.pairwise.traceback`` reads
(row max(i - 1, 0), column j with jnp's negative wrap), so the codes, k
and the rows built from them are byte-identical to it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, kernel_call, round_up
from ...core.pairwise import M_ST, IX_ST, IY_ST, FRESH
from .sw_kernel import group_size

# walk steps between two window checks, and the row alignment of a window
STEPS = 32
TILE_ROWS = 32

# int8 direction rows a program may hold in VMEM (G pairs x 2 slots x R
# rows x Mp); R shrinks to fit it at long target widths
TRACE_BYTES = 32 << 20


def window_rows(n_pad: int, Mp: int, G: int) -> int:
    """Rows R of one direction window: the whole matrix where it fits, else
    the most 32-row tiles (at least two) that fit ``TRACE_BYTES``. A matrix
    whose rows are not whole tiles (n_pad < 128, the forward kernel's one
    row block) is held whole."""
    fit = max(2 * TILE_ROWS,
              TRACE_BYTES // (2 * G * Mp) // TILE_ROWS * TILE_ROWS)
    if n_pad <= fit or n_pad % TILE_ROWS:
        return n_pad
    return fit


def _kernel(starts_ref, dirs_hbm, codes_ref, k_ref, buf, sem, *,
            out_len: int):
    g = pl.program_id(0)
    G, R, Mp = buf.shape[0], buf.shape[2], buf.shape[3]
    B, n_pad = dirs_hbm.shape[0], dirs_hbm.shape[1]
    th = TILE_ROWS if R % TILE_ROWS == 0 else R
    lane = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (G, LANES), 0)
    t_sub = jax.lax.broadcasted_iota(jnp.int32, (th, LANES), 0)
    t_lane = jax.lax.broadcasted_iota(jnp.int32, (th, LANES), 1)

    def copy(k, slot, s):
        p = jnp.minimum(g * G + k, B - 1)
        s = pl.multiple_of(s, TILE_ROWS)      # every window start is one
        return pltpu.make_async_copy(dirs_hbm.at[p, pl.ds(s, R), :],
                                     buf.at[k, slot], sem.at[k, slot])

    def first_window(x):
        """Lowest window start that holds row x with 31 rows above it."""
        s = (jnp.maximum(x + 1 - R, 0) + TILE_ROWS - 1) // TILE_ROWS
        return jnp.minimum(s * TILE_ROWS, n_pad - R)

    def next_window(s):
        """The window above s: overlaps it by the 32 rows the walk may
        still need after a check."""
        return jnp.maximum(s - R + STEPS, 0)

    codes_ref[...] = jnp.zeros(codes_ref.shape, jnp.int32)
    walks, wins = [], []
    for k in range(G):
        p = g * G + k
        i0 = starts_ref[3 * p]
        j0 = starts_ref[3 * p + 1]
        s0 = first_window(jnp.maximum(i0 - 1, 0))
        copy(k, 0, s0).start()

        @pl.when(s0 > 0)
        def _prefetch():
            copy(k, 1, next_window(s0)).start()

        walks.append((i0, j0, starts_ref[3 * p + 2], (i0 == 0) & (j0 == 0),
                      jnp.int32(0)))
        wins.append((s0, jnp.int32(0)))
    for k in range(G):
        copy(k, 0, 0).wait()

    def step(t, walk, win, k):
        """One walk step of pair k: pairwise.traceback's ``step``."""
        i, j, st, done, kk = walk
        s, slot = win
        r = jnp.clip(jnp.maximum(i - 1, 0) - s, 0, R - 1)
        jj = jnp.clip(jnp.where(j < 0, j + Mp, j), 0, Mp - 1)
        tile = buf[k, slot, pl.ds(pl.multiple_of(r // th * th, th), th),
                   pl.ds(pl.multiple_of(jj // LANES * LANES, LANES), LANES)]
        hit = (t_sub == r % th) & (t_lane == jj % LANES)
        byte = jnp.max(jnp.where(hit, tile.astype(jnp.int32), 0))
        byte = jnp.where(i == 0, FRESH | (jnp.where(j == 1, 0, 1) << 3),
                         byte)
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
        stop = done | (t >= out_len)
        ndone = stop | (is_m & (dir_m == FRESH)) | ((ni == 0) & (nj == 0))
        return (jnp.where(stop, i, ni), jnp.where(stop, j, nj),
                jnp.where(stop, st, nst), ndone, jnp.where(stop, kk, kk + 1))

    def chunk(carry):
        t0, walks, wins = carry
        new_wins = []
        for k in range(G):
            i, _, _, done, _ = walks[k]
            s, slot = wins[k]
            move = (~done) & (s > 0) & (jnp.maximum(i - 1, 0) - s < STEPS - 1)

            @pl.when(move)
            def _switch():
                copy(k, 1 - slot, 0).wait()
                s2 = next_window(s)

                @pl.when(s2 > 0)
                def _prefetch():
                    copy(k, slot, next_window(s2)).start()

            new_wins.append((jnp.where(move, next_window(s), s),
                             jnp.where(move, 1 - slot, slot)))

        row = t0 // LANES

        def body(u, c):
            cur, walks = c
            t = t0 + u
            # column t consumes what each pair's state before the step
            # says; columns past a pair's k are never read
            codes = jnp.zeros((G, LANES), jnp.int32)
            for k in range(G):
                codes = jnp.where(sub == k, walks[k][2], codes)
            cur = jnp.where(lane == t % LANES, codes, cur)
            return cur, tuple(step(t, walks[k], new_wins[k], k)
                              for k in range(G))

        cur, walks = jax.lax.fori_loop(0, STEPS, body,
                                       (codes_ref[0, row], walks))
        codes_ref[0, row] = cur
        return t0 + STEPS, walks, tuple(new_wins)

    def live(carry):
        t0, walks, _ = carry
        any_live = ~walks[0][3]
        for k in range(1, G):
            any_live = any_live | ~walks[k][3]
        return any_live & (t0 < out_len)

    _, walks, wins = jax.lax.while_loop(
        live, chunk, (jnp.int32(0), tuple(walks), tuple(wins)))
    for k in range(G):
        s, slot = wins[k]

        @pl.when(s > 0)
        def _drain():
            copy(k, 1 - slot, 0).wait()

    kv = jnp.zeros((G, LANES), jnp.int32)
    for k in range(G):
        kv = jnp.where(sub == k, walks[k][4], kv)
    k_ref[0] = kv


def sw_traceback_kernel(dirs, starts, *, out_len: int,
                        interpret: bool | None = None):
    """dirs: (B, n_pad, Mp) int8 DP rows 1..n (the forward kernel's
    output), starts: (B, 3) i32 [start_i, start_j, start_state].

    Returns codes (B, kr * 128) int32, the state of each alignment column
    from the start cell back (valid for the first k entries), and k (B,)
    i32.
    """
    B, n_pad, Mp = dirs.shape
    # the forward kernel pads rows to 8 (one block) or to 32-row blocks
    assert Mp % LANES == 0 and n_pad % 8 == 0, (n_pad, Mp)
    G = group_size(B)
    n_groups = -(-B // G)
    R = window_rows(n_pad, Mp, G)
    kr = -(-out_len // LANES)
    # empty slots start at (0, 0): done before the first step
    starts = jnp.pad(starts.astype(jnp.int32),
                     ((0, n_groups * G - B), (0, 0))).reshape(-1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n_groups,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((1, kr, G, LANES), lambda g, s: (g, 0, 0, 0)),
            pl.BlockSpec((1, G, LANES), lambda g, s: (g, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((G, 2, R, Mp), jnp.int8),
                        pltpu.SemaphoreType.DMA((G, 2))],
    )
    need = 2 * G * round_up(R, TILE_ROWS) * Mp + 2 * (kr + 1) * 8 * LANES * 4
    codes, k = kernel_call(
        functools.partial(_kernel, out_len=out_len),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((n_groups, kr, G, LANES), jnp.int32),
            jax.ShapeDtypeStruct((n_groups, G, LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=int(min(max(need + (8 << 20), 16 << 20),
                                     100 << 20))),
        interpret=interpret,
    )(starts, dirs)
    codes = codes.transpose(0, 2, 1, 3).reshape(n_groups * G, kr * LANES)
    return codes[:B], k[:, :, 0].reshape(-1)[:B]

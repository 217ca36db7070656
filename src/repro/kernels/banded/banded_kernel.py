"""Pallas TPU kernels: banded Gotoh forward + fused score-and-traceback.

Both kernels run the same width-W band recurrence (``ref.band_row_update``
— the function the jnp scan in ``align.banded`` also calls, which is what
makes parity bit-identical rather than approximate) from one kernel body:

forward — batch path. grid = (batch, row_blocks); the three band state
rows (M/Ix/Iy) live in VMEM scratch persisting across the sequential
row-block dimension, rows advance as an anti-diagonal wavefront (all W band
cells of a row are elementwise or cummax work on the VPU lanes), and HBM
traffic per DP row is one int8 direction slab — O(n·W) instead of the SW
kernel's O(n·m). The edge-pressure overflow detector runs in-kernel on the
same row state, so the ``AlignEngine`` fallback contract needs no extra
pass.

fused — coalesced ``align_pairs`` path. Same grid, but the direction rows
go to an (n, Wp) VMEM scratch that persists across the row blocks, and the
last row block walks that scratch back from the end cell. The direction
matrix never exists in HBM at all: per pair the kernel moves only the
sequences in and the path out — one int8 state code per alignment column
(M / Ix / Iy, i.e. which of a and b the column consumes);
``core.pairwise.rows_from_codes`` turns the codes into the two gapped
rows with a prefix sum and a gather, as it does for the SW traceback
kernel.

Layout for the TPU target (what Mosaic accepts): band rows are (1, Wp)
with Wp = round_up(W, 128) lanes (lanes past W are held at NEG); the
per-pair lengths are scalar-prefetch operands and the query residues an
SMEM row block; the substitution row of a band is an aligned dynamic
window of a per-target profile ``prof[c, x] = sub[c, b[x - pad]]`` (read
as Wp + 128 lanes at a 128-aligned offset, then lane-rolled into place),
so no gather runs in the kernel; the traceback reads one direction byte as
a dynamic sublane row plus a lane select, and records its state codes the
same way.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import LANES, kernel_call, round_up
from ...core.pairwise import NEG
from .ref import (band_lo, band_row_init, band_row_update, edge_pressure,
                  end_state, trace_step_math)

# rows of the (8, Wp) f32 state scratch: band rows, then per-pair stats
# broadcast across the lanes (end-cell capture per Gotoh state, overflow
# flag, previous live row's best score for edge pressure)
_M, _IX, _IY, _CAP_M, _CAP_IX, _CAP_IY, _EDGE, _HB = range(8)


def _row(st, i):
    return st[i:i + 1, :]


def _val(st, i):
    return st[i:i + 1, 0:1]


def _put(st, i, v):
    st[i:i + 1, :] = jnp.broadcast_to(v, (1, st.shape[1]))


def _window(prof_ref, a_i, x0, Wp: int):
    """Lanes [x0, x0 + Wp) of profile row ``a_i``: a 128-aligned dynamic
    load of Wp + 128 lanes, rolled left by the misalignment."""
    base = pl.multiple_of((x0 // LANES) * LANES, LANES)
    win = prof_ref[0, pl.ds(a_i, 1), pl.ds(base, Wp + LANES)]
    d = x0 - base
    return pltpu.roll(win, (Wp + LANES - d) % (Wp + LANES), 1)[:, :Wp]


def _scalar(v):
    """(1, k) int32 vector -> scalar (its max)."""
    return jnp.max(v)


def _kernel(lens_ref, margin_ref, a_ref, prof_ref, *refs, band: int,
            block_rows: int, gap_open: float, gap_extend: float, pad: int,
            out_len: int, fused: bool):
    if fused:
        out_ref, codes_ref, st, drow, cbuf = refs
    else:
        dirs_ref, out_ref, st, drow = refs
    W = band
    mid = W // 2
    p = pl.program_id(0)
    rb = pl.program_id(1)
    n_rb = pl.num_programs(1)
    la = lens_ref[p, 0]
    lb = lens_ref[p, 1]
    margin = margin_ref[0]
    Wp = st.shape[1]
    x_max = prof_ref.shape[2] - Wp - LANES
    go = jnp.float32(gap_open)
    ge = jnp.float32(gap_extend)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    @pl.when(rb == 0)
    def _init():
        m0, ix0, iy0, hb0 = band_row_init(la, lb, go, ge, band=W, width=Wp)
        _put(st, _M, m0)
        _put(st, _IX, ix0)
        _put(st, _IY, iy0)
        _put(st, _CAP_M, m0[:, mid:mid + 1])
        _put(st, _CAP_IX, ix0[:, mid:mid + 1])
        _put(st, _CAP_IY, iy0[:, mid:mid + 1])
        _put(st, _EDGE, jnp.float32(0.0))
        _put(st, _HB, hb0)

    def row(l, _):
        r = rb * block_rows + l + 1          # DP row index (1-based)
        lo_prev = band_lo(r - 1, la, lb, W)
        lo_i = band_lo(r, la, lb, W)
        # rows whose band starts past the profile hold no matrix cell
        x0 = jnp.clip(lo_i - 1 + pad, 0, x_max)
        s_row = _window(prof_ref, a_ref[0, 0, 0, l], x0, Wp)
        m_new, ix_new, iy_new, dirs, h_new, h_prev, s = band_row_update(
            _row(st, _M), _row(st, _IX), _row(st, _IY), s_row, lo_prev,
            lo_i, go, ge, lb, band=W, roll=pltpu.roll)
        drow[pl.ds(r - 1 if fused else l, 1), :] = dirs
        # State advances unconditionally (the jnp scan does the same);
        # rows past la only touch the dead padding tail.
        _put(st, _M, m_new)
        _put(st, _IX, ix_new)
        _put(st, _IY, iy_new)

        hit = r == la                        # end cell (la, lb) sits at mid
        for dst, v in ((_CAP_M, m_new), (_CAP_IX, ix_new), (_CAP_IY, iy_new)):
            _put(st, dst, jnp.where(hit, v[:, mid:mid + 1], _val(st, dst)))

        live = r <= la
        comp, hb = edge_pressure(h_new, h_prev, _val(st, _HB), s, margin,
                                 band=W)
        _put(st, _EDGE, jnp.where(live & comp, 1.0, _val(st, _EDGE)))
        _put(st, _HB, jnp.where(live, hb, _val(st, _HB)))
        return 0

    jax.lax.fori_loop(0, block_rows, row, 0)
    if not fused:
        dirs_ref[0] = drow[...].astype(jnp.int8)

    @pl.when(rb == n_rb - 1)
    def _fin():
        score, state = end_state(_val(st, _CAP_M), _val(st, _CAP_IX),
                                 _val(st, _CAP_IY))
        edge_fwd = _val(st, _EDGE)
        la_f = jnp.full((1, 1), la, jnp.int32).astype(jnp.float32)
        lb_f = jnp.full((1, 1), lb, jnp.int32).astype(jnp.float32)
        head = jnp.where(slot == 0, score,
               jnp.where(slot == 1, la_f,
               jnp.where(slot == 2, lb_f,
               jnp.where(slot == 3, state.astype(jnp.float32), 0.0))))
        if not fused:
            out_ref[0] = jnp.where(slot == 4, edge_fwd, head)
            return

        # ---- traceback: walk the VMEM band, never touching HBM dirs ----
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, Wp), 1)
        n_rows = drow.shape[0]
        cbuf[...] = jnp.zeros(cbuf.shape, jnp.int32)

        def tb_step(t, carry):
            i, j, stt, done, edge, oob, k = carry
            o = j - band_lo(i, la, lb, W)
            brow = drow[pl.ds(jnp.clip(i - 1, 0, n_rows - 1), 1), :]
            byte_band = _scalar(jnp.where(lane == o, brow, 0))
            ni, nj, nst, done, ndone, lost, edge_hit = trace_step_math(
                i, j, o, stt, done, byte_band, lb, W)
            # column k consumes a and/or b as state ``stt`` says
            kr = k // LANES
            cur = cbuf[pl.ds(kr, 1), :]
            cbuf[pl.ds(kr, 1), :] = jnp.where(
                (slot == k % LANES) & jnp.logical_not(done), stt, cur)
            return (jnp.where(done, i, ni), jnp.where(done, j, nj),
                    jnp.where(done, stt, nst), ndone, edge | edge_hit,
                    oob | lost, jnp.where(done, k, k + 1))

        st0 = _scalar(jnp.broadcast_to(state, (1, LANES)))
        init = (la, lb, st0, (la == 0) & (lb == 0), jnp.bool_(False),
                jnp.bool_(False), jnp.int32(0))
        (_, _, _, _, edge, oob, k) = jax.lax.fori_loop(0, out_len, tb_step,
                                                       init)
        bad = (edge | oob)
        ok = jnp.where(bad, 0.0, jnp.where((edge_fwd > 0.5) | (score <= NEG / 2),
                                           0.0, 1.0))
        k_f = jnp.full((1, 1), k, jnp.int32).astype(jnp.float32)
        out_ref[0] = jnp.where(slot == 4, k_f,
                     jnp.where(slot == 5, ok,
                     jnp.where(slot == 6, edge_fwd, head)))
        codes_ref[0] = cbuf[...].astype(jnp.int8)


def _vmem_limit(nbytes: int) -> int:
    return int(min(max(2 * nbytes, 16 << 20), 100 << 20))


def _call(a, prof, lens, margin, *, band: int, block_rows: int,
          gap_open: float, gap_extend: float, pad: int, out_len: int,
          fused: bool, interpret):
    B, n = a.shape
    C, P = prof.shape[1], prof.shape[2]
    Wp = round_up(band, LANES)
    assert n % block_rows == 0, (n, block_rows)
    kern = functools.partial(_kernel, band=band, block_rows=block_rows,
                             gap_open=gap_open, gap_extend=gap_extend,
                             pad=pad, out_len=out_len, fused=fused)
    head = pl.BlockSpec((1, 1, LANES), lambda p, r, *_: (p, 0, 0))
    out_head = jax.ShapeDtypeStruct((B, 1, LANES), jnp.float32)
    scratch = [pltpu.VMEM((8, Wp), jnp.float32)]
    if fused:
        kr = -(-out_len // LANES)
        out_specs = [head, pl.BlockSpec((1, kr, LANES),
                                        lambda p, r, *_: (p, 0, 0))]
        out_shape = [out_head,
                     jax.ShapeDtypeStruct((B, kr, LANES), jnp.int8)]
        scratch += [pltpu.VMEM((n, Wp), jnp.int32),
                    pltpu.VMEM((kr, LANES), jnp.int32)]
        vmem = n * Wp * 4 + kr * LANES * 5
    else:
        out_specs = [pl.BlockSpec((1, block_rows, Wp),
                                  lambda p, r, *_: (p, r, 0)), head]
        out_shape = [jax.ShapeDtypeStruct((B, n, Wp), jnp.int8), out_head]
        scratch += [pltpu.VMEM((block_rows, Wp), jnp.int32)]
        vmem = block_rows * Wp * 6
    vmem += 2 * round_up(C, 8) * P * 4
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n // block_rows),
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_rows),
                         lambda p, r, *_: (p, r, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, C, P), lambda p, r, *_: (p, 0, 0)),
        ],
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    return kernel_call(
        kern,
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(vmem)),
        interpret=interpret,
    )(lens, margin, a.reshape(B, n // block_rows, 1, block_rows), prof)


def banded_forward_kernel(a, prof, lens, margin, *, gap_open: float,
                          gap_extend: float, band: int, pad: int,
                          block_rows: int = 128,
                          interpret: bool | None = None):
    """a: (B, n) int32 (n % block_rows == 0), prof: (B, C, P) f32 profile
    (column x = b[x - pad]), lens: (B, 2) i32, margin: (1,) f32.

    Returns dirs (B, n, Wp) int8 (DP rows 1..n, lanes past ``band`` are
    padding) and out (B, 1, 128) f32 [score, la, lb, start_state, edge, 0...].
    """
    return _call(a, prof, lens, margin, band=band, block_rows=block_rows,
                 gap_open=gap_open, gap_extend=gap_extend, pad=pad,
                 out_len=0, fused=False, interpret=interpret)


def banded_fused_kernel(a, prof, lens, margin, *, gap_open: float,
                        gap_extend: float, band: int, pad: int, out_len: int,
                        block_rows: int = 128,
                        interpret: bool | None = None):
    """Fused banded score+traceback; operands as ``banded_forward_kernel``.

    Returns out (B, 1, 128) f32 [score, la, lb, st, aln_len, ok, edge, 0...]
    and codes (B, ceil(out_len/128), 128) int8: the state (M/Ix/Iy) of each
    alignment column from the end cell back, valid for the first aln_len
    entries — no direction matrix ever reaches HBM.
    """
    return _call(a, prof, lens, margin, band=band, block_rows=block_rows,
                 gap_open=gap_open, gap_extend=gap_extend, pad=pad,
                 out_len=out_len, fused=True, interpret=interpret)

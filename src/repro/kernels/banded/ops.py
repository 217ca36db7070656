"""jit'd public wrappers for the banded Gotoh Pallas kernels.

``banded_forward_pallas`` pads the query axis to the row-block size and
returns a batched ``BandedForward`` — drop-in for vmapped
``align.banded.banded_forward`` (the jnp traceback then consumes the HBM
dirs exactly as before). ``banded_pairs_fused`` is the whole map(1) in
one kernel: scores, path, lengths, and the ok flag come back with no
direction matrix ever materialized in HBM; the gapped rows are rebuilt
from the path's per-column state codes here.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.pairwise import M_ST, IX_ST, IY_ST
from .. import LANES, round_up
from .banded_kernel import banded_forward_kernel, banded_fused_kernel
from .ref import BandedForward


def _operands(a, b, sub, band: int, block_rows: int):
    """Kernel operands: row-block padded int32 queries, the per-target
    substitution profile with ``pad`` leading columns, and the margin."""
    n = a.shape[1]
    B, m = b.shape
    Wp = round_up(band, LANES)
    # row blocks: a multiple of 32 (the int8 tile height), or one block
    # covering the whole (8-row padded) query
    br = min(round_up(block_rows, 32), round_up(max(n, 1), 8))
    a = jnp.pad(a.astype(jnp.int32), ((0, 0), (0, (-n) % br)))
    pad = round_up(band // 2 + 1, LANES)          # lo_i - 1 >= -pad
    P = round_up(pad + m + Wp + LANES, LANES)
    sub = sub.astype(jnp.float32)
    prof = jnp.transpose(sub[:, b.astype(jnp.int32)], (1, 0, 2))
    prof = jnp.pad(prof, ((0, 0), (0, 0), (pad, P - pad - m)))
    return a, prof, jnp.max(sub)[None], pad, br


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "block_rows",
                                             "interpret"))
def banded_forward_pallas(a, b, lens, sub, *, gap_open, gap_extend, band,
                          block_rows: int = 128,
                          interpret: bool | None = None) -> BandedForward:
    """Batched banded forward. a: (B, n) int8, b: (B, m), lens: (B, 2) i32.

    Returns BandedForward with batched leaves: dirs (B, n, band) int8,
    score/edge (B,), start_* (B,) i32. ``interpret=None`` resolves
    platform-aware (compiled on TPU, interpreter elsewhere).
    """
    n = a.shape[1]
    ap, prof, margin, pad, br = _operands(a, b, sub, band, block_rows)
    dirs, out = banded_forward_kernel(
        ap, prof, lens.astype(jnp.int32), margin, gap_open=float(gap_open),
        gap_extend=float(gap_extend), band=band, pad=pad, block_rows=br,
        interpret=interpret)
    out = out[:, 0, :]
    return BandedForward(dirs[:, :n, :band], out[:, 0],
                         out[:, 1].astype(jnp.int32),
                         out[:, 2].astype(jnp.int32),
                         out[:, 3].astype(jnp.int32),
                         out[:, 4] > 0.5)


def _rows_from_codes(x, lx, codes, k, consumes, gap_code: int):
    """One gapped row from the reversed path: column t (t < k) of the walk
    carries x[lx - 1 - (# earlier columns consuming x)] when its state
    consumes x, else a gap; then un-reverse like ``pairwise.traceback``."""
    out_len = codes.shape[0]
    t = jnp.arange(out_len)
    use = (t < k) & consumes
    cnt = jnp.cumsum(use.astype(jnp.int32)) - use
    idx = jnp.clip(lx - 1 - cnt, 0, x.shape[0] - 1)
    rev = jnp.where(use, x[idx], gap_code).astype(jnp.int8)
    return jnp.roll(jnp.flip(rev), k - out_len)


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code",
                                             "block_rows", "interpret"))
def banded_pairs_fused(a, b, lens, sub, *, gap_open, gap_extend, band,
                       gap_code: int = 5, block_rows: int = 128,
                       interpret: bool | None = None):
    """Fused banded score+traceback for a coalesced pairs bucket.

    a: (B, n) int8, b: (B, m) int8, lens: (B, 2) i32. Returns
    (score (B,) f32, a_row (B, n+m) int8, b_row (B, n+m) int8,
    aln_len (B,) i32, ok (B,) bool) — the BatchAlignment field order.
    """
    n, m = a.shape[1], b.shape[1]
    out_len = n + m
    ap, prof, margin, pad, br = _operands(a, b, sub, band, block_rows)
    lens = lens.astype(jnp.int32)
    out, codes = banded_fused_kernel(
        ap, prof, lens, margin, gap_open=float(gap_open),
        gap_extend=float(gap_extend), band=band, pad=pad, out_len=out_len,
        block_rows=br, interpret=interpret)
    out = out[:, 0, :]
    k = out[:, 4].astype(jnp.int32)
    codes = codes.reshape(codes.shape[0], -1)[:, :out_len].astype(jnp.int32)
    is_m = codes == M_ST
    rows = jax.vmap(_rows_from_codes, in_axes=(0, 0, 0, 0, 0, None))
    a_row = rows(a, lens[:, 0], codes, k, is_m | (codes == IX_ST), gap_code)
    b_row = rows(b, lens[:, 1], codes, k, is_m | (codes == IY_ST), gap_code)
    return (out[:, 0], a_row, b_row, k, out[:, 5] > 0.5)

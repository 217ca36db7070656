"""jit'd public wrappers for the SW/Gotoh kernels: the forward (padding,
the per-target substitution profile; a drop-in replacement for
pairwise.gotoh_forward in batch form) and the traceback over its
directions (a drop-in replacement for a vmapped pairwise.traceback)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.pairwise import ForwardResult, rows_from_codes
from .. import LANES, round_up
from .sw_kernel import PAIRS_PER_PROGRAM, gotoh_forward_kernel, group_size
from .traceback_kernel import sw_traceback_kernel

__all__ = ["PAIRS_PER_PROGRAM", "gotoh_forward_pallas", "sw_traceback_pallas"]

# int32 direction staging one program may hold in VMEM (G pairs x block
# rows x Mp); block_rows shrinks to fit it at long target widths
_STAGING_BYTES = 16 << 20


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend", "local",
                                             "block_rows", "interpret"))
def gotoh_forward_pallas(a, b, lens, sub, *, gap_open, gap_extend,
                         local=False, block_rows: int = 128,
                         interpret: bool | None = None) -> ForwardResult:
    """Batched forward with the kernel, as a batched ForwardResult.

    a: (B, n) int8, b: (B, m) int8, lens: (B, 2) i32 [[la, lb], ...].
    ``dirs`` holds DP rows 1..n (row 0 is closed-form) as a padded
    (B, n_pad, Mp) int8 buffer, Mp = round_up(m+1, 128);
    ``sw_traceback_pallas`` consumes it as is. ``interpret=None``
    resolves platform-aware (compiled on TPU) inside the shared
    ``kernels.kernel_call`` wrapper.
    """
    B, n = a.shape
    m = b.shape[1]
    Mp = round_up(m + 1, LANES)
    # row blocks: a multiple of 32 (the int8 tile height) whose staging
    # fits _STAGING_BYTES, or one block covering the whole (8-row padded)
    # query
    fit = max(32, _STAGING_BYTES // (group_size(B) * Mp * 4) // 32 * 32)
    br = min(round_up(block_rows, 32), fit, round_up(max(n, 1), 8))
    a = jnp.pad(a.astype(jnp.int32), ((0, 0), (0, (-n) % br)))
    sub = sub.astype(jnp.float32)
    # prof[p, c, j] = sub[c, b[p, j-1]]; column 0 and the lane padding are 0
    prof = jnp.transpose(sub[:, b.astype(jnp.int32)], (1, 0, 2))
    prof = jnp.pad(prof, ((0, 0), (0, 0), (1, Mp - m - 1)))
    dirs, out = gotoh_forward_kernel(
        a, prof, lens.astype(jnp.int32), gap_open=float(gap_open),
        gap_extend=float(gap_extend), local=local, block_rows=br,
        interpret=interpret)
    out = out[:, 0, :]
    return ForwardResult(dirs, out[:, 0], out[:, 1].astype(jnp.int32),
                         out[:, 2].astype(jnp.int32),
                         out[:, 3].astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("gap_code", "interpret"))
def sw_traceback_pallas(a, b, fwd: ForwardResult, *, gap_code: int,
                        interpret: bool | None = None):
    """Batched traceback with the kernel: vmapped ``pairwise.traceback``'s
    (a_row, b_row, k), byte for byte.

    a: (B, n) int8, b: (B, m) int8, ``fwd`` the batched result of
    ``gotoh_forward_pallas`` on them. The kernel walks the directions and
    returns one state code per column; the rows are built from the codes
    here (``core.pairwise.rows_from_codes``).
    """
    starts = jnp.stack([fwd.start_i, fwd.start_j, fwd.start_state], axis=1)
    codes, k = sw_traceback_kernel(fwd.dirs, starts,
                                   out_len=a.shape[1] + b.shape[1],
                                   interpret=interpret)
    a_row, b_row = rows_from_codes(a, b, fwd.start_i, fwd.start_j, codes, k,
                                   gap_code)
    return a_row, b_row, k

"""Pallas TPU kernel: pairwise match/valid counts via on-the-fly one-hot MXU.

The NJ distance matrix needs, for every row pair (i, j) of the MSA, the
number of equal non-gap columns (match) and both-non-gap columns (valid).
Done naively this is an O(N^2 L) byte-compare loop; expressed as
one-hot(X) @ one-hot(X)^T it is MXU work — but materializing the one-hot in
HBM would multiply sequence bytes by 4*|alphabet|. This kernel builds the
one-hot tiles in VMEM from the int8 tiles at use time, so HBM traffic stays
int8 while the MXU does the counting.

Profile packing (``pack``): the default ``"int8"`` feeds the one-hot tiles
as int8 operands of an int32-accumulating dot (the MXU's integer path); the
legacy ``"f32"`` path feeds f32. Counts are exact small integers either
way, so the f32 results the ops layer returns are bit-identical between
packings.

Tiling: grid (N/BN, N/BN, L/BL); A-tile (BN, BL) int8 and B-tile (BN, BL)
int8. The match count is the sum over characters c of (A == c) @ (B == c)^T
— C two-dimensional (BN, BL) x (BL, BN) dots, each one-hot tile a compare
away from the int8 tile — accumulated with the valid count into two
(BN, BN) outputs over the L/BL reduction dimension (last grid dim =
sequential on TPU, accumulation in the output block is the standard Pallas
matmul pattern). MXU dims: BN=128 rows, BL a multiple of 128 lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .. import kernel_call


def _kernel(a_ref, b_ref, match_ref, valid_ref, *, n_chars: int,
            gap_code: int, pack: str):
    lk = pl.program_id(2)
    op_t = jnp.int8 if pack == "int8" else jnp.float32
    acc_t = jnp.int32 if pack == "int8" else jnp.float32

    @pl.when(lk == 0)
    def _():
        match_ref[:, :] = jnp.zeros_like(match_ref)
        valid_ref[:, :] = jnp.zeros_like(valid_ref)

    a = a_ref[:, :].astype(jnp.int32)
    b = b_ref[:, :].astype(jnp.int32)

    def nt_dot(x, y):                 # x @ y.T on the MXU
        return jax.lax.dot_general(x.astype(op_t), y.astype(op_t),
                                   (((1,), (1,)), ((), ())),
                                   preferred_element_type=acc_t)

    valid_ref[:, :] += nt_dot((a != gap_code) & (a < n_chars),
                              (b != gap_code) & (b < n_chars))
    # one-hot match counts as a sum of per-character 2-D dots: the
    # one-hot tile of character c is just (x == c), so no 3-D expansion
    match = None
    for c in range(n_chars):
        if c == gap_code:
            continue
        d = nt_dot(a == c, b == c)
        match = d if match is None else match + d
    match_ref[:, :] += match


def match_valid_kernel(msa_a, msa_b, *, n_chars: int, gap_code: int,
                       bn: int = 128, bl: int = 128, pack: str = "int8",
                       interpret: bool | None = None):
    """msa_a: (N, L) int8, msa_b: (M, L) int8 (pad N/M to bn, L to bl).

    Returns match (N, M) and valid (N, M) — int32 counts under
    ``pack="int8"``, f32 under the legacy ``pack="f32"``.
    """
    N, L = msa_a.shape
    M = msa_b.shape[0]
    assert N % bn == 0 and M % bn == 0 and L % bl == 0, (N, M, L, bn, bl)
    assert pack in ("int8", "f32"), pack
    acc_t = jnp.int32 if pack == "int8" else jnp.float32
    grid = (N // bn, M // bn, L // bl)
    kern = functools.partial(_kernel, n_chars=n_chars, gap_code=gap_code,
                             pack=pack)
    return kernel_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bn, bl), lambda i, j, k: (i, k)),
            pl.BlockSpec((bn, bl), lambda i, j, k: (j, k)),
        ],
        out_specs=[
            pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
            pl.BlockSpec((bn, bn), lambda i, j, k: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((N, M), acc_t),
            jax.ShapeDtypeStruct((N, M), acc_t),
        ],
        interpret=interpret,
    )(msa_a, msa_b)

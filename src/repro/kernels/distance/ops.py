"""jit'd wrapper: pad, call the kernel, crop, and a full distance_matrix."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ...core.distance import jc69_distance
from .. import LANES, round_up
from .distance_kernel import match_valid_kernel


@functools.partial(jax.jit, static_argnames=("n_chars", "gap_code", "bn", "bl",
                                             "pack", "interpret"))
def match_valid_pallas(msa_a, msa_b, *, n_chars: int, gap_code: int,
                       bn: int = 128, bl: int = 128, pack: str = "int8",
                       interpret: bool | None = None):
    """Match/valid counts as f32. ``pack="int8"`` (default) runs the
    kernel with int8 one-hot operands and int32 accumulation — counts are
    exact integers either way, so both packings are bit-identical."""
    N, L = msa_a.shape
    M = msa_b.shape[0]
    # whole (128, 128) tiles: the int8 operand tiles and the int32 count
    # tiles are lane-dense on the TPU; padding rows/columns are gaps
    bn, bl = round_up(bn, LANES), round_up(bl, LANES)
    pn, pm, pl_ = (-N) % bn, (-M) % bn, (-L) % bl
    a = jnp.pad(msa_a, ((0, pn), (0, pl_)), constant_values=gap_code)
    b = jnp.pad(msa_b, ((0, pm), (0, pl_)), constant_values=gap_code)
    match, valid = match_valid_kernel(a, b, n_chars=n_chars, gap_code=gap_code,
                                      bn=bn, bl=bl, pack=pack,
                                      interpret=interpret)
    return (match[:N, :M].astype(jnp.float32),
            valid[:N, :M].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_chars", "gap_code", "correct",
                                             "bn", "bl", "pack", "interpret"))
def distance_matrix_pallas(msa, *, n_chars: int, gap_code: int,
                           correct: bool = True, bn: int = 128, bl: int = 128,
                           pack: str = "int8",
                           interpret: bool | None = None):
    match, valid = match_valid_pallas(msa, msa, n_chars=n_chars,
                                      gap_code=gap_code, bn=bn, bl=bl,
                                      pack=pack, interpret=interpret)
    p = 1.0 - match / jnp.maximum(valid, 1.0)
    p = jnp.where(valid > 0, p, 0.75)
    d = jc69_distance(p) if correct else p
    d = (d + d.T) / 2.0
    return d * (1.0 - jnp.eye(d.shape[0]))

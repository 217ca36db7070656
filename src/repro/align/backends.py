"""The AlignEngine backend protocol and its three implementations.

Every backend is a jit-compatible batched map(1) primitive with one
contract (``BatchAlignment``): align queries ``Q (B, n)`` with lengths
``lens`` against one broadcast target ``b (m,)`` of length ``lb`` and
return gap-padded aligned rows of width ``n + m`` plus per-pair ``ok``
flags (False = the backend's heuristic gave up and the pair needs a
full-DP re-alignment — only the ``banded`` backend ever clears it).

  jnp            the row-scan Gotoh oracle (``core.pairwise``); O(n·m)
                 dirs
  pallas         the ``kernels.sw`` Pallas kernels (compiled on TPU,
                 interpreted elsewhere): the forward, O(n·m) dirs in HBM
                 with the row scores never leaving VMEM, then the
                 traceback kernel walking those dirs from VMEM windows
  banded         diagonal band as a jnp scan, O(n·W) dirs, per-pair
                 overflow flags
  banded-pallas  the same band as a native Pallas kernel
                 (``kernels.banded``): band state resident in VMEM,
                 wavefront rows, in-kernel overflow flags — bit-identical
                 to ``banded`` by construction (both call
                 ``kernels.banded.ref``); the pairs variant fuses
                 score+traceback so no direction matrix reaches HBM

All four are registered in ``BACKENDS`` so the engine, the shard_map
pipeline, and the benchmarks dispatch by name.

Each backend also has a *pairs* variant (``*_align_pairs``,
``PAIR_BACKENDS``) with per-pair targets ``T (B, m)`` instead of one
broadcast ``b`` — the batch-entry contract that lets
``AlignEngine.align_pairs`` merge pre-encoded requests from many callers
(each with its own center) into one jitted call. ``repro.serve.queue``
is the consumer.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..core import pairwise
from ..kernels.banded.ops import banded_forward_pallas, banded_pairs_fused
from ..kernels.sw.ops import gotoh_forward_pallas, sw_traceback_pallas
from . import banded as banded_mod


class BatchAlignment(NamedTuple):
    score: jnp.ndarray      # (B,) f32
    a_row: jnp.ndarray      # (B, n+m) int8 gap-padded aligned queries
    b_row: jnp.ndarray      # (B, n+m) int8 gap-padded aligned target
    aln_len: jnp.ndarray    # (B,) i32 valid leading columns
    ok: jnp.ndarray         # (B,) bool; False = needs full-DP fallback


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "local", "gap_code"))
def jnp_align_batch(Q, lens, b, lb, sub, *, gap_open, gap_extend,
                    local=False, gap_code=5):
    res = pairwise.align_many_to_one(Q, lens, b, lb, sub, gap_open=gap_open,
                                     gap_extend=gap_extend, local=local,
                                     gap_code=gap_code)
    return BatchAlignment(res.score, res.a_row, res.b_row, res.aln_len,
                          jnp.ones(Q.shape[0], jnp.bool_))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "local", "gap_code",
                                             "block_rows", "interpret"))
def pallas_align_batch(Q, lens, b, lb, sub, *, gap_open, gap_extend,
                       local=False, gap_code=5, block_rows=128,
                       interpret=None):
    B, n = Q.shape
    Bm = jnp.broadcast_to(b[None, :], (B, b.shape[0]))
    lens2 = jnp.stack([lens.astype(jnp.int32),
                       jnp.full((B,), lb, jnp.int32)], axis=1)
    fwd = gotoh_forward_pallas(Q, Bm, lens2, sub, gap_open=gap_open,
                               gap_extend=gap_extend, local=local,
                               block_rows=min(block_rows, max(n, 1)),
                               interpret=interpret)
    a_row, b_row, k = sw_traceback_pallas(Q, Bm, fwd, gap_code=gap_code,
                                          interpret=interpret)
    return BatchAlignment(fwd.score, a_row, b_row, k,
                          jnp.ones(B, jnp.bool_))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code"))
def banded_align_batch(Q, lens, b, lb, sub, *, gap_open, gap_extend,
                       band=64, gap_code=5):
    def one(q, lq):
        fwd = banded_mod.banded_forward(q, lq, b, lb, sub, gap_open,
                                        gap_extend, band=band)
        a_row, b_row, k, ok = banded_mod.banded_traceback(
            q, b, fwd, gap_code, band=band)
        return BatchAlignment(fwd.score, a_row, b_row, k, ok)
    return jax.vmap(one)(Q, lens.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "local", "gap_code"))
def jnp_align_pairs(Q, qlens, T, tlens, sub, *, gap_open, gap_extend,
                    local=False, gap_code=5):
    f = lambda q, lq, t, lt: pairwise.align_pair(
        q, lq, t, lt, sub, gap_open=gap_open, gap_extend=gap_extend,
        local=local, gap_code=gap_code)
    res = jax.vmap(f)(Q, qlens.astype(jnp.int32), T, tlens.astype(jnp.int32))
    return BatchAlignment(res.score, res.a_row, res.b_row, res.aln_len,
                          jnp.ones(Q.shape[0], jnp.bool_))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "local", "gap_code",
                                             "block_rows", "interpret"))
def pallas_align_pairs(Q, qlens, T, tlens, sub, *, gap_open, gap_extend,
                       local=False, gap_code=5, block_rows=128,
                       interpret=None):
    # the kernel already takes a (B, m) target batch — the broadcast path
    # above is just this with T = tile(b); per-pair targets come for free
    B, n = Q.shape
    lens2 = jnp.stack([qlens.astype(jnp.int32), tlens.astype(jnp.int32)],
                      axis=1)
    fwd = gotoh_forward_pallas(Q, T, lens2, sub, gap_open=gap_open,
                               gap_extend=gap_extend, local=local,
                               block_rows=min(block_rows, max(n, 1)),
                               interpret=interpret)
    a_row, b_row, k = sw_traceback_pallas(Q, T, fwd, gap_code=gap_code,
                                          interpret=interpret)
    return BatchAlignment(fwd.score, a_row, b_row, k,
                          jnp.ones(B, jnp.bool_))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code"))
def banded_align_pairs(Q, qlens, T, tlens, sub, *, gap_open, gap_extend,
                       band=64, gap_code=5):
    def one(q, lq, t, lt):
        fwd = banded_mod.banded_forward(q, lq, t, lt, sub, gap_open,
                                        gap_extend, band=band)
        a_row, b_row, k, ok = banded_mod.banded_traceback(
            q, t, fwd, gap_code, band=band)
        return BatchAlignment(fwd.score, a_row, b_row, k, ok)
    return jax.vmap(one)(Q, qlens.astype(jnp.int32), T,
                         tlens.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code",
                                             "block_rows", "interpret"))
def banded_pallas_align_batch(Q, lens, b, lb, sub, *, gap_open, gap_extend,
                              band=64, gap_code=5, block_rows=128,
                              interpret=None):
    # Forward runs in the kernel (band in VMEM, O(n·W) dirs to HBM);
    # the jnp traceback then walks those dirs exactly like ``banded``.
    B, n = Q.shape
    Bm = jnp.broadcast_to(b[None, :], (B, b.shape[0]))
    lens2 = jnp.stack([lens.astype(jnp.int32),
                       jnp.full((B,), lb, jnp.int32)], axis=1)
    fwd = banded_forward_pallas(Q, Bm, lens2, sub, gap_open=gap_open,
                                gap_extend=gap_extend, band=band,
                                block_rows=min(block_rows, max(n, 1)),
                                interpret=interpret)
    a_row, b_row, k, ok = jax.vmap(
        lambda a_, b_, f: banded_mod.banded_traceback(a_, b_, f, gap_code,
                                                      band=band))(Q, Bm, fwd)
    return BatchAlignment(fwd.score, a_row, b_row, k, ok)


@functools.partial(jax.jit, static_argnames=("gap_open", "gap_extend",
                                             "band", "gap_code",
                                             "interpret"))
def banded_pallas_align_pairs(Q, qlens, T, tlens, sub, *, gap_open,
                              gap_extend, band=64, gap_code=5,
                              interpret=None):
    # Fully fused: score rows AND the traceback band stay in VMEM for the
    # whole bucket; the per-pair direction matrix never reaches HBM.
    lens2 = jnp.stack([qlens.astype(jnp.int32), tlens.astype(jnp.int32)],
                      axis=1)
    score, a_row, b_row, k, ok = banded_pairs_fused(
        Q, T, lens2, sub, gap_open=gap_open, gap_extend=gap_extend,
        band=band, gap_code=gap_code, interpret=interpret)
    return BatchAlignment(score, a_row, b_row, k, ok)


BACKENDS = {
    "jnp": jnp_align_batch,
    "pallas": pallas_align_batch,
    "banded": banded_align_batch,
    "banded-pallas": banded_pallas_align_batch,
}

PAIR_BACKENDS = {
    "jnp": jnp_align_pairs,
    "pallas": pallas_align_pairs,
    "banded": banded_align_pairs,
    "banded-pallas": banded_pallas_align_pairs,
}


def resolve_backend(name: str) -> str:
    """``auto`` → the compiled kernel on TPU, the jnp oracle elsewhere."""
    if name == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jnp"
    if name not in BACKENDS:
        raise ValueError(f"unknown align backend {name!r}; "
                         f"expected one of {sorted(BACKENDS)} or 'auto'")
    return name

"""High-level MSA driver: HAlign-II's pipeline as host-orchestrated jitted stages.

Pipeline (paper Fig. 3):
  1. pick the center sequence (first, or most-shared-kmers sample heuristic)
  2. map(1): align every sequence to the broadcast center
       - 'sw' / 'plain': Gotoh DP through ``repro.align.AlignEngine``
         (backend-dispatched: jnp scan / Pallas kernel / banded,
         length-bucketed batching)
       - 'kmer': chain k-mer anchors, DP only on inter-anchor segments
         (trie-accelerated path; per-pair fallback through the engine
         when chaining fails, e.g. diverged sequences)
  3. reduce(1): merge insert-space profiles (columnwise max)
  4. map(2): rebuild every row in the merged frame

The distributed version runs the same jitted stages under shard_map with the
center replicated: ``repro.dist.mapreduce.distributed_center_star`` is the
jitted pipeline, ``repro.dist.mapreduce.msa_over_mesh`` the host driver, and
``repro.launch.msa_run --dist`` the CLI entry. This module is the
single-host reference and the building block both reuse.
"""
from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import alphabet as ab
from . import centerstar, kmer_index, pairwise
from ..obs import metrics as _obs
from ..obs import trace as _trace

_M_CHAIN = _obs.counter(
    "repro_kmer_chain_pairs_total",
    "k-mer chained pairs in map(1), by outcome (failed pairs take the "
    "full DP)", ("outcome",))


@dataclasses.dataclass(frozen=True)
class MSAConfig:
    alphabet: str = "dna"            # dna | rna | protein
    method: str = "kmer"             # kmer | plain | sw
    match: int = 2
    mismatch: int = -1
    gap_open: int = 3
    gap_extend: int = 1
    k: int = 11                      # k-mer width (trie depth equivalent)
    stride: int = 1                  # query probe stride
    max_anchors: int = 256
    max_seg: int = 64                # inter-anchor DP budget
    center: str = "first"            # first | sampled
    local: bool = False              # Smith-Waterman local stage-1 alignment
    backend: str = "auto"            # map(1) DP: auto | jnp | pallas |
                                     #   banded | banded-pallas
    band: int = 64                   # band width for the banded backends
    bucket: bool = True              # length-bucketed batching in map(1)

    def alpha(self) -> ab.Alphabet:
        return {"dna": ab.DNA, "rna": ab.RNA, "protein": ab.PROTEIN}[self.alphabet]

    def matrix(self) -> jnp.ndarray:
        if self.alphabet == "protein":
            return ab.blosum62().astype(jnp.float32)
        return ab.dna_matrix(self.match, self.mismatch).astype(jnp.float32)

    def engine(self, *, bucket: Optional[bool] = None):
        """The configured ``repro.align.AlignEngine`` for this MSA run."""
        from ..align import AlignEngine
        return AlignEngine(self.matrix(), gap_open=self.gap_open,
                           gap_extend=self.gap_extend,
                           gap_code=self.alpha().gap_code,
                           backend=self.backend, band=self.band,
                           local=self.local,
                           bucket=self.bucket if bucket is None else bucket)


class MSAResult(NamedTuple):
    msa: np.ndarray          # (N, L) int8 aligned rows, original order
    center_idx: int
    n_fallback: int          # pairs that fell back to full DP (kmer chain
                             # failure or banded-DP band overflow)
    width: int
    center_mode: str = "first"   # effective center selection ('first'|'sampled')


# ---------------------------------------------------------------- k-mer path

@functools.partial(jax.jit, static_argnames=("k", "stride", "max_anchors",
                                             "max_seg", "gap_open",
                                             "gap_extend", "gap_code"))
def kmer_align_batch(Q, lens, center, lc, table, sub, *, k, stride,
                     max_anchors, max_seg, gap_open, gap_extend, gap_code):
    """Anchor-chained alignment of a batch of queries against the center.

    Returns (a_rows, b_rows) in a fixed assembly buffer plus per-pair ok flags.
    Dead (gap,gap) columns are interior padding, ignored downstream.
    """
    A = max_anchors
    blk = 2 * max_seg
    kbuf = (A + 1) * blk + A * k + blk

    def one(q, lq):
        anch = kmer_index.chain_anchors(q, lq, table, lc, k=k, stride=stride,
                                        max_anchors=A, max_seg=max_seg)
        qs, qlen, cs, clen = kmer_index.segment_bounds(anch, lq, lc, k=k)

        def get_seg(seq, start, length, width):
            # pad before slicing so end-of-sequence segments stay aligned
            seqp = jnp.concatenate(
                [seq, jnp.full((width,), gap_code, seq.dtype)])
            s = jax.lax.dynamic_slice(seqp, (jnp.clip(start, 0, seq.shape[0]),),
                                      (width,))
            mask = jnp.arange(width) < length
            return jnp.where(mask, s, gap_code).astype(jnp.int8)

        seg_q = jax.vmap(lambda s, l: get_seg(q, s, l, max_seg))(qs, qlen)
        seg_c = jax.vmap(lambda s, l: get_seg(center, s, l, max_seg))(cs, clen)

        aln = jax.vmap(lambda a, la, b, lb: pairwise.align_pair(
            a, la, b, lb, sub, gap_open=gap_open, gap_extend=gap_extend,
            local=False, gap_code=gap_code))(seg_q, qlen, seg_c, clen)

        # anchor blocks: exact k-length matches, padded to blk
        def anchor_block(aq, ac):
            qa = get_seg(q, aq, jnp.int32(k), blk)
            ca = get_seg(center, ac, jnp.int32(k), blk)
            return qa, ca
        anch_a, anch_b = jax.vmap(anchor_block)(anch.q_pos, anch.c_pos)
        anch_live = jnp.arange(A) < anch.count
        anch_len = jnp.where(anch_live, k, 0)

        # interleave: seg0, anch0, seg1, anch1, ..., seg_A
        blocks_a = jnp.zeros((2 * A + 1, blk), jnp.int8)
        blocks_b = jnp.zeros((2 * A + 1, blk), jnp.int8)
        blocks_a = blocks_a.at[0::2].set(aln.a_row[:, :blk])
        blocks_b = blocks_b.at[0::2].set(aln.b_row[:, :blk])
        blocks_a = blocks_a.at[1::2].set(anch_a)
        blocks_b = blocks_b.at[1::2].set(anch_b)
        seg_live = jnp.arange(A + 1) <= anch.count
        seg_len = jnp.where(seg_live, aln.aln_len, 0)
        lens_u = jnp.zeros((2 * A + 1,), jnp.int32)
        lens_u = lens_u.at[0::2].set(seg_len)
        lens_u = lens_u.at[1::2].set(anch_len)

        buf_a = jnp.full((kbuf,), gap_code, jnp.int8)
        buf_b = jnp.full((kbuf,), gap_code, jnp.int8)

        def put(u, carry):
            ba, bb, off = carry
            ba = jax.lax.dynamic_update_slice(ba, blocks_a[u], (off,))
            bb = jax.lax.dynamic_update_slice(bb, blocks_b[u], (off,))
            return ba, bb, off + lens_u[u]
        buf_a, buf_b, _ = jax.lax.fori_loop(0, 2 * A + 1, put, (buf_a, buf_b, jnp.int32(0)))
        return buf_a, buf_b, anch.ok

    return jax.vmap(one)(Q, lens)


# ------------------------------------------------------------------- driver

def encode_for_msa(seqs: Sequence[str], cfg: MSAConfig):
    """Normalize (RNA U->T) and encode a string batch for ``cfg``'s alphabet.

    Shared by this host driver and ``repro.dist.mapreduce.msa_over_mesh`` so
    the two pipelines can never diverge on preprocessing.
    """
    return ab.encode_batch(
        [s.replace("U", "T").replace("u", "t")
         if cfg.alphabet == "rna" else s for s in seqs], cfg.alpha())


def map1_align_to_center(Q, qlens, center, lc, cfg: MSAConfig, engine=None):
    """The map(1) stage on its own: a query batch against a frozen center.

    Returns ``(a_rows, b_rows, n_fallback)`` — the per-pair aligned rows
    every downstream consumer (``assemble_center_star`` here, the
    incremental add-to-MSA path in ``repro.serve.incremental``) feeds to
    the reduce(1)/map(2) assembly. Kept separate from ``center_star_msa``
    so incremental alignment of *new* sequences runs the exact same code
    path as a full realign — the bit-identity the serve tests pin depends
    on it.

    Two spans split the stage: ``map1.chain`` (k-mer method only: the
    center index, the chaining and the host read of the per-pair ``ok``
    flags, which waits for the chaining's device work) and ``map1.dp``
    (the full-DP work, ended on the rows only when the span is recorded).
    """
    gap = cfg.alpha().gap_code
    sub = cfg.matrix()
    engine = cfg.engine() if engine is None else engine
    if cfg.method == "kmer":
        with _trace.span("map1.chain", n=int(Q.shape[0])):
            table = kmer_index.build_center_index(center, lc, k=cfg.k)
            a_rows, b_rows, ok = kmer_align_batch(
                Q, qlens, center, lc, table, sub, k=cfg.k, stride=cfg.stride,
                max_anchors=cfg.max_anchors, max_seg=cfg.max_seg,
                gap_open=cfg.gap_open, gap_extend=cfg.gap_extend,
                gap_code=gap)
            ok = np.asarray(ok)
        n_kept = int(ok.sum())
        _M_CHAIN.labels(outcome="kept").inc(n_kept)
        _M_CHAIN.labels(outcome="failed").inc(len(ok) - n_kept)
        # chain failures re-align through the engine; rows stay on device
        with _trace.span("map1.dp", n=len(ok) - n_kept) as sp:
            a_rows, b_rows, n_fallback = engine.realign_failed(
                Q, qlens, center, lc, a_rows, b_rows, ok)
            if sp is not None:
                jax.block_until_ready((a_rows, b_rows))
        return a_rows, b_rows, n_fallback
    with _trace.span("map1.dp", n=int(Q.shape[0])) as sp:
        res = engine.align_to_center(Q, qlens, center, lc)
        if sp is not None:
            jax.block_until_ready((res.a_row, res.b_row))
    return res.a_row, res.b_row, res.n_fallback


def assemble_center_star(a_rows, b_rows, center, lc, *, others, cidx: int,
                         n_total: int, gap: int):
    """reduce(1) + map(2): merge insert profiles, rebuild rows, place center.

    ``a_rows``/``b_rows`` are the map(1) pair alignments for the ``others``
    rows (any width — dead (gap, gap) columns are ignored). Returns
    ``(msa, width)`` with rows in original order. Shared by
    ``center_star_msa`` and the coalesced request path in
    ``repro.serve.service`` (which obtains the pair alignments through
    ``AlignEngine.align_pairs`` batched across callers).
    """
    num_slots = int(center.shape[0]) + 1
    g = centerstar.gap_profiles(a_rows, b_rows,
                                gap_code=gap, num_slots=num_slots)
    G = centerstar.merge_profiles(g)
    width = centerstar.msa_width(G, int(lc))

    rows = centerstar.build_rows(a_rows, b_rows, G,
                                 gap_code=gap, out_len=width)
    crow = centerstar.center_msa_row(center, lc, G, gap_code=gap,
                                     out_len=width)

    msa = np.full((n_total, width), gap, np.int8)
    msa[np.asarray(others)] = np.asarray(rows)
    msa[cidx] = np.asarray(crow)
    return msa, width


def center_star_msa(seqs: Sequence[str] | np.ndarray,
                    cfg: MSAConfig,
                    lens: Optional[np.ndarray] = None) -> MSAResult:
    alpha = cfg.alpha()
    gap = alpha.gap_code
    if isinstance(seqs, (list, tuple)):
        with _trace.span("encode", n=len(seqs)):
            S, lens = encode_for_msa(seqs, cfg)
    else:
        S = jnp.asarray(seqs)
        lens = jnp.asarray(lens)
    N, Lmax = S.shape
    if N < 2:
        # center selection never runs; the effective mode is trivially first
        return MSAResult(np.asarray(S), 0, 0, Lmax, "first")

    with _trace.span("center", n=int(N), mode=cfg.center):
        cidx, center_mode = _select_center(S, lens, cfg)
        center = S[cidx]
        lc = lens[cidx]
        others = np.array([i for i in range(N) if i != cidx])
        Q, qlens = S[jnp.asarray(others)], lens[jnp.asarray(others)]

    with _trace.span("map1", n=int(N) - 1, method=cfg.method,
                     backend=cfg.backend) as sp:
        a_rows, b_rows, n_fallback = map1_align_to_center(
            Q, qlens, center, lc, cfg)
        if sp is not None:
            # async dispatch would otherwise bill the DP to "assemble"
            jax.block_until_ready((a_rows, b_rows))
    with _trace.span("assemble", n=int(N)):
        msa, width = assemble_center_star(a_rows, b_rows, center, lc,
                                          others=others, cidx=int(cidx),
                                          n_total=N, gap=gap)
    return MSAResult(msa, int(cidx), n_fallback, width, center_mode)


def _select_center(S, lens, cfg: MSAConfig) -> tuple[int, str]:
    """Pick the center row; returns (index, effective mode).

    ``center='sampled'`` needs the k-mer index, which only exists for
    nucleotide alphabets — for proteins the request silently downgraded
    before; now it warns and reports ``center_mode='first'`` in MSAResult.
    """
    if cfg.center == "first" or S.shape[0] <= 2:
        return 0, "first"
    if cfg.alphabet == "protein":
        warnings.warn(
            "center='sampled' is unsupported for protein alphabets (no "
            "k-mer index); falling back to center='first'", stacklevel=2)
        return 0, "first"
    # 'sampled': index sequence 0, pick the sequence sharing the most k-mers —
    # the paper's "contains the most segments among all sequences" heuristic.
    table = kmer_index.build_center_index(S[0], lens[0], k=cfg.k)

    @jax.jit
    def hits(q, lq):
        codes = kmer_index.kmer_codes(q, lq, cfg.k)
        cand = table[jnp.clip(codes, 0), 0]          # first occurrence column
        return jnp.sum((codes >= 0) & (cand != kmer_index.EMPTY))
    h = jax.vmap(hits)(S, lens)
    return int(jnp.argmax(h)), "sampled"


def decode_msa(msa: np.ndarray, cfg: MSAConfig) -> list[str]:
    alpha = cfg.alpha()
    return [alpha.decode(r) for r in np.asarray(msa)]

"""Plain global alignment with affine gaps (Gotoh), the reference of map(1).

Three states, as in the paper's Eq. (1)-(2): M (a residue of each),
X (a query residue against a gap) and Y (a gap against a target
residue). A gap of length L costs ``gap_open + (L - 1) * gap_extend``;
gaps at the ends count; a gap opens only after M.

``best_scores`` gives the optimal score of a batch of pairs in exact
int32 arithmetic, one DP row per scan step. ``align`` also keeps the
direction of every cell and walks them back on the host into two gapped
rows; run in a narrower dtype (the control) it shows what a loss of
precision does to the alignments. ``score_rows`` scores two gapped rows
under the same model. Nothing here imports the program under test.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CODES = {"A": 0, "C": 1, "G": 2, "T": 3, "U": 3}
NEG = -(10 ** 8)


def encode(seqs, width: int) -> tuple[np.ndarray, np.ndarray]:
    """Codes 0-3 for ACGT (U as T), 4 for anything else and for padding."""
    lut = np.full(256, 4, np.int8)
    for c, v in CODES.items():
        lut[ord(c)] = v
    out = np.full((len(seqs), width), 4, np.int8)
    lens = np.zeros(len(seqs), np.int32)
    for i, s in enumerate(seqs):
        out[i, :len(s)] = lut[np.frombuffer(s.encode(), np.uint8)]
        lens[i] = len(s)
    return out, lens


def _rows(a, la, b, lb, *, match, mismatch, gap_open, gap_extend, dtype,
          keep_dirs):
    """Row scan over the queries a: (P, n) against targets b: (P, m).
    Returns the (P, 3) values of M, X, Y at (la, lb), and with
    ``keep_dirs`` the (n, P, m + 1) direction bytes of DP rows 1..n:
    bits 0-1 the state M came from, bit 2 X extended, bit 3 Y extended."""
    P, m = b.shape
    neg = jnp.asarray(NEG, dtype)
    go = jnp.asarray(gap_open, dtype)
    ge = jnp.asarray(gap_extend, dtype)
    j = jnp.arange(m + 1)
    jf = j.astype(dtype)
    lb = lb.astype(jnp.int32)[:, None]
    m_row = jnp.broadcast_to(jnp.where(j == 0, jnp.asarray(0, dtype), neg),
                             (P, m + 1))
    x_row = jnp.full((P, m + 1), neg)
    y_row = jnp.broadcast_to(jnp.where(j >= 1, -go - (jf - 1) * ge, neg),
                             (P, m + 1))
    pad = jnp.full((P, 1), neg)

    def at_lb(row):
        return jnp.take_along_axis(row, lb, 1)[:, 0]

    final = jnp.stack([at_lb(m_row), at_lb(x_row), at_lb(y_row)], 1)
    final = jnp.where((la == 0)[:, None], final, neg)

    def step(carry, ai_i):
        m_prev, x_prev, y_prev, final = carry
        ai, i = ai_i
        h_prev = jnp.maximum(m_prev, jnp.maximum(x_prev, y_prev))
        same = jnp.where(ai[:, None] == b, match, mismatch)
        known = (ai[:, None] < 4) & (b < 4)
        s = jnp.where(known, same, 0).astype(dtype)
        m_new = jnp.concatenate([pad, h_prev[:, :-1] + s], axis=1)
        x_open = m_prev - go
        x_ext = x_prev - ge
        x_new = jnp.maximum(x_open, x_ext)
        # Y[j] = max over k < j of M[k] - go - (j - 1 - k) * ge
        run = jax.lax.cummax(m_new + jf * ge, axis=1)
        y_new = jnp.concatenate([pad, run[:, :-1] - go - (jf[1:] - 1) * ge],
                                axis=1)
        hit = (i + 1 == la)[:, None]
        final = jnp.where(hit, jnp.stack(
            [at_lb(m_new), at_lb(x_new), at_lb(y_new)], 1), final)
        out = None
        if keep_dirs:
            src = jnp.where(m_prev >= h_prev, 0,
                            jnp.where(x_prev >= h_prev, 1, 2))
            src_diag = jnp.concatenate(
                [jnp.zeros((P, 1), src.dtype), src[:, :-1]], axis=1)
            y_ext = (jnp.concatenate([pad, y_new[:, :-1]], 1) - ge
                     > jnp.concatenate([pad, m_new[:, :-1]], 1) - go)
            out = (src_diag | ((x_ext > x_open).astype(src.dtype) << 2)
                   | (y_ext.astype(src.dtype) << 3)).astype(jnp.int8)
        return (m_new, x_new, y_new, final), out

    rows = jnp.arange(a.shape[1], dtype=jnp.int32)
    (_, _, _, final), dirs = jax.lax.scan(
        step, (m_row, x_row, y_row, final), (a.T, rows))
    return final, dirs


@functools.partial(jax.jit, static_argnames=("match", "mismatch", "gap_open",
                                             "gap_extend"))
def best_scores(a, la, b, lb, *, match, mismatch, gap_open, gap_extend):
    """Optimal global affine-gap scores of the pairs (a[p], b[p]), int32."""
    final, _ = _rows(a, la, b, lb, match=match, mismatch=mismatch,
                     gap_open=gap_open, gap_extend=gap_extend,
                     dtype=jnp.int32, keep_dirs=False)
    return jnp.max(final, axis=1)


@functools.partial(jax.jit, static_argnames=("match", "mismatch", "gap_open",
                                             "gap_extend", "dtype"))
def _forward(a, la, b, lb, *, match, mismatch, gap_open, gap_extend, dtype):
    return _rows(a, la, b, lb, match=match, mismatch=mismatch,
                 gap_open=gap_open, gap_extend=gap_extend,
                 dtype=jnp.dtype(dtype), keep_dirs=True)


def align(qs, ts, *, match, mismatch, gap_open, gap_extend,
          dtype: str = "int32") -> list[tuple[str, str]]:
    """Gapped rows (query, target) of each pair, the DP run in ``dtype``."""
    a, la = encode(qs, max(len(q) for q in qs))
    b, lb = encode(ts, max(len(t) for t in ts))
    final, dirs = _forward(jnp.asarray(a), jnp.asarray(la), jnp.asarray(b),
                           jnp.asarray(lb), match=match, mismatch=mismatch,
                           gap_open=gap_open, gap_extend=gap_extend,
                           dtype=dtype)
    ends = np.argmax(np.asarray(final, np.float64), axis=1)
    return [walk(np.asarray(dirs[:len(q), p, :len(t) + 1]), q, t,
                 int(ends[p]))
            for p, (q, t) in enumerate(zip(qs, ts))]


def walk(d, q: str, t: str, state: int) -> tuple[str, str]:
    """Traceback from (len q, len t) in ``state``; ``d`` holds rows 1..n."""
    i, j = len(q), len(t)
    ra, rb = [], []
    while i > 0 or j > 0:
        if i == 0 or (j > 0 and state == 2):
            ra.append("-")
            rb.append(t[j - 1])
            state = 2 if i > 0 and (int(d[i - 1, j]) >> 3) & 1 else 0
            j -= 1
        elif j == 0 or state == 1:
            ra.append(q[i - 1])
            rb.append("-")
            state = 1 if (int(d[i - 1, j]) >> 2) & 1 else 0
            i -= 1
        else:
            ra.append(q[i - 1])
            rb.append(t[j - 1])
            state = int(d[i - 1, j]) & 3
            i, j = i - 1, j - 1
    return "".join(reversed(ra)), "".join(reversed(rb))


def score_rows(row_q: str, row_t: str, *, match, mismatch, gap_open,
               gap_extend) -> int:
    """Exact score of one pairwise alignment given as two gapped rows;
    columns where both rows hold a gap are skipped."""
    score = 0
    prev = None                      # 'x', 'y' or None (after M or at start)
    for cq, ct in zip(row_q, row_t):
        gq, gt = cq == "-", ct == "-"
        if gq and gt:
            continue
        if gt:
            score -= gap_extend if prev == "x" else gap_open
            prev = "x"
        elif gq:
            score -= gap_extend if prev == "y" else gap_open
            prev = "y"
        else:
            vq, vt = CODES.get(cq, 4), CODES.get(ct, 4)
            if vq < 4 and vt < 4:
                score += match if vq == vt else mismatch
            prev = None
    return score

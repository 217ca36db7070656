"""The comparisons that decide ``correct``, on what the timed path wrote.

An aligned family is checked on three layers:

* the assembled MSA: every row degaps to its input sequence, rows keep
  their names and order, all rows have one width, and no column is gaps
  only (``rows_bad``, ``dead_cols``; exact, limit 0);
* map(1): the pairwise alignment of a sampled sequence with the center,
  read back from the MSA by dropping the columns where both rows hold a
  gap, scores exactly what the reference's optimal alignment scores
  (``pair_score_gap``: the largest difference over the sample; exact,
  limit 0; ties between optimal alignments do not matter);
* the tree: its path lengths against those of the reference's
  neighbour-joining tree of the alignment's JC69 distances, the worst
  leaf's summed gap over its summed reference path lengths
  (``tree_nj_gap``; limit from the readings in PERF.md).
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from reference import gotoh, nj, tree_fit


class Check(NamedTuple):
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return bool(np.isfinite(self.value)) and self.value <= self.limit


def read_fasta(path) -> tuple[list[str], list[str]]:
    names, seqs, chunks = [], [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line.startswith(">"):
                if names:
                    seqs.append("".join(chunks))
                names.append(line[1:].split()[0])
                chunks = []
            elif line:
                chunks.append(line)
    if names:
        seqs.append("".join(chunks))
    return names, seqs


def norm(seq: str) -> str:
    return seq.upper().replace("U", "T")


def rows_bad(names, rows, exp_names, exp_seqs) -> int:
    """Rows that are missing, renamed, reordered, ragged or do not degap
    to their input; a wholly wrong answer counts every row."""
    if len(rows) != len(exp_seqs) or list(names) != list(exp_names):
        return len(exp_seqs)
    width = len(rows[0]) if rows else 0
    return sum(1 for r, s in zip(rows, exp_seqs)
               if len(r) != width or norm(r.replace("-", "")) != norm(s))


def dead_cols(rows) -> int:
    if not rows or len({len(r) for r in rows}) != 1:
        return 0
    arr = np.frombuffer("".join(rows).encode(), np.uint8).reshape(
        len(rows), -1)
    return int(np.all(arr == ord("-"), axis=0).sum())


def project(row_q: str, row_c: str) -> tuple[str, str]:
    keep = [k for k, (a, b) in enumerate(zip(row_q, row_c))
            if a != "-" or b != "-"]
    return ("".join(row_q[k] for k in keep), "".join(row_c[k] for k in keep))


def pair_score_gap(pairs, scoring: dict, width: int) -> float:
    """``pairs``: (query, center, query row, center row) tuples. The
    largest |optimal score - score of the rows| over them."""
    if not pairs:
        return float("nan")
    q, lq = gotoh.encode([p[0] for p in pairs], width)
    c, lc = gotoh.encode([p[1] for p in pairs], width)
    best = np.asarray(gotoh.best_scores(
        jnp.asarray(q), jnp.asarray(lq), jnp.asarray(c), jnp.asarray(lc),
        **scoring))
    got = np.array([gotoh.score_rows(*project(p[2], p[3]), **scoring)
                    for p in pairs])
    return float(np.max(np.abs(best.astype(np.int64) - got)))


def reference_paths(rows) -> np.ndarray:
    """Path lengths of the reference tree of the alignment ``rows``."""
    return nj.patristic(tree_fit.jc69(rows))


def tree_nj_gap(newick: str, names, ref_paths: np.ndarray) -> float:
    """The worst leaf's summed |tree path - reference path| over its
    summed reference path lengths; a tree that does not parse, or whose
    leaves are not the alignment's rows, is infinitely far."""
    try:
        tree_d = tree_fit.patristic(newick, list(names))
    except (ValueError, KeyError, IndexError):
        return float("inf")
    gap = np.abs(tree_d - ref_paths).sum(1)
    return float(np.max(gap / np.maximum(ref_paths.sum(1), 1e-12)))

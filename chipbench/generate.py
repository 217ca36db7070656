"""Inputs of every run, made from ``--seed`` alone.

One general generator reads a configuration (the deployment's shapes:
sequence count, length, divergence) and a traffic mix (how many families
a closed loop sends in turn, and where their histories come from).
Neither file holds code, so a new cell is new data.

Families follow the model of the paper's simulated sets (a random
ancestor evolved along a random binary tree with JC69-like substitutions
and Poisson indels of mean length 2; the same model and parameters as
``phi_dna`` / ``phi_rna``), written here again so that a later change to
the program's simulator cannot change the yardstick. Every leaf is then
cut to the configuration's published length.

The program compiles its assembly, tree and score stages once per MSA
width, and the width follows from the families' evolutionary history.
A traffic mix that names a ``history_seed`` draws the histories from it,
the same for every run, and the run's own seed relabels the nucleotides
(one of the 24 bijections of ACGT) and orders the families. Alignment
scores, centers, widths and distances do not change under a relabelling
(every mismatch scores alike, and k-mers match as before), so every
seed does the same work, finds the same programs in the cache after the
first run, and still sends its own bytes.
"""
from __future__ import annotations

from typing import List, NamedTuple

import numpy as np

DNA = np.array(list("ACGT"))
# the root is longer than the cut so that no leaf falls short of it
ROOT_MARGIN = 256


class Family(NamedTuple):
    names: List[str]
    seqs: List[str]


def _random_topology(n: int, rng):
    """Sequential random joins with Exp(1) branch lengths."""
    children = np.full((2 * n - 1, 2), -1, np.int64)
    blen = np.zeros((2 * n - 1, 2))
    active = list(range(n))
    nxt = n
    while len(active) > 1:
        i, j = rng.choice(len(active), size=2, replace=False)
        children[nxt] = (active[i], active[j])
        blen[nxt] = rng.exponential(1.0, size=2)
        for x in sorted((i, j), reverse=True):
            active.pop(x)
        active.append(nxt)
        nxt += 1
    return children, blen, nxt - 1


def _evolve(seq, t_sub, t_indel, indel_len_mean, rng):
    n = len(seq)
    mask = rng.random(n) < 1.0 - np.exp(-t_sub)
    seq = seq.copy()
    if mask.any():
        seq[mask] = DNA[rng.integers(0, 4, int(mask.sum()))]
    for _ in range(rng.poisson(t_indel * n)):
        pos = rng.integers(0, max(len(seq), 1))
        ln = max(1, rng.poisson(indel_len_mean))
        if rng.random() < 0.5 and len(seq) > ln + 2:
            seq = np.concatenate([seq[:pos], seq[pos + ln:]])
        else:
            seq = np.concatenate([seq[:pos], DNA[rng.integers(0, 4, ln)],
                                  seq[pos:]])
    return seq


def simulate_family(rng, *, n: int, length: int, branch_sub: float,
                    branch_indel: float, indel_len_mean: float = 2.0
                    ) -> Family:
    """``n`` sequences of exactly ``length`` residues with a shared history."""
    while True:
        children, blen, root = _random_topology(n, rng)
        leaves = {}
        stack = [(root, DNA[rng.integers(0, 4, length + ROOT_MARGIN)])]
        while stack:
            node, seq = stack.pop()
            if children[node, 0] < 0:
                leaves[node] = seq
                continue
            for c, t in zip(children[node], blen[node]):
                stack.append((int(c), _evolve(seq, t * branch_sub,
                                              t * branch_indel,
                                              indel_len_mean, rng)))
        if min(len(s) for s in leaves.values()) >= length:
            break
    return Family([f"s{i}" for i in range(n)],
                  ["".join(leaves[i][:length]) for i in range(n)])


def config_family(rng, cfg: dict) -> Family:
    fam = cfg["family"]
    return simulate_family(rng, n=cfg["n_sequences"],
                           length=fam["length"],
                           branch_sub=fam["branch_sub"],
                           branch_indel=fam["branch_indel"],
                           indel_len_mean=fam["indel_len_mean"])


def relabel(fam: Family, perm) -> Family:
    """The family with nucleotide ``DNA[k]`` written as ``DNA[perm[k]]``."""
    table = str.maketrans("".join(DNA), "".join(DNA[np.asarray(perm)]))
    return Family(fam.names, [s.translate(table) for s in fam.seqs])


def closed_loop_families(cfg: dict, traffic: dict, seed: int
                         ) -> List[Family]:
    """The ``traffic["families"]`` families a closed-loop run sends in
    turn: histories from ``traffic["history_seed"]`` (from ``seed`` where
    the mix names none), nucleotides relabelled and order drawn from
    ``seed``."""
    hist = run_rng(traffic.get("history_seed", seed), "families")
    fams = [config_family(hist, cfg) for _ in range(traffic["families"])]
    rng = run_rng(seed, "relabel")
    return [relabel(fams[k], rng.permutation(4))
            for k in rng.permutation(len(fams))]


def run_rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per input stream of a run; any whole seed
    (negative or wider than 64 bits too) maps to one fixed state."""
    key = [ord(c) for c in stream]
    s = int(seed)
    words = []
    mag = abs(s)
    while True:
        words.append(mag & 0xFFFFFFFF)
        mag >>= 32
        if not mag:
            break
    return np.random.default_rng(key + [int(s < 0)] + words)

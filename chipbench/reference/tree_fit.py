"""Plain reference of the tree stage's inputs and outputs: the JC69
distances of an alignment, and the path lengths of a Newick tree.

``jc69`` counts, for every pair of aligned rows, the columns where both
hold a nucleotide and the mismatches among them (exact integer counts in
float32 matrix products), and applies the JC69 correction in float64.
``patristic`` parses a Newick string and gives the path length between
every pair of leaves.
"""
from __future__ import annotations

import numpy as np

from .gotoh import encode

P_MAX = 0.75 - 1e-6


def jc69(rows: list[str]) -> np.ndarray:
    codes, _ = encode(rows, len(rows[0]))
    onehot = np.stack([(codes == c) for c in range(4)], -1).astype(np.float32)
    flat = onehot.reshape(len(rows), -1)
    match = flat @ flat.T
    known = onehot.sum(-1)
    valid = known @ known.T
    p = 1.0 - match.astype(np.float64) / np.maximum(valid, 1.0)
    p = np.clip(p, 0.0, P_MAX)
    d = -0.75 * np.log1p(-4.0 / 3.0 * p)
    np.fill_diagonal(d, 0.0)
    return d


def parse_newick(text: str):
    """(parent, length, leaf names by node) of a Newick tree."""
    text = text.strip().rstrip(";")
    parent, length, names = [-1], [0.0], {}
    stack, node, i = [0], 0, 0
    while i < len(text):
        c = text[i]
        if c == "(":
            parent.append(stack[-1])
            length.append(0.0)
            node = len(parent) - 1
            stack.append(node)
            i += 1
            continue
        if c == ",":
            stack.pop()
            parent.append(stack[-1])
            length.append(0.0)
            node = len(parent) - 1
            stack.append(node)
            i += 1
            continue
        if c == ")":
            stack.pop()
            node = stack[-1]
            i += 1
            continue
        j = i
        while j < len(text) and text[j] not in "(),":
            j += 1
        label, _, blen = text[i:j].partition(":")
        if label and not text[i - 1] == ")":
            names[node] = label
        if blen:
            length[node] = float(blen)
        i = j
    return np.asarray(parent), np.asarray(length), names


def patristic(newick: str, order: list[str]) -> np.ndarray:
    """Leaf-to-leaf path lengths, rows and columns in ``order``."""
    parent, length, names = parse_newick(newick)
    n_nodes = len(parent)
    depth = np.zeros(n_nodes)
    for v in range(1, n_nodes):           # parents precede children
        depth[v] = depth[parent[v]] + length[v]
    index = {name: k for k, name in enumerate(order)}
    if sorted(names.values()) != sorted(order):
        raise ValueError("tree leaves differ from the alignment's rows")
    under = [[] for _ in range(n_nodes)]
    for v, name in names.items():
        under[v].append(index[name])
    lca_depth = np.zeros((len(order), len(order)))
    for v in range(n_nodes - 1, -1, -1):
        kids = [c for c in np.flatnonzero(parent == v)]
        seen = list(under[v])
        for c in kids:
            if seen and under[c]:
                lca_depth[np.ix_(seen, under[c])] = depth[v]
                lca_depth[np.ix_(under[c], seen)] = depth[v]
            seen += under[c]
        under[v] = seen
    leaf_depth = np.zeros(len(order))
    for v, name in names.items():
        leaf_depth[index[name]] = depth[v]
    d = leaf_depth[:, None] + leaf_depth[None, :] - 2 * lca_depth
    np.fill_diagonal(d, 0.0)
    return d

"""Plain neighbour joining in float64 on the host: the tree the program's
tree stage states it builds, for its tree to be compared with.

Saitou & Nei's method with the Studier-Keppler criterion, as the
configurations state the tree: among the n active nodes, join the pair
(i, j), i < j, of least Q = (n - 2) D_ij - R_i - R_j (R the row sums),
the first in row order on a tie; the new node takes i's place and j
leaves; its branches are D_ij / 2 + (R_i - R_j) / (2 (n - 2)) to i and
the rest of D_ij to j, unclamped; its distances are
(D_i + D_j - D_ij) / 2. The last two nodes meet at a root halfway
between them. The result is the path length between every two leaves.
"""
from __future__ import annotations

import numpy as np


def patristic(D) -> np.ndarray:
    """Leaf-to-leaf path lengths of the neighbour-joining tree of ``D``."""
    D = np.array(D, np.float64)
    n = D.shape[0]
    out = np.zeros((n, n))
    if n < 2:
        return out
    leaves = [np.array([k]) for k in range(n)]
    depth = np.zeros(n)        # each leaf's path length to its node

    def join(i, j, li, lj):
        a, b = leaves[i], leaves[j]
        cross = (depth[a] + li)[:, None] + (depth[b] + lj)[None, :]
        out[np.ix_(a, b)] = cross
        out[np.ix_(b, a)] = cross.T
        depth[a] += li
        depth[b] += lj

    while D.shape[0] > 2:
        m = D.shape[0]
        R = D.sum(axis=1)
        Q = (m - 2) * D - R[:, None] - R[None, :]
        np.fill_diagonal(Q, np.inf)
        i, j = divmod(int(np.argmin(Q)), m)
        dij = D[i, j]
        li = 0.5 * dij + (R[i] - R[j]) / (2.0 * (m - 2))
        join(i, j, li, dij - li)
        row = 0.5 * (D[i] + D[j] - dij)
        D[i, :] = row
        D[:, i] = row
        D[i, i] = 0.0
        leaves[i] = np.concatenate([leaves[i], leaves[j]])
        del leaves[j]
        D = np.delete(np.delete(D, j, axis=0), j, axis=1)
    half = D[0, 1] / 2.0
    join(0, 1, half, half)
    return out

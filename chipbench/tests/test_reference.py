"""The plain references against brute force on small inputs."""
import numpy as np
import pytest

from reference import gotoh, nj, tree_fit

SCORING = dict(match=2, mismatch=-1, gap_open=3, gap_extend=1)


def brute_best(q, t, *, match, mismatch, gap_open, gap_extend):
    """Cell-by-cell Gotoh in Python integers."""
    n, m = len(q), len(t)
    neg = -10 ** 9
    M = [[neg] * (m + 1) for _ in range(n + 1)]
    X = [[neg] * (m + 1) for _ in range(n + 1)]
    Y = [[neg] * (m + 1) for _ in range(n + 1)]
    M[0][0] = 0
    for i in range(1, n + 1):
        X[i][0] = -gap_open - (i - 1) * gap_extend
    for j in range(1, m + 1):
        Y[0][j] = -gap_open - (j - 1) * gap_extend
    for i in range(1, n + 1):
        for j in range(1, m + 1):
            s = match if q[i - 1] == t[j - 1] else mismatch
            M[i][j] = s + max(M[i - 1][j - 1], X[i - 1][j - 1],
                              Y[i - 1][j - 1])
            X[i][j] = max(M[i - 1][j] - gap_open, X[i - 1][j] - gap_extend)
            Y[i][j] = max(M[i][j - 1] - gap_open, Y[i][j - 1] - gap_extend)
    return max(M[n][m], X[n][m], Y[n][m])


def pairs(seed, count, lo=1, hi=14):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        q = "".join(rng.choice(list("ACGT"), rng.integers(lo, hi)))
        t = list(q)
        for _ in range(rng.integers(0, 4)):
            k = rng.integers(0, len(t) + 1)
            if rng.random() < 0.5 and t:
                del t[min(k, len(t) - 1)]
            else:
                t.insert(k, rng.choice(list("ACGT")))
        out.append((q, "".join(t) or "A"))
    return out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_best_scores_match_brute_force(seed):
    ps = pairs(seed, 24)
    width = max(max(len(q), len(t)) for q, t in ps)
    a, la = gotoh.encode([q for q, _ in ps], width)
    b, lb = gotoh.encode([t for _, t in ps], width)
    got = np.asarray(gotoh.best_scores(a, la, b, lb, **SCORING))
    exp = [brute_best(q, t, **SCORING) for q, t in ps]
    assert got.tolist() == exp


def test_align_rows_are_optimal_and_degap():
    ps = pairs(3, 16)
    rows = gotoh.align([q for q, _ in ps], [t for _, t in ps], **SCORING)
    for (q, t), (rq, rt) in zip(ps, rows):
        assert rq.replace("-", "") == q and rt.replace("-", "") == t
        assert gotoh.score_rows(rq, rt, **SCORING) == brute_best(q, t,
                                                                 **SCORING)


def test_score_rows_affine_gaps():
    assert gotoh.score_rows("AC--GT", "ACTTGT", **SCORING) == 8 - 4
    assert gotoh.score_rows("A-C-", "AGCT", **SCORING) == 4 - 6
    assert gotoh.score_rows("A--C", "A--C", **SCORING) == 4   # dead columns


def test_control_precision_loses_optimality():
    """The DP in bfloat16 (the control) returns alignments that score
    below the optimum once scores pass bfloat16's integer range."""
    rng = np.random.default_rng(4)
    base = "".join(rng.choice(list("ACGT"), 600))
    t = base[:200] + base[203:450] + "GATT" + base[450:]
    rows = gotoh.align([base], [t], dtype="bfloat16", **SCORING)
    got = gotoh.score_rows(*rows[0], **SCORING)
    a, la = gotoh.encode([base], 600)
    b, lb = gotoh.encode([t], 601)
    best = int(np.asarray(gotoh.best_scores(a, la, b, lb, **SCORING))[0])
    assert got < best


def test_patristic_and_jc69():
    nwk = "((a:0.1,b:0.2):0.05,(c:0.3,d:0.4)inner:0.0,e:1.0);"
    d = tree_fit.patristic(nwk, ["a", "b", "c", "d", "e"])
    assert d[0, 1] == pytest.approx(0.3)
    assert d[0, 2] == pytest.approx(0.1 + 0.05 + 0.3)
    assert d[2, 3] == pytest.approx(0.7)
    assert d[1, 4] == pytest.approx(0.2 + 0.05 + 1.0)
    rows = ["ACGTACGTAC", "ACGTACGTAA", "AC-TACGTAC"]
    jc = tree_fit.jc69(rows)
    p = 1 / 10
    assert jc[0, 1] == pytest.approx(-0.75 * np.log(1 - 4 / 3 * p))
    assert jc[0, 2] == 0.0
    assert np.allclose(jc, jc.T)


def test_patristic_rejects_wrong_leaves():
    with pytest.raises(ValueError):
        tree_fit.patristic("(a:1,b:1);", ["a", "c"])


def random_tree_paths(rng, n):
    """Path lengths of a random binary tree with positive branches."""
    nodes = [{k: 0.0} for k in range(n)]
    d = np.zeros((n, n))
    while len(nodes) > 1:
        i, j = sorted(rng.choice(len(nodes), 2, replace=False))
        a, b = nodes[i], nodes[j]
        la, lb = rng.uniform(0.01, 0.2, 2)
        for x, dx in a.items():
            for y, dy in b.items():
                d[x, y] = d[y, x] = dx + la + dy + lb
        merged = {x: dx + la for x, dx in a.items()}
        merged.update({y: dy + lb for y, dy in b.items()})
        nodes[i] = merged
        del nodes[j]
    return d


@pytest.mark.parametrize("n", [3, 5, 12])
def test_nj_recovers_additive_tree(n):
    """Neighbour joining is exact on tree distances: its path lengths are
    the distances it was given."""
    d = random_tree_paths(np.random.default_rng(n), n)
    assert np.allclose(nj.patristic(d), d, atol=1e-12)


def test_nj_path_lengths_by_hand():
    # four leaves, ((a,b),(c,d)) with pendant edges 1, 2, 3, 4 and an
    # inner edge of 5: NJ joins a and b first
    d = np.array([[0, 3, 9, 10], [3, 0, 10, 11],
                  [9, 10, 0, 7], [10, 11, 7, 0]], float)
    assert np.allclose(nj.patristic(d), d)
    assert nj.patristic(np.zeros((1, 1))).shape == (1, 1)


def test_tree_nj_gap_sees_a_swap_of_two_leaves():
    import compare
    rng = np.random.default_rng(3)
    names = [f"s{k}" for k in range(8)]
    root = list(rng.choice(list("ACGT"), 400))
    rows = []
    for k in range(8):           # two clades of four, 30 sites apart
        r = list(root)
        for pos in list(range(30 * (k // 4))) + list(
                rng.choice(np.arange(30, 400), 4 + 3 * k, replace=False)):
            r[pos] = "ACGT"[("ACGT".index(r[pos]) + 1) % 4]
        rows.append("".join(r))
    ref = compare.reference_paths(rows)
    nwk = newick_of(ref, names)
    assert compare.tree_nj_gap(nwk, names, ref) < 1e-9
    swapped = nwk.replace("s1:", "\0").replace("s6:", "s1:").replace(
        "\0", "s6:")
    assert compare.tree_nj_gap(swapped, names, ref) > 0.1
    assert compare.tree_nj_gap("(s0:1,s1:1);", names, ref) == float("inf")


def newick_of(paths, names):
    """A Newick string whose leaf-to-leaf path lengths are ``paths``
    (an additive matrix), by joining cherries as neighbour joining does."""
    D = np.array(paths, float)
    labels = list(names)
    while len(labels) > 2:
        m = len(labels)
        R = D.sum(1)
        Q = (m - 2) * D - R[:, None] - R[None, :]
        np.fill_diagonal(Q, np.inf)
        i, j = divmod(int(np.argmin(Q)), m)
        li = 0.5 * D[i, j] + (R[i] - R[j]) / (2 * (m - 2))
        lj = D[i, j] - li
        row = 0.5 * (D[i] + D[j] - D[i, j])
        labels[i] = f"({labels[i]}:{float(li)!r},{labels[j]}:{float(lj)!r})"
        D[i, :] = row
        D[:, i] = row
        D[i, i] = 0.0
        del labels[j]
        D = np.delete(np.delete(D, j, 0), j, 1)
    half = float(D[0, 1] / 2)
    return f"({labels[0]}:{half!r},{labels[1]}:{half!r});"

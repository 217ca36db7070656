"""A run with the timed path broken underneath must come out not correct.

Each case drives the rest of a run (set-up, window, checks) on the CPU
at a small size, skipping only the look for a chip, with one fault
planted in the program: an answer altered where it is produced (a
residue of an aligned row, two leaves of the tree), half of a batch left
out (map(1)'s pairs, or the tree stage's distance rows, filled with the
other half's), and a step that hands back its input unchanged (queries
returned unaligned).
"""
import copy
import io
import json
import time

import numpy as np
import pytest

import harness


def small(cell_name):
    cell = harness.load_cell(cell_name)
    cfg = copy.deepcopy(cell.config)
    # diverged enough that every family holds indels at this length
    cfg["n_sequences"] = 6
    cfg["family"].update(length=160, branch_sub=0.02, branch_indel=0.01)
    cfg["check"]["pairs"] = 5
    return cell._replace(config=cfg)


def run(cell, seconds=0.4):
    out, err = io.StringIO(), io.StringIO()
    res = harness.run_cell(cell, seed=2 ** 31 + 11, seconds=seconds,
                           trace=False, t_start=time.perf_counter(),
                           chips_check=False, out=out, err=err)
    assert json.loads(out.getvalue().splitlines()[-1]) == res
    assert err.getvalue().splitlines()[-1].startswith("check ")
    return res


def alter(a_rows, b_rows, how):
    a = np.array(a_rows)
    b = np.array(b_rows)
    gap = 5
    if how == "altered":
        col = int(np.flatnonzero(a[0] != gap)[0])
        a[0, col] = (a[0, col] + 1) % 4
    elif how == "half":
        h = len(a) // 2
        a[len(a) - h:] = a[:h]
        b[len(b) - h:] = b[:h]
    return a, b


@pytest.fixture
def batch_fault(monkeypatch):
    from repro.core import msa

    def plant(how):
        orig = msa.map1_align_to_center

        def broken(Q, qlens, center, lc, cfg, engine=None):
            if how == "unchanged":
                P = Q.shape[1] + center.shape[0]
                a = np.full((Q.shape[0], P), 5, np.int8)
                a[:, :Q.shape[1]] = np.asarray(Q)
                b = np.full((Q.shape[0], P), 5, np.int8)
                b[:, :center.shape[0]] = np.asarray(center)
                return a, b, 0
            a, b, nf = orig(Q, qlens, center, lc, cfg, engine)
            a, b = alter(a, b, how)
            return a, b, nf
        monkeypatch.setattr(msa, "map1_align_to_center", broken)
    return plant


@pytest.fixture
def tree_fault(monkeypatch):
    import jax.numpy as jnp
    from repro.core import nj

    def plant(how):
        if how == "swapped":
            orig = nj.host_tree

            def broken(tree):
                children, blen, root = orig(tree)
                n = int(tree.n_leaves)
                a, b = 0, n - 1
                swap = np.where(children == a, b,
                                np.where(children == b, a, children))
                return swap, blen, root
            monkeypatch.setattr(nj, "host_tree", broken)
        else:
            orig = nj.neighbor_joining

            def broken(D, size):
                n = D.shape[0]
                keep = (n + 1) // 2
                idx = jnp.concatenate([jnp.arange(keep),
                                       jnp.arange(n - keep)])
                return orig(D[idx][:, idx], size)
            monkeypatch.setattr(nj, "neighbor_joining", broken)
    return plant


def test_batch_run_is_correct_unbroken():
    assert run(small("mtdna-msa"))["correct"] is True


@pytest.mark.parametrize("how", ["altered", "half", "unchanged"])
def test_batch_fault_is_caught(batch_fault, how):
    batch_fault(how)
    res = run(small("mtdna-msa"))
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["checks"].values())


@pytest.mark.parametrize("how", ["swapped", "half"])
def test_tree_fault_is_caught(tree_fault, how):
    tree_fault(how)
    res = run(small("rrna16s-nj"))
    assert res["correct"] is False
    assert res["checks"]["tree_nj_gap"]["value"] > \
        res["checks"]["tree_nj_gap"]["limit"]

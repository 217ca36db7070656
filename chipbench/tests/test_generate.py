"""The closed loop's families: histories fixed by the traffic mix, the
nucleotides and the order drawn from the seed, and the program's MSA
width the same under every relabelling."""
import copy

import numpy as np
import pytest

import generate
import harness

SEEDS = [7, 2 ** 31 + 11, -3]


def small_cell(families=2, **traffic):
    cell = harness.load_cell("mtdna-msa")
    cfg = copy.deepcopy(cell.config)
    cfg["n_sequences"] = 5
    cfg["family"].update(length=120, branch_sub=0.03, branch_indel=0.01)
    return cfg, dict(cell.traffic, families=families, **traffic)


def unlabel(fam, ref):
    """The bijection of ACGT that maps ``fam`` onto ``ref``, applied."""
    pairs = {(a, b) for s, r in zip(fam.seqs, ref.seqs) for a, b in zip(s, r)}
    table = dict(pairs)
    assert len(table) == len(pairs) == 4          # one image per letter
    return [s.translate(str.maketrans(table)) for s in fam.seqs]


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_bytes(seed):
    cfg, traffic = small_cell()
    a = generate.closed_loop_families(cfg, traffic, seed)
    b = generate.closed_loop_families(cfg, traffic, seed)
    assert a == b
    assert all(len(s) == 120 for f in a for s in f.seqs)


def test_seeds_share_histories_and_differ_in_bytes():
    cfg, traffic = small_cell(families=1)
    fams = [generate.closed_loop_families(cfg, traffic, s)[0]
            for s in range(12)]
    assert len({tuple(f.seqs) for f in fams}) > 1
    for f in fams[1:]:
        assert unlabel(f, fams[0]) == fams[0].seqs
    # without a history seed the histories follow the run's seed
    del traffic["history_seed"]
    a, b = (generate.closed_loop_families(cfg, traffic, s)[0]
            for s in (1, 2))
    with pytest.raises(AssertionError):
        unlabel(a, b)


def test_relabelling_keeps_the_msa_width_and_center():
    from repro.core.msa import MSAConfig, center_star_msa
    cfg, traffic = small_cell(families=1)
    fam = generate.closed_loop_families(cfg, traffic, 5)[0]
    mcfg = MSAConfig(method="kmer", alphabet="dna", k=5)
    base = center_star_msa(fam.seqs, mcfg)
    for perm in ([1, 0, 3, 2], [3, 2, 1, 0], [2, 3, 0, 1]):
        other = center_star_msa(generate.relabel(fam, perm).seqs, mcfg)
        assert other.width == base.width
        assert other.center_idx == base.center_idx
        back = np.argsort(perm)
        assert np.array_equal(np.where(other.msa < 4, back[
            np.minimum(other.msa, 3)], other.msa), base.msa)

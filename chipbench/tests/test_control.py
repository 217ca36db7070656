"""The control, at a size a test run holds: the reference in the
program's place with its DP in bfloat16 fails the pair comparison that
the program passes; a tree with two leaves swapped, and one built with
half of the tree stage's batch left out, lie farther from the reference
tree than the program's own."""
import copy

import harness
import control


def test_control_and_tree_fault_readings():
    cell = harness.load_cell("mtdna-msa")
    cfg = copy.deepcopy(cell.config)
    cfg["n_sequences"] = 6
    cfg["family"].update(length=700, branch_sub=0.02, branch_indel=0.01)
    cfg["check"]["pairs"] = 3
    cell = cell._replace(config=cfg, traffic=dict(cell.traffic, families=1))
    out = control.batch_readings(cell, seed=9)
    assert out["control_pair_score_gap"] > 0
    sound = max(out["tree_nj_gap"])
    assert sound < 1e-4
    assert min(out["tree_nj_gap_swapped"]) > 100 * max(sound, 1e-6)
    assert min(out["tree_nj_gap_swapped_nearest"]) > 100 * max(sound, 1e-6)
    assert out["tree_nj_gap_half"] > 100 * max(sound, 1e-6)


def test_swap_leaves():
    assert control.swap_leaves("(s1:0.1,(s2:0.2,s10:0.3):0.4);", "s1",
                               "s10") == "(s10:0.1,(s2:0.2,s1:0.3):0.4);"

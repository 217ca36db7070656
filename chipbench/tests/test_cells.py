"""What ``harness.load_cell`` gives each cell: its chips and the names of
the metrics it reports; and the families of the whole-set mesh cell."""
import copy

import pytest

import generate
import harness

SPANS = ["map1_s.batch", "map1.chain_s.batch", "map1.dp_s.batch",
         "map1.dp_useful.batch", "map1.chain_fail.batch",
         "assemble_s.batch", "write_s.batch", "tree_s.batch"]

CELLS = {
    "mtdna-msa": (1, ["family_s", "setup_s"],
                  ["map1_s.batch", "tree_s.batch", "map1.chain_s.batch",
                   "map1.dp_s.batch", "map1.dp_useful.batch",
                   "map1.chain_fail.batch", "assemble_s.batch",
                   "write_s.batch"]),
    "rrna16s-nj": (1, ["family_s", "setup_s"],
                   ["idle_share.batch", "sw.gcups.batch", "map1_s.batch",
                    "tree_s.batch", "map1.dp_s.batch",
                    "map1.dp_useful.batch", "assemble_s.batch",
                    "write_s.batch"]),
    "mtdna-mesh4": (4, ["family_s", "setup_s"],
                    ["map1_s.batch", "tree_s.batch", "map1.chain_s.batch",
                     "map1.dp_s.batch", "map1.dp_useful.batch",
                     "map1.chain_fail.batch", "assemble_s.batch",
                     "write_s.batch", "map1.place_s.batch"]),
}


@pytest.mark.parametrize("name", sorted(CELLS))
def test_cell_chips_and_metric_names(name):
    chips, e2e, per_layer = CELLS[name]
    cell = harness.load_cell(name)
    assert cell.chips == chips
    assert [m["name"] for m in cell.end_to_end] == e2e
    assert [m["name"] for m in cell.per_layer] == per_layer


def test_mesh_cell_config():
    cfg = harness.load_cell("mtdna-mesh4").config
    assert cfg["n_sequences"] == 672 and cfg["reduced"] == {}
    args = cfg["msa_run"]
    assert args[args.index("--tree") + 1] == "nj"
    assert args[args.index("--mesh") + 1] == "4x1" and "--dist" in args
    assert cfg["check"] == {"pairs": 24, "tree_nj_gap": 0.0005}


def test_mesh_cell_families_at_a_small_count():
    cell = harness.load_cell("mtdna-mesh4")
    cfg = copy.deepcopy(cell.config)
    cfg["n_sequences"] = 6
    fams = generate.closed_loop_families(cfg, cell.traffic, 2 ** 31 + 5)
    assert len(fams) == cell.traffic["families"]
    for fam in fams:
        assert len(fam.seqs) == 6
        assert {len(s) for s in fam.seqs} == {16569}

#!/usr/bin/env python3
"""Readings that set the limits of ``correct``, at a cell's own size.

  python chipbench/control.py --workload mtdna-msa --seeds 11,12,13

For each seed, on the inputs a run with that seed makes:

* the control: the reference put in the program's place, its DP in
  bfloat16 (the precision below the float32 the program states), aligns
  the same sample of sequences to the same center as a run's check
  compares; its ``pair_score_gap`` is read as a run's is;
* the program's own family runs (set-up's, through ``msa_run``): the
  tree's ``tree_nj_gap`` as it is; with the answer altered where it is
  produced (two leaves of the written tree swapped: two drawn from the
  seed, and the closest two whose rows differ); and with half of the tree stage's batch left out (the
  program's neighbour joining handed a distance matrix whose second
  half of rows and columns repeats the first half's).

It prints one JSON line per seed. It is not part of a benchmark run.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def control_gap(pairs, scoring: dict, width: int) -> float:
    """pair_score_gap of the bfloat16 reference's alignments."""
    from reference import gotoh
    import compare
    rows = gotoh.align([p[0] for p in pairs], [p[1] for p in pairs],
                       dtype="bfloat16", **scoring)
    return compare.pair_score_gap(
        [(q, c, rq, rc) for (q, c), (rq, rc) in zip(pairs, rows)],
        scoring, width)


def swap_leaves(newick: str, a: str, b: str) -> str:
    tmp = "\x00"
    for x, y in ((f"{a}:", tmp), (f"{b}:", f"{a}:"), (tmp, f"{b}:")):
        newick = newick.replace(x, y)
    return newick


def tree_half_fault(drv, out) -> float:
    """``tree_nj_gap`` of family 0 run with the fault planted."""
    import jax.numpy as jnp
    import compare
    from repro.core import nj

    orig = nj.neighbor_joining

    def half_left_out(D, size):
        n = D.shape[0]
        keep = (n + 1) // 2
        idx = jnp.concatenate([jnp.arange(keep), jnp.arange(n - keep)])
        return orig(D[idx][:, idx], size)

    nj.neighbor_joining = half_left_out
    try:
        drv._run(0, out)
    finally:
        nj.neighbor_joining = orig
    names, rows = compare.read_fasta(out / "aligned.fasta")
    return compare.tree_nj_gap((out / "tree.nwk").read_text(), names,
                               compare.reference_paths(rows))


def batch_readings(cell, seed: int) -> dict:
    import numpy as np
    import compare
    import generate
    import harness
    work = Path(tempfile.mkdtemp(prefix="chipbench_control_"))
    drv = harness.load_module("entries", cell.config["entry"]).Entry(
        cell.config, cell.traffic, seed=seed, workdir=work, chips=cell.chips)
    try:
        drv.setup(0)
        rng = generate.run_rng(seed, "control")
        sound, swapped, nearest = [], [], []
        for k, fam in enumerate(drv.families):
            out = work / f"warm{k}"
            names, rows = compare.read_fasta(out / "aligned.fasta")
            tree = (out / "tree.nwk").read_text()
            ref = compare.reference_paths(rows)
            sound.append(compare.tree_nj_gap(tree, names, ref))
            a, b = rng.choice(len(names), 2, replace=False)
            swapped.append(compare.tree_nj_gap(
                swap_leaves(tree, names[a], names[b]), names, ref))
            # the closest two leaves whose rows differ
            a, b = np.unravel_index(np.argmin(np.where(ref > 0, ref, np.inf)),
                                    ref.shape)
            nearest.append(compare.tree_nj_gap(
                swap_leaves(tree, names[a], names[b]), names, ref))
        half = tree_half_fault(drv, work / "half")
        fam = drv.families[0]
        pick = rng.choice(np.arange(1, len(fam.seqs)),
                          min(cell.config["check"]["pairs"],
                              len(fam.seqs) - 1), replace=False)
        pairs = [(fam.seqs[r], fam.seqs[0]) for r in pick]
    finally:
        drv.close()
        shutil.rmtree(work, ignore_errors=True)
    return {"tree_nj_gap": sound, "tree_nj_gap_swapped": swapped,
            "tree_nj_gap_swapped_nearest": nearest,
            "tree_nj_gap_half": half,
            "control_pair_score_gap": control_gap(
                pairs, cell.config["scoring"],
                cell.config["family"]["length"])}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import harness
    cell = harness.load_cell(args.workload)
    harness.require_tpu(cell.chips)
    harness.configure_jax()
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = batch_readings(cell, seed)
        print(json.dumps(dict(out, workload=cell.name, seed=seed,
                              seconds=time.perf_counter() - t0)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

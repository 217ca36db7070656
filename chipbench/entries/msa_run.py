"""Batch cells: a closed loop of families through ``repro.launch.msa_run``.

Set-up makes the traffic mix's families (``generate.closed_loop_families``:
histories fixed by the mix, nucleotides and order drawn from the seed),
writes each as a FASTA file and runs each once through ``msa_run.main``.
That compiles, or loads from the cache, every shape the window uses: the
MSA width, and with it the assembly, tree and SP-score shapes, follows
from the histories, so every seed loads the same programs. The window
then sends the families again, one at a time, in turn, until ``seconds``
have passed; the family in flight then is finished and counted.
``family_s`` is the window's elapsed seconds over the families completed.
"""
from __future__ import annotations

import hashlib
import json
import time

import numpy as np

import compare
import generate
import harness
import stats


class Entry:
    def __init__(self, config, traffic, *, seed, workdir, chips):
        self.cfg = config
        self.traffic = traffic
        self.seed = seed
        self.work = workdir
        self.chips = chips
        self.families = []
        self.done = []              # (family index, output dir)
        self.failed = 0

    def _run(self, k: int, out) -> None:
        from repro.launch import msa_run
        with harness.quiet_stdout():
            msa_run.main(["--fasta", str(self.work / f"f{k}.fasta"),
                          "--out", str(out), *self.cfg["msa_run"]])

    def setup(self, seconds: float) -> None:
        self.families = generate.closed_loop_families(
            self.cfg, self.traffic, self.seed)
        for k, fam in enumerate(self.families):
            with open(self.work / f"f{k}.fasta", "w") as f:
                for name, seq in zip(fam.names, fam.seqs):
                    f.write(f">{name}\n{seq}\n")
        for k in range(len(self.families)):
            self._run(k, self.work / f"warm{k}")

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            k = i % len(self.families)
            out = self.work / f"run{i}"
            try:
                self._run(k, out)
                self.done.append((k, out))
            except Exception as e:      # a failed family is counted, not fatal
                self.failed += 1
                print(f"family {i} failed: {e!r}", flush=True)
            i += 1
        elapsed = time.perf_counter() - t0
        n_done = len(self.done)
        n, length = self.cfg["n_sequences"], self.cfg["family"]["length"]
        return {
            "attempted": i, "failed": self.failed,
            "e2e": {"family_s": stats.per_family_seconds(elapsed, n_done)},
            "work": {"families": n_done, "n_sequences": n, "length": length,
                     # useful DP cells: every non-center sequence against
                     # the center, both of the configuration's length
                     "dp_cells": n_done * (n - 1) * length * length},
            "note": f"families completed {n_done} of {i}",
        }

    def release(self) -> None:
        """The program holds no device state between families."""

    def close(self) -> None:
        pass

    def check(self) -> list:
        chk = self.cfg["check"]
        scoring = self.cfg["scoring"]
        rng = generate.run_rng(self.seed, "check")
        n_rows = n_dead = 0
        tree_gap = 0.0
        ref_paths = {}              # one reference tree per distinct MSA
        candidates = []
        for run_i, (k, out) in enumerate(self.done):
            fam = self.families[k]
            names, rows = compare.read_fasta(out / "aligned.fasta")
            n_rows += compare.rows_bad(names, rows, fam.names, fam.seqs)
            n_dead += compare.dead_cols(rows)
            center = json.loads((out / "report.json").read_text())["center"]
            if center not in names or len(names) != len(fam.names):
                n_rows += len(fam.names)
                continue
            c = names.index(center)
            candidates += [(run_i, c, r) for r in range(len(names)) if r != c]
            key = hashlib.sha256("\n".join(rows).encode()).digest()
            if key not in ref_paths:
                ref_paths[key] = compare.reference_paths(rows)
            tree = (out / "tree.nwk").read_text()
            tree_gap = max(tree_gap, compare.tree_nj_gap(tree, names,
                                                         ref_paths[key]))
        pairs = []
        if candidates:
            pick = rng.choice(len(candidates),
                              size=min(chk["pairs"], len(candidates)),
                              replace=False)
            cache = {}
            for p in np.sort(pick):
                run_i, c, r = candidates[p]
                if run_i not in cache:
                    cache[run_i] = compare.read_fasta(
                        self.done[run_i][1] / "aligned.fasta")[1]
                rows = cache[run_i]
                fam = self.families[self.done[run_i][0]]
                pairs.append((fam.seqs[r], fam.seqs[c], rows[r], rows[c]))
        gap = compare.pair_score_gap(pairs, scoring,
                                     self.cfg["family"]["length"])
        return [compare.Check("rows_bad", n_rows, 0),
                compare.Check("dead_cols", n_dead, 0),
                compare.Check("pair_score_gap", gap, 0),
                compare.Check("tree_nj_gap", tree_gap, chk["tree_nj_gap"])]

"""Distributed MSA launcher: FASTA in, aligned FASTA + tree out.

Runs the Spark-pattern pipeline on whatever mesh the process sees (one CPU
device here; a real pod under jax.distributed). The same jitted stages are
what dryrun.py lowers for 512 devices.

  PYTHONPATH=src python -m repro.launch.msa_run --fasta in.fa --out out/ \
      --method kmer --tree cluster [--backend banded --band 128] \
      [--dist] [--mesh 4x1]

``--dist`` routes the alignment through ``repro.dist.mapreduce`` (shard_map
over the data axis — identical math, Spark-style execution); the default
path is the single-host driver in ``repro.core.msa``. ``--backend`` picks
the map(1) DP primitive from the ``repro.align`` registry (``auto`` =
Pallas kernel on TPU, jnp scan elsewhere; ``banded`` = O(n·band) memory).
``--tree`` picks the ``repro.phylo.TreeEngine`` backend for the phylogeny
stage (``nj`` = dense; ``tiled`` composes with ``--dist`` by shard-mapping
the distance strips over the same mesh; ``ml`` = auto backend plus
maximum-likelihood refinement — autodiff branch lengths, BIC model
selection, vmapped NNI); ``repro.launch.tree_run`` rebuilds a tree from
an already-aligned FASTA without redoing the MSA (and exposes the full
``--refine``/``--model``/``--bootstrap`` surface).

Flags:
  --fasta               input FASTA (required)
  --out                 output directory (aligned.fasta, tree.nwk,
                        report.json); default msa_out
  --method              kmer | plain | sw map(1) path (kmer = the paper's
                        trie-accelerated anchor chaining)
  --alphabet            dna | rna | protein (picks encoding + matrix;
                        protein uses BLOSUM62, gap_open 11)
  --tree                nj | cluster | tiled | auto | ml | none tree
                        backend (ml = auto backend + ML refinement)
  --cluster-threshold   N at or below which cluster/auto fall back to
                        dense NJ
  --tree-ll             record the tree's JC69 log-likelihood (DNA/RNA)
  --k                   k-mer width for the kmer method / sampled center
  --backend / --band    map(1) DP backend registry + band width
  --dist / --mesh       run the shard_map pipeline over a DxM mesh
  --trace-out           write the run's span tree as Chrome-trace JSON
  --metrics-out         write the final metrics snapshot as JSON

``docs/CLI.md`` holds the generated ``--help`` reference for every
launcher (kept in sync by ``tests/test_docs.py``).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax.numpy as jnp


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.msa_run",
        description="distributed MSA launcher: FASTA in, aligned FASTA + "
                    "tree out")
    ap.add_argument("--fasta", required=True)
    ap.add_argument("--out", default="msa_out")
    ap.add_argument("--method", default="kmer",
                    choices=["kmer", "plain", "sw"])
    ap.add_argument("--alphabet", default="dna",
                    choices=["dna", "rna", "protein"])
    ap.add_argument("--tree", default="nj",
                    choices=["nj", "cluster", "tiled", "auto", "ml", "none"],
                    help="tree backend (repro.phylo registry; nj = dense; "
                         "ml = auto backend + ML refinement)")
    ap.add_argument("--cluster-threshold", type=int, default=64,
                    help="N at or below which cluster/auto tree backends "
                         "fall back to dense NJ")
    ap.add_argument("--tree-ll", action="store_true",
                    help="record the tree's JC69 log-likelihood in the "
                         "report (DNA/RNA only)")
    ap.add_argument("--k", type=int, default=11)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "jnp", "pallas", "banded",
                             "banded-pallas"],
                    help="map(1) DP backend (repro.align registry)")
    ap.add_argument("--band", type=int, default=64,
                    help="band width for the banded backends (O(n*band) "
                         "direction memory; overflows fall back per pair)")
    ap.add_argument("--dist", action="store_true",
                    help="run the shard_map pipeline (repro.dist.mapreduce)")
    ap.add_argument("--mesh", default=None,
                    help="data x model for --dist, e.g. 4x1; default: all "
                         "visible devices x 1")
    from ..obs import export as obs_export
    obs_export.add_output_args(ap)
    return ap


def main(argv=None):
    parser = build_parser()
    args = parser.parse_args(argv)
    from . import compile_cache
    compile_cache.enable()
    if args.tree == "ml" and args.alphabet == "protein":
        parser.error("--tree ml needs a nucleotide alphabet (the 4-state "
                     "likelihood); use --tree cluster/tiled for protein")
    from ..obs import export as obs_export
    from ..obs import trace as _trace
    with _trace.request_trace(), _trace.span("msa_run", fasta=args.fasta):
        _run(args)
    obs_export.write_outputs(args)


def _run(args):
    from ..obs import trace as _trace
    with _trace.span("load"):
        from ..core import alphabet as ab
        from ..core import likelihood, sp_score
        from ..core.msa import MSAConfig, center_star_msa, decode_msa
        from ..data import read_fasta, write_fasta
        names, seqs = read_fasta(args.fasta)

    alpha = {"dna": ab.DNA, "rna": ab.RNA, "protein": ab.PROTEIN}[args.alphabet]
    cfg = MSAConfig(method=args.method, alphabet=args.alphabet, k=args.k,
                    gap_open=11 if args.alphabet == "protein" else 3,
                    backend=args.backend, band=args.band)
    mesh = None
    if args.dist:
        from .mesh import mesh_from_arg
        mesh = mesh_from_arg(args.mesh)
    t0 = time.time()
    if args.dist:
        from ..dist import mapreduce
        res = mapreduce.msa_over_mesh(seqs, cfg, mesh)
    else:
        res = center_star_msa(seqs, cfg)
    t_msa = time.time() - t0
    out = Path(args.out)
    with _trace.span("write", out=str(out)):
        out.mkdir(parents=True, exist_ok=True)
        write_fasta(out / "aligned.fasta", names, decode_msa(res.msa, cfg))

    with _trace.span("score"):
        msa = jnp.asarray(res.msa)
        sp = float(sp_score.avg_sp(msa, gap_code=alpha.gap_code,
                                   n_chars=alpha.n_chars))
    from ..align import resolve_backend
    report = {"n_sequences": len(seqs), "width": res.width,
              "center": names[res.center_idx],
              "center_mode": res.center_mode,
              "backend": resolve_backend(args.backend),
              "avg_sp_penalty": sp,
              "kmer_fallbacks": res.n_fallback,
              "msa_seconds": t_msa}

    if args.tree != "none":
        from ..phylo import TreeEngine
        t0 = time.time()
        backend = {"nj": "dense", "ml": "auto"}.get(args.tree, args.tree)
        engine = TreeEngine(gap_code=alpha.gap_code, n_chars=alpha.n_chars,
                            correct=args.alphabet != "protein",
                            backend=backend,
                            cluster_threshold=args.cluster_threshold,
                            mesh=mesh,
                            refine="ml" if args.tree == "ml" else "none")
        tree_res = engine.build(res.msa)
        report["tree_seconds"] = time.time() - t0
        report["tree_backend"] = tree_res.backend
        if tree_res.logl is not None:
            report["tree_model"] = tree_res.model
            report["tree_logl"] = tree_res.logl
        if tree_res.tile_stats is not None:
            report["tile_stats"] = tree_res.tile_stats
        nwk = tree_res.newick(names)
        with _trace.span("write", artifact="tree.nwk"):
            (out / "tree.nwk").write_text(nwk + "\n")
        if args.tree_ll and args.alphabet != "protein":
            report["log_likelihood"] = float(likelihood.log_likelihood(
                msa, jnp.asarray(tree_res.children),
                jnp.asarray(tree_res.blen), tree_res.root,
                gap_code=alpha.gap_code))

    with _trace.span("report"):
        (out / "report.json").write_text(json.dumps(report, indent=1))
    print(json.dumps(report, indent=1))


if __name__ == "__main__":
    main()

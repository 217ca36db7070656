"""MSA/phylogeny web service launcher: the paper's web-server pillar.

  PYTHONPATH=src python -m repro.launch.serve_msa --port 8642 \\
      [--method plain --backend auto] [--dist --mesh 4x1]

Serves ``repro.serve.MSAService`` over stdlib HTTP/JSON:

  POST /align      {"fasta": ">a\\nACGT..."} or {"sequences": [...],
                   "names": [...]} -> aligned rows + msa_id; with
                   ?name=... (or "name" in the body) and --store-dir:
                   create/load a persistent named alignment
  POST /align/add  {"msa_id": ..., "fasta"/"sequences": ...} ->
                   incremental insertion against the frozen center;
                   {"name": ...} ingests into the store (one atomic
                   generation per add, background realign past drift)
  POST /tree       {"msa_id": ...}, {"name": ...} or sequences -> Newick
  POST /search     query sequences -> per-query top-k database hits
                   (needs --search-db / --search-index)
  GET  /healthz    liveness + cache / coalescing-queue stats
  GET  /metrics    Prometheus text exposition of the repro.obs registry
  GET  /statusz    human-readable status page (config, queues, spans)

Flags:
  --host/--port         bind address (default 127.0.0.1:8642)
  --alphabet            dna | rna | protein (server-wide engine config)
  --method              plain | sw | kmer map(1) path; kmer requests run
                        uncoalesced (per-center index)
  --backend/--band      repro.align DP backend registry + band width
  --k/--center          k-mer width / center selection policy
  --max-batch           coalescing: flush a merged batch at this many pairs
  --max-wait-ms         coalescing: max time a request waits for company
  --cache-mb            result-cache byte budget (content-hash LRU)
  --drift-threshold     /align/add width growth past which a full realign
                        replaces the incremental merge (named alignments:
                        cumulative growth scheduling a background realign)
  --store-dir           persistent MSAStore root enabling named
                        alignments that survive restarts
  --store-keep          generation files retained per named alignment
  --store-realign       background (realign + atomic swap) | never
  --tree-backend        repro.phylo registry default for /tree
  --tree-refine         none | ml default /tree refinement (requests can
                        override per call with {"refine": "ml"})
  --tree-model          substitution model for refine=ml (auto = BIC)
  --tree-bootstrap      default bootstrap replicate count for refine=ml
  --tree-seed           default bootstrap/ML seed (part of the tree
                        cache fingerprint)
  --cluster-threshold   N at or below which cluster/auto trees go dense
  --search-db           database FASTA enabling POST /search
  --search-index        search-index artifact: loaded when present, else
                        built from --search-db and saved atomically
  --search-k            seeding k-mer width for --search-db index builds
  --dist/--mesh         shard requests of >= --dist-threshold sequences
                        over the mesh (repro.dist.mapreduce) and shard-map
                        /tree distance strips over it
  --verbose             log one line per HTTP request
  --trace-out           on exit, write the span tree as Chrome-trace JSON
  --metrics-out         on exit, write the final metrics snapshot as JSON

SIGINT/SIGTERM drain gracefully: the listener stops, in-flight requests
finish, and the coalescing queue flushes before exit.
"""
from __future__ import annotations

import argparse
import signal


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(
        prog="repro.launch.serve_msa",
        description="MSA/phylogeny web service over the repro engines")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8642)
    ap.add_argument("--alphabet", default="dna",
                    choices=["dna", "rna", "protein"])
    ap.add_argument("--method", default="plain",
                    choices=["plain", "sw", "kmer"],
                    help="map(1) path; kmer requests run uncoalesced")
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "jnp", "pallas", "banded",
                             "banded-pallas"],
                    help="map(1) DP backend (repro.align registry)")
    ap.add_argument("--band", type=int, default=64,
                    help="band width for the banded backends")
    ap.add_argument("--k", type=int, default=11, help="k-mer width")
    ap.add_argument("--center", default="first",
                    choices=["first", "sampled"],
                    help="center selection policy")
    ap.add_argument("--max-batch", type=int, default=256,
                    help="coalescing: flush at this many merged pairs")
    ap.add_argument("--max-wait-ms", type=float, default=5.0,
                    help="coalescing: max wait for request company")
    ap.add_argument("--cache-mb", type=int, default=256,
                    help="result cache byte budget (MiB)")
    ap.add_argument("--drift-threshold", type=float, default=0.25,
                    help="align/add relative width growth forcing a full "
                         "realign (for named alignments: the cumulative "
                         "growth that schedules a background realign)")
    ap.add_argument("--store-dir", default=None,
                    help="persistent MSA store root: enables named "
                         "alignments (/align?name=...) with atomic "
                         "generation commits surviving restarts")
    ap.add_argument("--store-keep", type=int, default=4,
                    help="generation files retained per named alignment")
    ap.add_argument("--store-realign", default="background",
                    choices=["background", "never"],
                    help="drift response for named alignments: realign on "
                         "a worker thread and swap atomically, or never")
    ap.add_argument("--tree-backend", default="auto",
                    choices=["auto", "dense", "tiled", "cluster"],
                    help="default /tree backend (repro.phylo registry)")
    ap.add_argument("--tree-refine", default="none",
                    choices=["none", "ml"],
                    help="default /tree refinement (requests can override "
                         "with {'refine': 'ml'})")
    ap.add_argument("--tree-model", default="auto",
                    choices=["auto", "jc69", "k80", "hky85", "gtr"],
                    help="substitution model for refine=ml (auto = BIC)")
    ap.add_argument("--tree-bootstrap", type=int, default=0,
                    help="default bootstrap replicates (requires "
                         "refine=ml; requests without it get a 400)")
    ap.add_argument("--tree-seed", type=int, default=0,
                    help="default bootstrap/ML seed (requests can "
                         "override with {'seed': N})")
    ap.add_argument("--cluster-threshold", type=int, default=64,
                    help="N at or below which cluster/auto trees go dense")
    ap.add_argument("--search-db", default=None,
                    help="database FASTA enabling POST /search")
    ap.add_argument("--search-index", default=None,
                    help="search-index artifact: loaded when present, "
                         "else built from --search-db and saved")
    ap.add_argument("--search-k", type=int, default=6,
                    help="seeding k-mer width for --search-db builds")
    ap.add_argument("--dist", action="store_true",
                    help="route large requests through repro.dist.mapreduce")
    ap.add_argument("--mesh", default=None,
                    help="data x model for --dist, e.g. 4x1; default: all "
                         "visible devices x 1")
    ap.add_argument("--dist-threshold", type=int, default=512,
                    help="with --dist: sequence count at which a request "
                         "goes over the mesh")
    ap.add_argument("--verbose", action="store_true",
                    help="log one line per HTTP request")
    from ..obs import export as obs_export
    obs_export.add_output_args(ap)
    return ap


def main(argv=None):
    parser = build_parser()
    args = parser.parse_args(argv)
    from . import compile_cache
    compile_cache.enable()
    if args.tree_bootstrap > 0 and args.tree_refine != "ml":
        parser.error("--tree-bootstrap requires --tree-refine ml "
                     "(otherwise every plain /tree request would 400)")

    from ..serve import MSAService, ServiceConfig, serve_http

    mesh = None
    if args.dist:
        from .mesh import mesh_from_arg
        mesh = mesh_from_arg(args.mesh)

    search_index = None
    if args.search_db or args.search_index:
        if args.alphabet == "protein":
            parser.error("--search-db needs a nucleotide --alphabet "
                         "(base-4 k-mer seeding)")
        from pathlib import Path

        from ..search import SearchIndex
        idx_path = Path(args.search_index) if args.search_index else None
        if idx_path is not None and idx_path.exists():
            search_index = SearchIndex.load(idx_path)
        else:
            if not args.search_db:
                parser.error(f"--search-index {idx_path} does not exist; "
                             f"pass --search-db to build it")
            from ..data import read_fasta
            db_names, db_seqs = read_fasta(args.search_db)
            search_index = SearchIndex.build(db_names, db_seqs,
                                             k=args.search_k,
                                             alphabet=args.alphabet)
            if idx_path is not None:
                search_index.save(idx_path)

    service = MSAService(ServiceConfig(
        alphabet=args.alphabet, method=args.method, backend=args.backend,
        band=args.band, k=args.k, center=args.center,
        max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
        cache_bytes=args.cache_mb << 20,
        drift_threshold=args.drift_threshold,
        store_dir=args.store_dir, store_keep=args.store_keep,
        store_realign=args.store_realign,
        tree_backend=args.tree_backend,
        tree_refine=args.tree_refine,
        tree_model=args.tree_model,
        tree_bootstrap=args.tree_bootstrap,
        tree_seed=args.tree_seed,
        cluster_threshold=args.cluster_threshold,
        mesh=mesh, dist_threshold=args.dist_threshold,
        search_index=search_index))
    httpd = serve_http(service, args.host, args.port, verbose=args.verbose)

    def _shutdown(signum, frame):
        # runs on the main thread; shutdown() must come from another
        # thread, so just flip the flag serve_forever polls
        raise KeyboardInterrupt

    signal.signal(signal.SIGTERM, _shutdown)
    store_note = ""
    if service.store is not None:
        restored = service.store.names()
        store_note = (f" store={args.store_dir}"
                      f"[{len(restored)} named alignment(s)]")
    print(f"serving MSA/phylogeny on http://{args.host}:{args.port} "
          f"(alphabet={args.alphabet} method={args.method} "
          f"backend={service.engine.backend}"
          f"{' mesh' if mesh is not None else ''}"
          f"{f' search_db={search_index.n_seqs}' if search_index else ''}"
          f"{store_note})"
          f" — Ctrl-C drains")
    try:
        httpd.serve_forever()
    except KeyboardInterrupt:
        pass
    print("draining: finishing in-flight requests ...")
    httpd.server_close()          # waits for handler threads
    service.drain()               # flush the coalescing queue
    from ..obs import export as obs_export
    obs_export.write_outputs(args)
    print("drained; bye")


if __name__ == "__main__":
    main()

#!/usr/bin/env python3
"""Bring-up smoke run: the MSA -> tree path and the served /align path on
the TPU, through the entry points a user calls, at the paper's real
sequence lengths.

  python chip_smoke.py              # one chip: mtdna, 16s, served phases
  python chip_smoke.py --chips 4    # four chips: the mtDNA family through
                                    # --dist --mesh 4x1 vs the host path

Phases (one process; the server runs on a thread of it, so no second
process ever needs the chip):

  mtdna   672 x 16,569 (the human mtDNA length; phi_dna's divergence;
          N = the paper's 1x human-mtDNA set): ``msa_run --method kmer
          --tree tiled --tree-ll``
  16s     1,024 x 1,500 (phi_rna's divergence): ``msa_run --method plain
          --backend auto`` (the SW kernel on every pair) ``--tree tiled``
  served  an in-process ``serve_http``: concurrent POST /align of
          16S-length families and one POST /tree; every response 200 and
          the coalescer reports no failed batch

Each phase checks its result against a reference on the same device: the
``jnp`` backend's alignments (MSA rows and scores) for a subset of pairs,
the dense distance matrix for one tile of the tiled tree, and a finite
tree log-likelihood. It prints one line with its sizes, resolved backend,
seconds and device kind.

The last line of stdout is ``{"ok": true, "device": {"platform": "tpu",
"kind": ..., "count": N}}``. Without a TPU, outside a checkout, or on any
failed check the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import math
import socket
import sys
import tempfile
import threading
import time
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

# (n_sequences, root_len, branch_sub, branch_indel): phi_dna / phi_rna
# divergences (repro.data.datasets) at the real lengths
MTDNA = (672, 16569, 0.002, 0.0002)
RNA16S = (1024, 1500, 0.01, 0.001)
SERVED = (4, 16)              # /align requests x sequences per request
REF_PAIRS = 32                # pairs checked against the jnp backend
TILE = 128                    # rows of the distance tile checked


class SmokeFailure(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def _repro():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke.py: no repro package under {SRC}; "
                         f"run it from a checkout of the repository")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_tpu(n_chips: int) -> dict:
    """The chip or nothing: no CPU fallback exists on this path."""
    import jax
    from repro.align import resolve_backend
    from repro.kernels import default_interpret
    from repro.phylo.tiles import TileContext

    backend = jax.default_backend()
    if backend != "tpu":
        raise SystemExit(f"chip_smoke.py: JAX found no TPU (default backend "
                         f"{backend!r})")
    info = device_info()
    check(info["count"] >= n_chips,
          f"{n_chips} chips requested, JAX sees {info['count']}")
    check(default_interpret() is False, "Pallas kernels would interpret")
    check(resolve_backend("auto") == "pallas",
          "--backend auto does not resolve to the Pallas SW kernel")
    check(TileContext(gap_code=5, n_chars=5).use_kernel is True,
          "the tiled tree would not use the distance kernel")
    return info


def assert_kernel_in_map1(alphabet: str = "dna"):
    """The compiled HLO of one map(1) call holds the Pallas kernel."""
    import jax
    import jax.numpy as jnp
    from repro.core.msa import MSAConfig

    fn = MSAConfig(alphabet=alphabet, backend="auto").engine().batch_fn()
    lowered = jax.jit(fn).lower(
        jax.ShapeDtypeStruct((8, 256), jnp.int8),
        jax.ShapeDtypeStruct((8,), jnp.int32),
        jax.ShapeDtypeStruct((256,), jnp.int8),
        jax.ShapeDtypeStruct((), jnp.int32))
    check("tpu_custom_call" in lowered.compile().as_text(),
          "no tpu_custom_call in the compiled map(1) program")


# ------------------------------------------------------------------ data

def simulate(n: int, length: int, sub: float, indel: float, seed: int):
    from repro.data import SimConfig, simulate_family
    return simulate_family(SimConfig(n_leaves=n, root_len=length,
                                     branch_sub=sub, branch_indel=indel,
                                     seed=seed))


def _encode_aligned(rows, alpha):
    import numpy as np
    lut = np.full(256, alpha.unknown_code, np.int8)
    for c, code in alpha.char_to_code.items():
        lut[ord(c)] = code
    lut[ord("-")] = alpha.gap_code
    return np.stack([lut[np.frombuffer(r.upper().encode(), np.uint8)]
                     for r in rows])


# -------------------------------------------------------------- references

def check_pairs_vs_jnp(seqs, *, alphabet: str, method: str, backend: str,
                       n_pairs: int = REF_PAIRS):
    """The run's backend against the jnp backend on the same device, for
    the first ``n_pairs`` sequences against sequence 0: pair scores and
    rows (map(1)) and the assembled MSA rows, all bit-identical."""
    import dataclasses

    import numpy as np
    from repro.core.msa import MSAConfig, center_star_msa, encode_for_msa

    sub_seqs = list(seqs[:n_pairs + 1])
    cfg = MSAConfig(method=method, alphabet=alphabet, backend=backend)
    ref = dataclasses.replace(cfg, backend="jnp")
    S, lens = encode_for_msa(sub_seqs, cfg)
    got = cfg.engine().align_to_center(S[1:], lens[1:], S[0], lens[0])
    exp = ref.engine().align_to_center(S[1:], lens[1:], S[0], lens[0])
    for field in ("score", "a_row", "b_row", "aln_len"):
        check(np.array_equal(np.asarray(getattr(got, field)),
                             np.asarray(getattr(exp, field))),
              f"map(1) {field} differs from the jnp backend")
    check(np.array_equal(center_star_msa(sub_seqs, cfg).msa,
                         center_star_msa(sub_seqs, ref).msa),
          "MSA rows differ from the jnp backend")
    return len(sub_seqs) - 1


def check_tile_vs_dense(msa, alpha, use_kernel=None):
    """One tile of the tiled tree against the dense distance matrix of the
    same rows (``use_kernel=None``: the tiled tree's own choice, the
    compiled kernel on the chip). The match/valid counts are exact
    integers and must agree exactly; the JC69 distances agree to f32
    rounding (the device's log differs by fusion)."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.distance import distance_matrix, match_valid_counts
    from repro.kernels.distance import match_valid_pallas
    from repro.phylo.tiles import TileContext

    rows = jnp.asarray(msa[:TILE])
    kw = dict(gap_code=alpha.gap_code, n_chars=alpha.n_chars)
    ctx = TileContext(use_kernel=use_kernel, **kw)
    if ctx.use_kernel:
        got = match_valid_pallas(rows, rows, **kw)
        exp = match_valid_counts(rows, rows, **kw)
        check(all(np.array_equal(np.asarray(g), np.asarray(e))
                  for g, e in zip(got, exp)),
              "distance-kernel match/valid counts differ from the dense ones")
    tile = ctx.block(rows, rows)
    dense = np.asarray(distance_matrix(rows, **kw))
    check(np.allclose(tile, dense, rtol=1e-6, atol=1e-7),
          f"distance tile differs from the dense matrix (max abs "
          f"{float(np.max(np.abs(tile - dense)))})")
    return rows.shape[0]


# ------------------------------------------------------------------ phases

def phase_msa(name: str, fam, work: Path, *, alphabet: str, method: str,
              backend: str = "auto", use_kernel=None, extra=(),
              n_ref: int = REF_PAIRS) -> dict:
    """``msa_run`` FASTA -> aligned FASTA + tiled tree, then its checks."""
    from repro.core.msa import MSAConfig
    from repro.data import read_fasta, write_fasta
    from repro.launch import msa_run

    fasta = work / f"{name}.fasta"
    out = work / name
    write_fasta(fasta, fam.names, fam.seqs)
    t0 = time.perf_counter()
    msa_run.main(["--fasta", str(fasta), "--out", str(out),
                  "--alphabet", alphabet, "--method", method,
                  "--backend", backend, "--tree", "tiled", "--tree-ll",
                  *extra])
    seconds = time.perf_counter() - t0
    report = json.loads((out / "report.json").read_text())
    names, rows = read_fasta(out / "aligned.fasta")
    check(names == fam.names, f"{name}: aligned FASTA lost or reordered rows")
    check(len({len(r) for r in rows}) == 1, f"{name}: ragged MSA rows")
    check(all(r.replace("-", "") == s for r, s in zip(rows, fam.seqs)),
          f"{name}: an aligned row does not degap to its input")
    check(math.isfinite(report.get("log_likelihood", math.nan)),
          f"{name}: tree log-likelihood not finite")
    check((out / "tree.nwk").read_text().strip().endswith(";"),
          f"{name}: no Newick tree")
    alpha = MSAConfig(alphabet=alphabet).alpha()
    msa = _encode_aligned(rows, alpha)
    t1 = time.perf_counter()
    n_pairs = check_pairs_vs_jnp(fam.seqs, alphabet=alphabet, method=method,
                                 backend=backend, n_pairs=n_ref)
    tile = check_tile_vs_dense(msa, alpha, use_kernel)
    return {"phase": name, "n": len(fam.seqs),
            "len_max": int(max(len(s) for s in fam.seqs)),
            "width": int(report["width"]), "method": method,
            "backend": report["backend"],
            "tree_backend": report.get("tree_backend"),
            "kmer_fallbacks": report.get("kmer_fallbacks"),
            "logl": report["log_likelihood"],
            "msa_seconds": report["msa_seconds"],
            "tree_seconds": report.get("tree_seconds"),
            "seconds": seconds, "ref_pairs": n_pairs, "ref_tile": tile,
            "ref_seconds": time.perf_counter() - t1,
            "_msa": msa}


def _post(port: int, path: str, payload: dict, timeout: float = 600):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def phase_served(families, *, alphabet: str = "rna",
                 backend: str = "auto") -> dict:
    """Concurrent /align requests and one /tree against an in-process
    ``serve_http``; the first family is checked against a jnp-backend
    service computing the same request without HTTP."""
    import dataclasses

    from repro.serve import MSAService, ServiceConfig, serve_http

    cfg = ServiceConfig(alphabet=alphabet, method="plain", backend=backend)
    service = MSAService(cfg)
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    httpd = serve_http(service, "127.0.0.1", port)
    server = threading.Thread(target=httpd.serve_forever, daemon=True)
    server.start()
    t0 = time.perf_counter()
    try:
        with concurrent.futures.ThreadPoolExecutor(len(families)) as pool:
            replies = list(pool.map(
                lambda fam: _post(port, "/align", {"names": fam.names,
                                                   "sequences": fam.seqs}),
                families))
        for status, body in replies:
            check(status == 200, f"/align answered {status}: {body}")
        status, tree = _post(port, "/tree", {
            "msa_id": replies[0][1]["alignment"]["msa_id"]})
        check(status == 200 and tree.get("newick", "").endswith(";"),
              f"/tree answered {status}: {tree}")
        seconds = time.perf_counter() - t0
        stats = service.coalescer.stats()
    finally:
        httpd.shutdown()
        httpd.server_close()
        service.drain()
        server.join()
    check(stats["failed_batches"] == 0,
          f"coalescer failed {stats['failed_batches']} batch(es)")
    ref = MSAService(dataclasses.replace(cfg, backend="jnp"))
    try:
        exp = ref.align(families[0].names, families[0].seqs)
    finally:
        ref.drain()
    check(exp["alignment"]["rows"] == replies[0][1]["alignment"]["rows"],
          "served MSA rows differ from the jnp backend")
    return {"phase": "served", "requests": len(families) + 1,
            "n_per_request": len(families[0].seqs),
            "len_max": int(max(len(s) for f in families for s in f.seqs)),
            "backend": service.engine.backend,
            "paths": sorted({body["path"] for _, body in replies}),
            "engine_calls": stats["engine_calls"],
            "coalesced_jobs": stats["coalesced_jobs"],
            "failed_batches": stats["failed_batches"], "seconds": seconds}


def phase_dist4(fam, work: Path) -> dict:
    """The mtDNA family through ``--dist --mesh 4x1`` and through the host
    path (default device 0): identical MSA rows and tree, shards on 4
    chips."""
    import numpy as np
    from repro.core.msa import MSAConfig, encode_for_msa
    from repro.dist import mapreduce, sharding as sh
    from repro.launch import msa_run
    from repro.launch.mesh import mesh_from_arg

    mesh = mesh_from_arg("4x1")
    S, _ = encode_for_msa(fam.seqs[1:], MSAConfig())
    padded, _ = mapreduce.pad_rows(np.asarray(S), 4)
    shards = sh.shard_rows(padded, mesh, "data")
    check(len(shards.sharding.device_set) == 4,
          f"input shards on {len(shards.sharding.device_set)} device(s)")
    check(len({s.device for s in shards.addressable_shards}) == 4,
          "input shards do not land on 4 distinct devices")

    fasta = work / "mtdna.fasta"
    from repro.data import write_fasta
    write_fasta(fasta, fam.names, fam.seqs)
    common = ["--fasta", str(fasta), "--method", "kmer", "--tree", "tiled"]
    seconds = {}
    for label, extra in (("dist", ["--dist", "--mesh", "4x1"]), ("host", [])):
        t0 = time.perf_counter()
        msa_run.main(common + ["--out", str(work / label)] + extra)
        seconds[label] = time.perf_counter() - t0
    for artifact in ("aligned.fasta", "tree.nwk"):
        check((work / "dist" / artifact).read_bytes()
              == (work / "host" / artifact).read_bytes(),
              f"--dist {artifact} differs from the host path")
    return {"phase": "mtdna-dist4", "n": len(fam.seqs),
            "len_max": int(max(len(s) for s in fam.seqs)), "mesh": "4x1",
            "shard_devices": sorted(d.id for d in shards.sharding.device_set),
            "dist_seconds": seconds["dist"], "host_seconds": seconds["host"]}


def _line(info: dict, kind: str) -> str:
    shown = {k: v for k, v in info.items() if not k.startswith("_")}
    return "phase " + json.dumps(dict(shown, device_kind=kind))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mtDNA --dist mesh phase and the "
                         "host path it is compared with")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    _repro()
    from repro.launch import compile_cache
    compile_cache.enable()
    info = require_tpu(args.chips)
    kind = info["kind"]
    assert_kernel_in_map1()

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        work = Path(tmp)
        if args.chips == 4:
            fam = simulate(*MTDNA, seed=args.seed)
            print(_line(phase_dist4(fam, work), kind), flush=True)
        else:
            fam = simulate(*MTDNA, seed=args.seed)
            print(_line(phase_msa("mtdna", fam, work, alphabet="dna",
                                  method="kmer"), kind), flush=True)
            fam = simulate(*RNA16S, seed=args.seed + 1)
            print(_line(phase_msa("16s", fam, work, alphabet="rna",
                                  method="plain"), kind), flush=True)
            n_req, n_seq = SERVED
            fams = [simulate(n_seq, RNA16S[1], *RNA16S[2:],
                             seed=args.seed + 2 + i) for i in range(n_req)]
            print(_line(phase_served(fams), kind), flush=True)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

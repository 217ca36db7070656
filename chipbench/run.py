#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

  python chipbench/run.py --workload mtdna-msa --seed 7 --seconds 30 --trace 0

Set-up makes every input from ``--seed``, warms up every shape the
window will use (compiled programs come from the persistent cache in
``<checkout>/.jax_cache``), then the window runs for ``--seconds``. The
last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, last,
``checks``: every number compared, beside its limit. The same numbers
are the last lines of stderr. Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        raise SystemExit(f"chipbench: no program under {ROOT / 'src'}; run "
                         f"from a checkout of the repository")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))
    import harness

    cell = harness.load_cell(args.workload)
    harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                     trace=bool(args.trace), t_start=T_START)
    return 0


if __name__ == "__main__":
    sys.exit(main())

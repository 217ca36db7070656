"""chip_smoke.py stays honest off the chip: its phase functions run end to
end at a tiny size with the Pallas kernels in interpret mode, and the
script itself refuses to run (non-zero exit, no result line) without a
TPU or outside a checkout."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def test_phases_tiny_interpret(tmp_path):
    """mtdna / 16s / served phases at N=8, L~300 through the Pallas SW and
    distance kernels (interpreted), each checked against its reference."""
    fam = chip_smoke.simulate(8, 300, 0.002, 0.0002, seed=0)
    mt = chip_smoke.phase_msa("mtdna", fam, tmp_path, alphabet="dna",
                              method="kmer", backend="pallas",
                              use_kernel=True, n_ref=4)
    assert mt["backend"] == "pallas" and mt["n"] == 8
    fam = chip_smoke.simulate(8, 300, 0.01, 0.001, seed=1)
    r16 = chip_smoke.phase_msa("16s", fam, tmp_path, alphabet="rna",
                               method="plain", backend="pallas",
                               use_kernel=True, n_ref=4)
    assert r16["ref_pairs"] == 4 and r16["ref_tile"] == 8
    fams = [chip_smoke.simulate(4, 300, 0.01, 0.001, seed=2 + i)
            for i in range(2)]
    served = chip_smoke.phase_served(fams, backend="pallas")
    assert served["failed_batches"] == 0 and served["backend"] == "pallas"
    line = chip_smoke._line(served, "cpu")
    assert json.loads(line[len("phase "):])["device_kind"] == "cpu"


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=300)


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_script_fails_without_tpu(tmp_path, where):
    """On the CPU (and with nothing of the repo beside it) the script exits
    non-zero and never prints the ok line: there is no CPU fallback."""
    cwd = ROOT
    if where == "alone":
        shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
        cwd = tmp_path
    proc = _run(cwd)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout

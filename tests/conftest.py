import os
import sys

# Tests run on the real device count (1 CPU); the 512-device forcing lives
# ONLY in launch/dryrun.py (run via subprocess in test_dryrun_small.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# The launchers turn JAX's persistent compilation cache on
# (repro.launch.compile_cache); the test suite keeps it off, in this
# process and in the launcher subprocesses some tests start.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_compilation_cache", False)


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)


def mutate(s, rng, nsub=3, nins=1, ndel=1, alphabet="ACGT"):
    s = list(s)
    for _ in range(nsub):
        i = rng.integers(0, len(s))
        s[i] = alphabet[rng.integers(0, len(alphabet))]
    for _ in range(nins):
        i = rng.integers(0, len(s) + 1)
        s.insert(i, alphabet[rng.integers(0, len(alphabet))])
    for _ in range(ndel):
        if len(s) > 2:
            i = rng.integers(0, len(s))
            del s[i]
    return "".join(s)


@pytest.fixture
def dna_family():
    # dedicated generator: family content must not depend on test order
    r = np.random.default_rng(42)
    base = "".join(r.choice(list("ACGT"), 300))
    return [base] + [mutate(base, r, 4, 1, 1) for _ in range(7)]

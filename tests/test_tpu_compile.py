"""Every Pallas kernel of the main path compiles for a TPU v5e chip at real
widths, without a chip: the TPU compiler compiles for a described (not
attached) v5e:2x2 topology, and refuses what interpret mode accepts —
unaligned blocks, scalar VMEM stores, unsupported shape casts, gathers.

All cases live in this one file so the one test worker that describes the
topology (and so holds the TPU library) compiles all of them.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import alphabet as ab

SUB = ab.dna_matrix().shape            # (6, 6) DNA substitution matrix


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:               # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Pallas kernel is in it


@pytest.mark.parametrize("local", [False, True])
def test_sw_forward_16s(one_chip, local):
    """SW forward at the 16S cell: B=8, n = m = 1536."""
    from repro.kernels.sw.ops import gotoh_forward_pallas

    B, n = 8, 1536
    _compile(lambda a, b, l, s: gotoh_forward_pallas(
        a, b, l, s, gap_open=3, gap_extend=1, local=local, interpret=False),
        one_chip, ((B, n), jnp.int8), ((B, n), jnp.int8),
        ((B, 2), jnp.int32), (SUB, jnp.float32))


@pytest.mark.parametrize("B,n", [(7, 16569), (953, 1500)])
def test_sw_forward_cells(one_chip, B, n):
    """SW forward (global) at the benchmark cells' calls: mtdna-msa's 7
    pairs at 16,569 bp (one partial 8-pair group, 32-row blocks) and
    rrna16s-nj's 953 pairs at 1,500 bp (119 full groups and one of 1)."""
    from repro.kernels.sw.ops import gotoh_forward_pallas

    _compile(lambda a, b, l, s: gotoh_forward_pallas(
        a, b, l, s, gap_open=3, gap_extend=1, local=False, interpret=False),
        one_chip, ((B, n), jnp.int8), ((B, n), jnp.int8),
        ((B, 2), jnp.int32), (SUB, jnp.float32))


@pytest.mark.parametrize("fused", [False, True])
def test_banded_mtdna(one_chip, fused):
    """Banded forward and fused pairs at mtDNA length padded to 128
    (n = m = 16896), band 128."""
    from repro.kernels.banded.ops import banded_forward_pallas, banded_pairs_fused

    B, n, W = 4, 16896, 128
    kern = banded_pairs_fused if fused else banded_forward_pallas
    _compile(lambda a, b, l, s: kern(a, b, l, s, gap_open=3, gap_extend=1,
                                     band=W, interpret=False),
             one_chip, ((B, n), jnp.int8), ((B, n), jnp.int8),
             ((B, 2), jnp.int32), (SUB, jnp.float32))


def test_distance_tile(one_chip):
    """Distance match/valid counts at (1024, 16896): 1,024 rows of an
    mtDNA-width MSA."""
    from repro.kernels.distance import match_valid_pallas

    N, L = 1024, 16896
    _compile(lambda a, b: match_valid_pallas(a, b, n_chars=5, gap_code=5,
                                             interpret=False),
             one_chip, ((N, L), jnp.int8), ((N, L), jnp.int8))


def test_mesh_chain_calls_fit_the_budget(one_chip):
    """The mesh's chain program at 840 rows a chip (the mtDNA set
    replicated 5x on four chips) splits into calls whose temporaries fit
    ``DIRS_BUDGET_BYTES``; ``chain_row_bytes`` bounds what one row holds."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.align.engine import DIRS_BUDGET_BYTES
    from repro.dist import mapreduce

    rows, L = 840, 16569
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    progs = mapreduce.distributed_center_star(
        mesh, method="kmer", sub=jnp.asarray(ab.dna_matrix()), gap_code=5,
        out_len=2 * L + 64, num_slots=L + 1, gap_open=3, gap_extend=1)

    def sd(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))
    temps = progs.chain.lower(
        sd((rows, L), jnp.int8, P("data", None)),
        sd((rows,), jnp.int32, P("data")), sd((L,), jnp.int8, P()),
        sd((), jnp.int32, P()), sd((4 ** 11, 4), jnp.int32, P()),
    ).compile().memory_analysis().temp_size_in_bytes
    per_call = -(-rows // -(-rows // (DIRS_BUDGET_BYTES
                                      // mapreduce.chain_row_bytes(256, 64))))
    assert temps <= DIRS_BUDGET_BYTES
    assert temps <= per_call * mapreduce.chain_row_bytes(256, 64)

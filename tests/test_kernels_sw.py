"""SW/Gotoh Pallas kernel vs jnp oracle: shape/dtype/param sweeps."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alphabet as ab
from repro.core import pairwise as pw
from repro.kernels.sw.ops import gotoh_forward_pallas
from repro.kernels.sw.ref import gotoh_forward_ref

RNG = np.random.default_rng(0)


def _case(B, n, m, n_chars=4):
    A = RNG.integers(0, n_chars, (B, n)).astype(np.int8)
    Bm = RNG.integers(0, n_chars, (B, m)).astype(np.int8)
    lens = np.stack([RNG.integers(5, n + 1, B),
                     RNG.integers(5, m + 1, B)], 1).astype(np.int32)
    return jnp.asarray(A), jnp.asarray(Bm), jnp.asarray(lens)


@pytest.mark.parametrize("B,n,m,block", [
    (2, 32, 48, 16), (4, 64, 96, 32), (3, 128, 64, 128), (1, 96, 200, 32),
])
@pytest.mark.parametrize("local", [False, True])
def test_kernel_matches_oracle(B, n, m, block, local):
    a, b, lens = _case(B, n, m)
    sub = ab.dna_matrix().astype(jnp.float32)
    k = gotoh_forward_pallas(a, b, lens, sub, gap_open=3, gap_extend=1,
                             local=local, block_rows=block)
    dref, oref = gotoh_forward_ref(a, b, lens, sub, gap_open=3, gap_extend=1,
                                   local=local)
    np.testing.assert_allclose(np.asarray(k.score), np.asarray(oref[:, 0]))
    for i in range(B):
        la, lb = int(lens[i, 0]), int(lens[i, 1])
        dk = np.asarray(k.dirs[i])[:la, : lb + 1]
        dr = np.asarray(dref[i])[:la, : lb + 1]
        assert (dk == dr).all()


@pytest.mark.parametrize("go,ge", [(2, 1), (11, 1), (5, 2)])
def test_gap_params(go, ge):
    a, b, lens = _case(2, 64, 64)
    sub = ab.dna_matrix(match=2, mismatch=-3).astype(jnp.float32)
    k = gotoh_forward_pallas(a, b, lens, sub, gap_open=go, gap_extend=ge,
                             local=False, block_rows=32)
    _, oref = gotoh_forward_ref(a, b, lens, sub, gap_open=go, gap_extend=ge,
                                local=False)
    np.testing.assert_allclose(np.asarray(k.score), np.asarray(oref[:, 0]))


def test_protein_blosum():
    a, b, lens = _case(2, 64, 64, n_chars=20)
    sub = ab.blosum62().astype(jnp.float32)
    k = gotoh_forward_pallas(a, b, lens, sub, gap_open=11, gap_extend=1,
                             local=True, block_rows=32)
    _, oref = gotoh_forward_ref(a, b, lens, sub, gap_open=11, gap_extend=1,
                                local=True)
    np.testing.assert_allclose(np.asarray(k.score), np.asarray(oref[:, 0]))


def test_traceback_through_kernel_dirs():
    a, b, lens = _case(3, 64, 64)
    sub = ab.dna_matrix().astype(jnp.float32)
    k = gotoh_forward_pallas(a, b, lens, sub, gap_open=3, gap_extend=1,
                             local=False, block_rows=32)
    for i in range(3):
        fwd = pw.ForwardResult(k.dirs[i], k.score[i], k.start_i[i],
                               k.start_j[i], k.start_state[i])
        ra, rb, kk = pw.traceback(a[i], b[i], fwd, ab.DNA.gap_code)
        dec = ab.DNA.decode(np.asarray(ra)[: int(kk)])
        assert dec.replace("-", "") == ab.DNA.decode(
            np.asarray(a[i])[: int(lens[i, 0])])


def _group_case(B, n, m, n_chars, seed):
    """B pairs with per-pair targets and mixed lengths: pair 0 fills every
    query row (la = n), pair 1 ends inside the first row block."""
    r = np.random.default_rng(seed)
    A = r.integers(0, n_chars, (B, n)).astype(np.int8)
    T = r.integers(0, n_chars, (B, m)).astype(np.int8)
    la = r.integers(5, n + 1, B)
    lb = r.integers(5, m + 1, B)
    la[0] = n
    if B > 1:
        la[1] = 7
    return (jnp.asarray(A), jnp.asarray(T),
            jnp.asarray(np.stack([la, lb], 1).astype(np.int32)))


def _assert_matches_oracle(a, b, lens, sub, *, go, ge, local, block):
    k = gotoh_forward_pallas(a, b, lens, sub, gap_open=go, gap_extend=ge,
                             local=local, block_rows=block)
    dref, oref = gotoh_forward_ref(a, b, lens, sub, gap_open=go,
                                   gap_extend=ge, local=local)
    out = np.stack([np.asarray(k.score), np.asarray(k.start_i),
                    np.asarray(k.start_j), np.asarray(k.start_state)], 1)
    np.testing.assert_array_equal(out.astype(np.float32),
                                  np.asarray(oref[:, :4]))
    for i in range(a.shape[0]):
        la, lb = int(lens[i, 0]), int(lens[i, 1])
        np.testing.assert_array_equal(np.asarray(k.dirs[i])[:la, :lb + 1],
                                      np.asarray(dref[i])[:la, :lb + 1])
    return k


def _assert_traceback_decodes(k, a, b, lens, alpha, local):
    for i in range(a.shape[0]):
        fwd = pw.ForwardResult(k.dirs[i], k.score[i], k.start_i[i],
                               k.start_j[i], k.start_state[i])
        ra, _, kk = pw.traceback(a[i], b[i], fwd, alpha.gap_code)
        got = alpha.decode(np.asarray(ra)[: int(kk)]).replace("-", "")
        query = alpha.decode(np.asarray(a[i])[: int(lens[i, 0])])
        if local:
            assert got in query
        else:
            assert got == query


@pytest.mark.parametrize("B", [1, 7, 8, 9, 17])
@pytest.mark.parametrize("local", [False, True])
def test_pair_groups_match_oracle(B, local):
    """Pairs share a program, one per sublane (a partial last group for
    B = 9, 17): every pair's directions, score and start are the oracle's
    bit for bit, and its traceback gives back its query."""
    a, b, lens = _group_case(B, 64, 80, 4, seed=B)
    sub = ab.dna_matrix().astype(jnp.float32)
    k = _assert_matches_oracle(a, b, lens, sub, go=3, ge=1, local=local,
                               block=32)
    _assert_traceback_decodes(k, a, b, lens, ab.DNA, local)


@pytest.mark.parametrize("local", [False, True])
def test_pair_groups_blosum62(local):
    a, b, lens = _group_case(9, 48, 56, 20, seed=62)
    sub = ab.blosum62().astype(jnp.float32)
    k = _assert_matches_oracle(a, b, lens, sub, go=11, ge=1, local=local,
                               block=32)
    _assert_traceback_decodes(k, a, b, lens, ab.PROTEIN, local)

"""Distribution runtime: checkpoint, fault loop, compression, collectives,
distributed MSA semantics on a trivial mesh (multi-device in
test_multidevice.py via subprocess)."""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import collectives as col
from repro.dist import shard_map
from repro.dist import grad_compression as gc
from repro.dist import mapreduce, sharding as sh
from repro.dist.checkpoint import CheckpointManager
from repro.dist.fault import BackupShardPlan, ResilientLoop, StepFailure
from repro.launch.mesh import make_local_mesh


def test_checkpoint_roundtrip_and_gc():
    state = {"w": jnp.arange(12.0).reshape(3, 4), "step": jnp.int32(7),
             "nested": {"b": jnp.ones(5)}}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=2)
        for s in (0, 10, 20):
            cm.save(s, state, block=True)
        assert cm.all_steps() == [10, 20]
        like = jax.tree.map(jnp.zeros_like, state)
        restored, step = cm.restore(like)
        assert step == 20
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.asarray(state["w"]))


def test_checkpoint_async_then_wait():
    state = {"w": jnp.ones((64, 64))}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=3, async_write=True)
        cm.save(1, state)
        cm.wait()
        assert cm.all_steps() == [1]


def test_elastic_restore_new_mesh():
    state = {"w": jnp.arange(16.0).reshape(4, 4)}
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d)
        cm.save(5, state, block=True)
        mesh = make_local_mesh((1,), ("data",))
        shardings = {"w": NamedSharding(mesh, P("data", None))}
        restored, _ = cm.restore(jax.tree.map(jnp.zeros_like, state),
                                 shardings=shardings)
        np.testing.assert_allclose(np.asarray(restored["w"]),
                                   np.asarray(state["w"]))


def test_resilient_loop_replays_from_checkpoint():
    with tempfile.TemporaryDirectory() as d:
        cm = CheckpointManager(d, keep=3, async_write=False)
        fails = {8: 1, 3: 1}

        def hook(step):
            if fails.get(step, 0) > 0:
                fails[step] -= 1
                raise StepFailure(f"injected at {step}")

        class Batches:
            n_steps = 12

            def __call__(self, step):
                return jnp.float32(1.0)

        loop = ResilientLoop(lambda s, b: {"w": s["w"] + b}, cm,
                             ckpt_every=5, failure_hook=hook)
        final, steps = loop.run({"w": jnp.float32(0.0)}, Batches())
        assert steps == 12
        assert float(final["w"]) == 12.0  # deterministic replay => exact


def test_backup_shard_plan_invariants():
    plan = BackupShardPlan(n_hosts=8, replication=3)
    for s in range(8):
        owners = plan.owners(s)
        assert len(set(owners)) == 3 and owners[0] == s
    for dead in range(8):
        for s, takeover in plan.reassignment(dead).items():
            assert takeover != dead and dead in plan.owners(s)


def test_grad_compression_accuracy_and_error_feedback():
    mesh = make_local_mesh((1,), ("data",))
    g = {"a": jnp.asarray(np.random.default_rng(0).normal(0, 1, (128,)),
                          jnp.float32)}
    ef = gc.init_ef(g)
    fn = shard_map(lambda g, e: gc.tree_compressed_psum_mean(g, "data", e),
                   mesh=mesh, in_specs=(P(), P()), out_specs=(P(), P()),
                   check_vma=False)
    mean, ef2 = fn(g, ef)
    scale = float(jnp.max(jnp.abs(g["a"]))) / 127.0
    assert float(jnp.max(jnp.abs(mean["a"] - g["a"]))) <= scale * 1.01
    # error feedback holds the quantization residual
    np.testing.assert_allclose(np.asarray(ef2["a"]),
                               np.asarray(g["a"] - mean["a"]), atol=1e-6)


def test_collective_matmul_matches_plain():
    mesh = make_local_mesh((1,), ("data",))
    x = jnp.asarray(np.random.default_rng(1).normal(0, 1, (4, 16)), jnp.float32)
    w = jnp.asarray(np.random.default_rng(2).normal(0, 1, (16, 8)), jnp.float32)
    fn = shard_map(lambda x, w: col.ag_matmul_overlap(x, w, "data"),
                   mesh=mesh, in_specs=(P(), P(None, "data")), out_specs=P(),
                   check_vma=False)
    np.testing.assert_allclose(np.asarray(fn(x, w)), np.asarray(x @ w),
                               rtol=1e-5)


def test_distributed_center_star_equals_host_version(dna_family):
    from repro.core import alphabet as ab
    from repro.core import kmer_index
    from repro.core.msa import MSAConfig, center_star_msa

    mesh = make_local_mesh((1, 1), ("data", "model"))
    seqs = dna_family[1:]           # queries
    center_s = dna_family[0]
    S, lens = ab.encode_batch(seqs, ab.DNA)
    center = jnp.asarray(ab.DNA.encode(center_s))
    lc = jnp.int32(len(center_s))
    table = kmer_index.build_center_index(center, lc, k=8)
    sub = ab.dna_matrix().astype(jnp.float32)

    progs = mapreduce.distributed_center_star(
        mesh, method="kmer", sub=sub, gap_code=ab.DNA.gap_code,
        out_len=400, num_slots=int(center.shape[0]) + 1, gap_open=3,
        gap_extend=1, k=8, max_anchors=96, max_seg=48)
    ops = (sh.shard_rows(S, mesh), sh.shard_rows(lens, mesh),
           sh.broadcast(center, mesh), lc)
    rows, G = progs.align(*ops, *progs.chain(*ops, sh.broadcast(table, mesh)))
    for s, r in zip(seqs, np.asarray(rows)):
        assert ab.DNA.decode(r).replace("-", "") == s


def test_sharding_helpers():
    mesh = make_local_mesh((1, 1), ("data", "model"))
    assert sh.axis_size(mesh, ("data", "model")) == 1
    assert sh.maybe(mesh, 7, "data") == "data"   # 7 % 1 == 0
    assert sh.first_fit(mesh, 8, "model", None) == "model"

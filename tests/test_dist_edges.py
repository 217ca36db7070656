"""Edge cases for the distribution runtime: checkpoint retention/restore
(empty dir, corrupt latest step, structure mismatch), fault-plan
takeover/reassignment under cascading failures, ResilientLoop retry
exhaustion, and the mapreduce padding path when the shard count does not
divide the sequence count."""
import json
import subprocess
import sys
import warnings
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import alphabet as ab
from repro.core import kmer_index
from repro.dist import mapreduce, sharding as sh
from repro.dist.checkpoint import CheckpointManager
from repro.dist.fault import BackupShardPlan, ResilientLoop, StepFailure
from repro.launch.mesh import make_local_mesh


# ------------------------------------------------------------- checkpoints

def test_restore_empty_dir_raises(tmp_path):
    cm = CheckpointManager(tmp_path)
    assert cm.all_steps() == []
    assert cm.latest_step() is None
    with pytest.raises(FileNotFoundError):
        cm.restore({"w": jnp.zeros(3)})


def test_restore_skips_corrupt_latest(tmp_path):
    cm = CheckpointManager(tmp_path)
    state = {"w": jnp.arange(6.0)}
    cm.save(10, state, block=True)
    cm.save(20, {"w": state["w"] * 2}, block=True)
    cm._path(20).write_bytes(b"not an npz file")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        restored, step = cm.restore({"w": jnp.zeros(6)})
    assert step == 10
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.asarray(state["w"]))
    # explicitly requesting the corrupt step is strict
    with pytest.raises(Exception):
        cm.restore({"w": jnp.zeros(6)}, step=20)


def test_restore_skips_structure_mismatch(tmp_path):
    cm = CheckpointManager(tmp_path)
    cm.save(1, {"w": jnp.ones(4)}, block=True)
    cm.save(2, {"w": jnp.ones(4), "extra": jnp.ones(2)}, block=True)
    cm.save(3, {"w": jnp.ones(7)}, block=True)        # wrong shape
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        _, step = cm.restore({"w": jnp.zeros(4)})
    assert step == 1
    with pytest.raises(ValueError):
        cm.restore({"w": jnp.zeros(4)}, step=3)       # strict on explicit step


def test_retention_keep_one(tmp_path):
    cm = CheckpointManager(tmp_path, keep=1)
    for s in (1, 2, 3):
        cm.save(s, {"w": jnp.full(2, float(s))}, block=True)
    assert cm.all_steps() == [3]
    _, step = cm.restore({"w": jnp.zeros(2)})
    assert step == 3


# ----------------------------------------------------------- fault plans

def test_takeover_when_backup_owner_also_dead():
    plan = BackupShardPlan(n_hosts=4, replication=3)
    assert plan.owners(0) == [0, 1, 2]
    assert plan.takeover(0, 0) == 1             # single failure (int form)
    assert plan.takeover({0, 1}, 0) == 2        # backup owner dead too
    assert plan.takeover([1, 0], 0) == 2        # any iterable, any order
    assert plan.takeover({0, 1, 2}, 0) is None  # every replica gone
    # an unaffected shard still answers with its primary
    assert plan.takeover({0, 1, 2}, 3) == 3


def test_reassignment_after_cascading_failures():
    plan = BackupShardPlan(n_hosts=4, replication=2)
    out = plan.reassignment({0, 1})
    # shard 0's owners (0, 1) are both dead: it must be ABSENT, not
    # silently mapped to a dead host
    assert 0 not in out
    assert out == {1: 2, 3: 3}
    for s, h in out.items():
        assert h not in {0, 1}
        assert h in plan.owners(s)
    # the cascade is strictly worse than either single failure
    assert set(out) < set(plan.reassignment(0)) | set(plan.reassignment(1))


def test_reassignment_replication_one_drops_dead_shard():
    plan = BackupShardPlan(n_hosts=3, replication=1)
    assert plan.reassignment(1) == {}           # no replica to take over
    assert plan.reassignment({0, 1, 2}) == {}


def test_resilient_loop_retry_exhaustion(tmp_path):
    """A fault that persists across replays must surface after
    max_failures replays instead of looping forever."""
    class Batches:
        n_steps = 3

        def __call__(self, step):
            return step

    def always_fail(step):
        if step == 1:
            raise StepFailure("persistent fault")

    loop = ResilientLoop(lambda s, b: s + 1, CheckpointManager(tmp_path),
                         ckpt_every=1, failure_hook=always_fail,
                         max_failures=2)
    with pytest.raises(StepFailure, match="persistent fault"):
        loop.run(jnp.int32(0), Batches())


def test_resilient_loop_failure_without_checkpoint_raises(tmp_path):
    """ckpt_every=0 never saved — a StepFailure has nothing to replay
    from and must propagate immediately."""
    class Batches:
        n_steps = 2

        def __call__(self, step):
            return step

    def fail_first(step):
        raise StepFailure("no checkpoint to fall back to")

    loop = ResilientLoop(lambda s, b: s + 1, CheckpointManager(tmp_path),
                         ckpt_every=0, failure_hook=fail_first)
    with pytest.raises(StepFailure):
        loop.run(jnp.int32(0), Batches())


# ------------------------------------------------- mapreduce shard padding

def test_pad_rows_roundtrip():
    x = np.arange(10).reshape(5, 2)
    padded, n = mapreduce.pad_rows(x, 4)
    assert padded.shape == (8, 2) and n == 5
    np.testing.assert_array_equal(mapreduce.unpad_rows(padded, n), x)
    same, n2 = mapreduce.pad_rows(x, 5)
    assert same.shape == (5, 2) and n2 == 5


def test_padded_queries_align_as_all_gap(dna_family):
    """Empty-query padding rows must produce all-gap output rows and leave
    the merged profile untouched (checked on a 1-device mesh by feeding the
    padded batch directly)."""
    mesh = make_local_mesh((1, 1), ("data", "model"))
    seqs = dna_family[1:4]
    center_s = dna_family[0]
    S, lens = ab.encode_batch(seqs, ab.DNA)
    Q, n_q = mapreduce.pad_rows(np.asarray(S), 4)
    qlens, _ = mapreduce.pad_rows(np.asarray(lens), 4)
    assert Q.shape[0] == 4 and n_q == 3
    center = jnp.asarray(ab.DNA.encode(center_s))
    lc = jnp.int32(len(center_s))
    table = kmer_index.build_center_index(center, lc, k=8)
    progs = mapreduce.distributed_center_star(
        mesh, method="kmer", sub=ab.dna_matrix().astype(jnp.float32),
        gap_code=ab.DNA.gap_code, out_len=400,
        num_slots=int(center.shape[0]) + 1, gap_open=3, gap_extend=1, k=8,
        max_anchors=96, max_seg=48)
    ops = (sh.shard_rows(Q, mesh), sh.shard_rows(qlens, mesh),
           sh.broadcast(center, mesh), lc)
    rows, G = progs.align(*ops, *progs.chain(*ops, sh.broadcast(table, mesh)))
    rows = np.asarray(rows)
    for s, r in zip(seqs, rows[:n_q]):
        assert ab.DNA.decode(r).replace("-", "") == s
    assert (rows[n_q:] == ab.DNA.gap_code).all()          # padding -> all gap


def test_shard_rows_rejects_nondividing():
    mesh = make_local_mesh((1, 1), ("data", "model"))
    ok = sh.shard_rows(np.zeros((3, 2), np.int8), mesh)   # 3 % 1 == 0
    assert ok.shape == (3, 2)


SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
import sys
sys.path.insert(0, %r)
import json
import numpy as np
from repro.core.msa import MSAConfig, center_star_msa, decode_msa
from repro.dist import mapreduce
from repro.launch.mesh import make_local_mesh

rng = np.random.default_rng(7)
base = "".join(rng.choice(list("ACGT"), 80))
def mut(s):
    s = list(s)
    for _ in range(3):
        i = rng.integers(0, len(s)); s[i] = "ACGT"[rng.integers(0, 4)]
    return "".join(s)
seqs = [base] + [mut(base) for _ in range(5)]   # 5 queries over 2 shards
cfg = MSAConfig(method="kmer", k=8, max_anchors=64, max_seg=48)
mesh = make_local_mesh((2, 1), ("data", "model"))
res = mapreduce.msa_over_mesh(seqs, cfg, mesh)
host = center_star_msa(seqs, cfg)
rows = decode_msa(res.msa, cfg)
ok = all(r.replace("-", "") == s for s, r in zip(seqs, rows))
print("RESULT " + json.dumps({
    "ok": bool(ok), "n": len(rows), "width": int(res.width),
    "host_width": int(host.width)}))
'''


def test_mapreduce_nondividing_sequence_count_two_shards():
    """5 queries over 2 shards (padded to 6): distributed result must decode
    to the inputs and match the host pipeline's width."""
    src = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT % src],
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["ok"]
    assert out["n"] == 6
    assert out["width"] == out["host_width"]

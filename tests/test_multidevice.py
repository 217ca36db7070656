"""Real multi-device checks via subprocess (8 forced host devices):
distributed MSA == single-device result; sharded train step; elastic
restore across mesh shapes. Kept in a subprocess so the main pytest process
stays at the true device count."""
import json
import subprocess
import sys
from pathlib import Path

SCRIPT = r'''
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

sys_path = %r
import sys
sys.path.insert(0, sys_path)

from repro.core import alphabet as ab, kmer_index
from repro.core.msa import MSAConfig, center_star_msa
from repro.dist import mapreduce, sharding as sh
from repro.launch.mesh import make_local_mesh

assert jax.device_count() == 8

out = {}
# ---- distributed MSA on 4x2 mesh == host result
rng = np.random.default_rng(0)
base = "".join(rng.choice(list("ACGT"), 256))
def mut(s):
    s = list(s)
    for _ in range(4):
        i = rng.integers(0, len(s)); s[i] = "ACGT"[rng.integers(0, 4)]
    return "".join(s)
seqs = [mut(base) for _ in range(16)]
S, lens = ab.encode_batch(seqs, ab.DNA)
center = jnp.asarray(ab.DNA.encode(base)); lc = jnp.int32(len(base))
table = kmer_index.build_center_index(center, lc, k=8)
sub = ab.dna_matrix().astype(jnp.float32)
mesh = make_local_mesh((4, 2), ("data", "model"))
progs = mapreduce.distributed_center_star(
    mesh, method="kmer", sub=sub, gap_code=ab.DNA.gap_code, out_len=300,
    num_slots=int(center.shape[0]) + 1, gap_open=3, gap_extend=1, k=8,
    max_anchors=64, max_seg=48)
ops = (sh.shard_rows(S, mesh), sh.shard_rows(lens, mesh),
       sh.broadcast(center, mesh), lc)
rows, G = progs.align(*ops, *progs.chain(*ops, sh.broadcast(table, mesh)))
ok = all(ab.DNA.decode(r).replace("-", "") == s
         for s, r in zip(seqs, np.asarray(rows)))
out["msa_distributed_ok"] = bool(ok)
out["msa_sharding"] = str(rows.sharding.spec)

# ---- sharded train step on 4x2 mesh (FSDP x TP), smoke config
from repro.configs import get_arch
from repro.models import sharding_plan as sp
from repro.models.transformer import init_params
from repro.train.train_step import init_state, make_train_step
from repro.train.optimizer import AdamWConfig
import functools

cfg = get_arch("llama3.2-1b").smoke
key = jax.random.PRNGKey(0)
state_shape = jax.eval_shape(functools.partial(init_state, cfg), key)
pspecs = sp.params_pspecs(state_shape.params, mesh)
psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                   is_leaf=lambda x: isinstance(x, P))
from repro.train.train_step import TrainState
from repro.train import optimizer as opt
state_sh = TrainState(params=psh,
                      opt=opt.OptState(m=psh, v=psh, count=NamedSharding(mesh, P())),
                      step=NamedSharding(mesh, P()))
shard_fns = sp.make_shard_fns(cfg, mesh, 8)
fn2 = make_train_step(cfg, AdamWConfig(lr=1e-3), microbatches=2,
                      shard_fns=shard_fns)
jitted = jax.jit(fn2, in_shardings=(state_sh, None), out_shardings=(state_sh, None))
state = init_state(cfg, key)
state = jax.device_put(state, state_sh)
batch = {"tokens": jax.random.randint(key, (8, 32), 0, cfg.vocab_size),
         "labels": jax.random.randint(key, (8, 32), 0, cfg.vocab_size)}
losses = []
for _ in range(3):
    state, m = jitted(state, batch)
    losses.append(float(m["loss"]))
out["train_losses"] = losses
out["train_ok"] = bool(losses[-1] < losses[0])

# ---- elastic: save on 4x2, restore on 8x1
import tempfile
from repro.dist.checkpoint import CheckpointManager
with tempfile.TemporaryDirectory() as d:
    cm = CheckpointManager(d)
    cm.save(1, state.params, block=True)
    mesh2 = make_local_mesh((8, 1), ("data", "model"))
    pspecs2 = sp.params_pspecs(state_shape.params, mesh2)
    psh2 = jax.tree.map(lambda s: NamedSharding(mesh2, s), pspecs2,
                        is_leaf=lambda x: isinstance(x, P))
    restored, _ = cm.restore(jax.tree.map(
        lambda x: jnp.zeros(x.shape, x.dtype), state.params), shardings=psh2)
    w_old = np.asarray(jax.tree.leaves(state.params)[0])
    w_new = np.asarray(jax.tree.leaves(restored)[0])
    out["elastic_ok"] = bool(np.allclose(w_old, w_new))

print("RESULT " + json.dumps(out))
'''


def test_multidevice_subprocess():
    src = str(Path(__file__).resolve().parent.parent / "src")
    proc = subprocess.run([sys.executable, "-c", SCRIPT % src],
                          capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["msa_distributed_ok"]
    assert out["train_ok"], out["train_losses"]
    assert out["elastic_ok"]

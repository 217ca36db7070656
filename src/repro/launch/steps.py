"""Step builders for the dry-run and launchers: per (arch x shape x mesh),
produce the jitted step function plus ShapeDtypeStruct stand-ins for every
input (weak-type-correct, shardable, zero allocation).
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs import SHAPES, get_arch, shape_applicable
from ..models import sharding_plan as sp
from ..models.transformer import init_cache, init_params
from ..train import optimizer as opt
from ..train.optimizer import AdamWConfig
from ..train.serve_step import make_decode_step, make_prefill_step
from ..train.train_step import TrainState, init_state, make_train_step


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def input_specs(arch_id: str, shape_name: str) -> Dict[str, Any]:
    """ShapeDtypeStructs for the model inputs of this (arch, shape) cell."""
    cfg = get_arch(arch_id).config
    shape = SHAPES[shape_name]
    B, S = shape.global_batch, shape.seq_len
    specs: Dict[str, Any] = {}
    if shape.kind in ("train", "prefill"):
        if cfg.embed_input:
            specs["tokens"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
        else:
            specs["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                                   jnp.bfloat16)
        if cfg.m_rope:
            specs["pos3"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        if shape.kind == "train":
            specs["labels"] = jax.ShapeDtypeStruct((B, S), jnp.int32)
    else:  # decode
        if cfg.embed_input:
            specs["token"] = jax.ShapeDtypeStruct((B,), jnp.int32)
        else:
            specs["token"] = jax.ShapeDtypeStruct((B, cfg.d_model), jnp.bfloat16)
        specs["pos"] = jax.ShapeDtypeStruct((B,), jnp.int32)
    return specs


def microbatches_for(arch_id: str, shape_name: str, mesh) -> int:
    spec = get_arch(arch_id)
    mu = spec.microbatch_overrides.get(shape_name, 1)
    shape = SHAPES[shape_name]
    dp = sp._dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    while mu > 1 and (shape.global_batch // mu) % dp_size != 0:
        mu //= 2
    return max(mu, 1)


def build_step(arch_id: str, shape_name: str, mesh, *,
               adamw: AdamWConfig = AdamWConfig(), roofline: bool = False):
    """Returns (jitted_fn, args_tuple_of_SDS, out_shardings_info).

    roofline=True unrolls the layer scan and forces microbatches=1 so
    cost_analysis / collective parses count every layer exactly once per
    step; benchmarks/roofline.py multiplies back the microbatch factor.
    """
    import dataclasses as _dc
    spec = get_arch(arch_id)
    cfg = spec.config
    if roofline:
        cfg = _dc.replace(cfg, unroll_layers=True)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        raise ValueError(f"{arch_id} x {shape_name} skipped: {why}")

    key = jax.random.PRNGKey(0)
    B = shape.global_batch
    batch_sds = input_specs(arch_id, shape_name)
    shard_fns = sp.make_shard_fns(cfg, mesh, B)

    params_shape = jax.eval_shape(functools.partial(init_params, cfg), key)
    pspecs = sp.params_pspecs(params_shape, mesh)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                       is_leaf=lambda x: isinstance(x, P))

    if shape.kind == "train":
        # roofline keeps the production microbatch count: the micro-scan body
        # (counted once by cost_analysis) is homogeneous, so benchmarks/
        # roofline.py multiplies the step totals by mu exactly.
        mu = microbatches_for(arch_id, shape_name, mesh)
        state_shape = jax.eval_shape(functools.partial(init_state, cfg), key)
        state_sh = TrainState(
            params=psh,
            opt=opt.OptState(m=psh, v=psh,
                             count=NamedSharding(mesh, P())),
            step=NamedSharding(mesh, P()))
        bspecs = sp.batch_pspecs(cfg, "train", B, mesh, batch_sds)
        bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
        fn = make_train_step(cfg, adamw, microbatches=mu, shard_fns=shard_fns,
                             grad_shardings=psh)
        jitted = jax.jit(fn, in_shardings=(state_sh, bsh),
                         out_shardings=(state_sh, None))
        return jitted, (_sds(state_shape), batch_sds)

    if shape.kind == "prefill":
        bspecs = sp.batch_pspecs(cfg, "prefill", B, mesh, batch_sds)
        bsh = {k: NamedSharding(mesh, v) for k, v in bspecs.items()}
        cache_shape = jax.eval_shape(
            functools.partial(init_cache, cfg, B, shape.seq_len))
        csh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                           sp.cache_pspecs(cfg, cache_shape, B, mesh),
                           is_leaf=lambda x: isinstance(x, P))
        fn = make_prefill_step(cfg, shard_fns=shard_fns, max_len=shape.seq_len)
        if not cfg.has_decode:
            # encoder: full forward, no cache output
            from ..models.transformer import apply_model

            def enc_fn(params, batch):
                logits, _, _ = apply_model(params, cfg, batch,
                                           shard_fns=shard_fns)
                return logits
            jitted = jax.jit(enc_fn, in_shardings=(psh, bsh),
                             out_shardings=None)
        else:
            jitted = jax.jit(fn, in_shardings=(psh, bsh),
                             out_shardings=(None, csh))
        return jitted, (params_shape, batch_sds)

    # decode
    cache_shape = jax.eval_shape(
        functools.partial(init_cache, cfg, B, shape.seq_len))
    csh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                       sp.cache_pspecs(cfg, cache_shape, B, mesh),
                       is_leaf=lambda x: isinstance(x, P))
    dp = sp._dp_axes(mesh)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    tok_ax = dp if B % dp_size == 0 else None
    tok_sh = NamedSharding(mesh, P(tok_ax)) if cfg.embed_input else \
        NamedSharding(mesh, P(tok_ax, None))
    pos_sh = NamedSharding(mesh, P(tok_ax))
    fn = make_decode_step(cfg, shard_fns=shard_fns)
    jitted = jax.jit(fn, in_shardings=(psh, csh, tok_sh, pos_sh),
                     out_shardings=(None, csh))
    sds = input_specs(arch_id, shape_name)
    return jitted, (params_shape, _sds(cache_shape), sds["token"], sds["pos"])


# --------------------------------------------------------- MSA (paper) cells

MSA_CELLS = {
    # name: (N sequences, padded length, method, alphabet, k, map_chunks)
    "halign-dna-1000x": (671744, 16576, "kmer", "dna", 11, 1),
    "halign-rna-large": (1011712, 1600, "kmer", "dna", 11, 1),
    "halign-protein-100x": (1789952, 512, "sw", "protein", 0, 1),
    # §Perf variants: local shard processed in sequential chunks to bound
    # per-device temp memory (before/after recorded in EXPERIMENTS.md)
    "halign-dna-1000x-chunked": (671744, 16576, "kmer", "dna", 11, 8),
    "halign-protein-100x-chunked": (1789952, 512, "sw", "protein", 0, 8),
}


def build_msa_step(cell: str, mesh):
    """The distributed center-star MSA's DP + reduce(1) + map(2) program
    (the paper's own workload; it holds the pipeline's one collective) and
    its argument shapes, the k-mer method's chained rows from
    ``jax.eval_shape`` of the chain program."""
    import jax.numpy as jnp

    from ..core import alphabet as ab
    from ..dist import mapreduce

    N, L, method, alpha_name, k, map_chunks = MSA_CELLS[cell]
    alpha = ab.PROTEIN if alpha_name == "protein" else ab.DNA
    sub = (ab.blosum62() if alpha_name == "protein"
           else ab.dna_matrix()).astype(jnp.float32)
    out_len = L + 4096
    fn = mapreduce.distributed_center_star(
        mesh, method=method, sub=sub, gap_code=alpha.gap_code,
        out_len=out_len, num_slots=L + 1,
        gap_open=11 if alpha_name == "protein" else 3, gap_extend=1,
        k=k or 11, max_anchors=256, max_seg=64, map_chunks=map_chunks)
    Q = jax.ShapeDtypeStruct((N, L), jnp.int8)
    lens = jax.ShapeDtypeStruct((N,), jnp.int32)
    center = jax.ShapeDtypeStruct((L,), jnp.int8)
    lc = jax.ShapeDtypeStruct((), jnp.int32)
    if method == "kmer":
        table = jax.ShapeDtypeStruct((4 ** (k or 11), 4), jnp.int32)
        chained = jax.eval_shape(fn.chain, Q, lens, center, lc, table)
        return fn.align, (Q, lens, center, lc, *chained)
    return fn.align, (Q, lens, center, lc)

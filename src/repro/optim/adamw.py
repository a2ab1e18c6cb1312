"""AdamW in pure JAX with ZeRO-1 sharded state (DESIGN §3).

Data-replicated parameters (bf16) keep fp32 master/m/v only on their
per-leaf reduce-scatter shard: the executor emits per-leaf grad shards, the
optimizer updates each shard and all-gathers the refreshed bf16 leaf.
Data-sharded leaves (EP/TP experts) update locally with their own m/v
(configurable dtype — bf16 keeps grok's 314B state in budget).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.pipeline.sharding import ParamPartition


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    expert_state_dtype: Any = jnp.float32


def lr_at(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(1.0, (step + 1) / max(cfg.warmup_steps, 1))
    prog = jnp.clip(
        (step - cfg.warmup_steps) / max(cfg.total_steps - cfg.warmup_steps, 1),
        0.0, 1.0)
    cos = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (1 + jnp.cos(np.pi * prog))
    return cfg.lr * warm * cos


def _adamw_update(cfg: AdamWConfig, p, g, m, v, step, lr, scale=1.0):
    g = g.astype(jnp.float32) * scale
    m = cfg.beta1 * m + (1 - cfg.beta1) * g
    v = cfg.beta2 * v + (1 - cfg.beta2) * g * g
    mh = m / (1 - cfg.beta1 ** (step + 1))
    vh = v / (1 - cfg.beta2 ** (step + 1))
    upd = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p
    return p - lr * upd, m, v


# ---------------------------------------------------------------------------
def make_host_update(opt_cfg: AdamWConfig):
    """Jitted single-pytree AdamW step for the host actor runtimes.

    ``apply_update(params, grads, m, v, step) -> (params, m, v, lr)`` —
    unsharded, any params/grads pytree (heterogeneous per-stage trees
    included).  Master arithmetic in float32; params cast back to their
    own dtype.
    """

    @jax.jit
    def apply_update(params, grads, m, v, step):
        lr = lr_at(opt_cfg, step)

        def upd(p, g, m_, v_):
            p32, m2, v2 = _adamw_update(
                opt_cfg, p.astype(jnp.float32), g.astype(jnp.float32),
                m_, v_, step, lr)
            return p32.astype(p.dtype), m2, v2

        out = jax.tree.map(upd, params, grads, m, v)
        tup = lambda o: isinstance(o, tuple)  # noqa: E731
        return (jax.tree.map(lambda o: o[0], out, is_leaf=tup),
                jax.tree.map(lambda o: o[1], out, is_leaf=tup),
                jax.tree.map(lambda o: o[2], out, is_leaf=tup), lr)

    return apply_update


# ---------------------------------------------------------------------------
def make_optimizer(model, mesh, partition: ParamPartition, opt_cfg: AdamWConfig,
                   dp_axes: tuple = ("data",)):
    """Returns (init_fn, update_fn) for the per-leaf ZeRO-1 optimizer."""
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    S = model.num_stages
    flags = partition.stage_data_sharded

    stage_leaves = list(
        jax.tree_util.tree_leaves_with_path(partition.stage_specs))
    flag_leaves = [f for _, f in
                   jax.tree_util.tree_leaves_with_path(flags)]
    io_leaves = list(jax.tree_util.tree_leaves_with_path(partition.io_specs))
    shard_keys = [jax.tree_util.keystr(p) for (p, _), f in
                  zip(stage_leaves, flag_leaves) if not f]
    shard_keys += ["io:" + jax.tree_util.keystr(p) for p, _ in io_leaves]
    expert_keys = [jax.tree_util.keystr(p) for (p, _), f in
                   zip(stage_leaves, flag_leaves) if f]

    def _dp_index():
        idx = jax.lax.axis_index(dp_axes[0])
        for a in dp_axes[1:]:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    def _my_shard(leaf):
        v = leaf.astype(jnp.float32).reshape(-1)
        v = jnp.pad(v, (0, (-v.size) % dp_total))
        return v.reshape(dp_total, -1)[_dp_index()]

    def _leaf_items(sp, io):
        """(key, leaf) pairs in executor grad-shard order."""
        items = []
        for (path, leaf), flag in zip(
                jax.tree_util.tree_leaves_with_path(sp), flag_leaves):
            if not flag:
                items.append((jax.tree_util.keystr(path), leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(io):
            items.append(("io:" + jax.tree_util.keystr(path), leaf))
        return items

    # ---------------- init --------------------------------------------
    def device_init(stage_params, io):
        sp = jax.tree.map(lambda x: x[0], stage_params)
        shards = {}
        for k, leaf in _leaf_items(sp, io):
            m0 = _my_shard(leaf)
            shards[k] = {
                "master": m0[None],
                "m": jnp.zeros_like(m0)[None],
                "v": jnp.zeros_like(m0)[None],
            }
        experts = {}
        for (path, leaf), flag in zip(
                jax.tree_util.tree_leaves_with_path(sp), flag_leaves):
            if flag:
                k = jax.tree_util.keystr(path)
                experts[k] = {
                    "m": jnp.zeros(leaf.shape, opt_cfg.expert_state_dtype)[None],
                    "v": jnp.zeros(leaf.shape, opt_cfg.expert_state_dtype)[None],
                }
        return {"shards": shards, "experts": experts}

    expert_specs = {
        jax.tree_util.keystr(path): spec
        for (path, spec), flag in zip(stage_leaves, flag_leaves) if flag
    }
    shard_spec = P("model", dp_axes)
    state_specs = {
        "shards": {k: {"master": shard_spec, "m": shard_spec, "v": shard_spec}
                   for k in shard_keys},
        "experts": {k: {"m": s, "v": s} for k, s in expert_specs.items()},
    }

    init_fn = jax.shard_map(
        device_init, mesh=mesh,
        in_specs=(partition.stage_specs, partition.io_specs),
        out_specs=state_specs, check_vma=False)

    # ---------------- update ------------------------------------------
    def device_update(stage_params, io, opt_state, grad_shards, expert_grads,
                      step):
        sp = jax.tree.map(lambda x: x[0], stage_params)
        lr = lr_at(opt_cfg, step)

        # global grad norm: stage segments distinct across model rows; io
        # segments replicated across rows (weight 1/S).
        sq = jnp.zeros((), jnp.float32)
        for k in shard_keys:
            g = grad_shards[k][0].astype(jnp.float32)
            w = 1.0 / S if k.startswith("io:") else 1.0
            sq = sq + w * jnp.sum(g * g)
        for k in expert_keys:
            eg = expert_grads[k][0].astype(jnp.float32)
            sq = sq + jnp.sum(eg * eg)
        gnorm = jnp.sqrt(jax.lax.psum(sq, ("model",) + dp_axes))
        scale = jnp.minimum(1.0, opt_cfg.grad_clip / (gnorm + 1e-12))

        # per-leaf shard update + all-gather refreshed bf16 leaves
        new_shards = {}
        new_leaves = {}
        for k, leaf in _leaf_items(sp, io):
            st = opt_state["shards"][k]
            mast, mn, vn = _adamw_update(
                opt_cfg, st["master"][0], grad_shards[k][0], st["m"][0],
                st["v"][0], step, lr, scale)
            new_shards[k] = {"master": mast[None], "m": mn[None], "v": vn[None]}
            full = jax.lax.all_gather(
                mast.astype(leaf.dtype), dp_axes, tiled=True)
            new_leaves[k] = full[: leaf.size].reshape(leaf.shape)

        new_experts = {}
        expert_leaves = {}
        for (path, leaf), flag in zip(
                jax.tree_util.tree_leaves_with_path(sp), flag_leaves):
            if not flag:
                continue
            k = jax.tree_util.keystr(path)
            st = opt_state["experts"][k]
            pn, mn, vn = _adamw_update(
                opt_cfg, leaf.astype(jnp.float32), expert_grads[k][0],
                st["m"][0].astype(jnp.float32),
                st["v"][0].astype(jnp.float32), step, lr, scale)
            expert_leaves[k] = pn.astype(leaf.dtype)
            new_experts[k] = {
                "m": mn.astype(opt_cfg.expert_state_dtype)[None],
                "v": vn.astype(opt_cfg.expert_state_dtype)[None],
            }

        def rebuild_sp(path, leaf):
            k = jax.tree_util.keystr(path)
            if k in expert_leaves:
                return expert_leaves[k]
            return new_leaves[k]

        sp_new = jax.tree_util.tree_map_with_path(rebuild_sp, sp)
        io_new = jax.tree_util.tree_map_with_path(
            lambda p, l: new_leaves["io:" + jax.tree_util.keystr(p)], io)
        new_state = {"shards": new_shards, "experts": new_experts}
        stats = {"gnorm": gnorm, "lr": lr}
        return (jax.tree.map(lambda x: x[None], sp_new), io_new, new_state,
                stats)

    grad_specs = {k: shard_spec for k in shard_keys}
    update_fn = jax.shard_map(
        device_update, mesh=mesh,
        in_specs=(partition.stage_specs, partition.io_specs, state_specs,
                  grad_specs, expert_specs, P()),
        out_specs=(partition.stage_specs, partition.io_specs, state_specs,
                   {"gnorm": P(), "lr": P()}),
        check_vma=False)
    return init_fn, update_fn

"""Schedule-table-driven SPMD pipeline executor (DESIGN §2).

One compiled ``train_step`` executes ANY valid ScheduleTable (1F1B, GPipe,
ZB-lite, RRFP-synthesized): per tick each stage looks up its (op, microbatch)
entry and `lax.switch`es into F / B / W / idle.  Activations and gradients
move on ring collective-permutes (one hop per tick) into slotted on-device
buffers — the compiled analog of the paper's four per-stage message buffers;
buffer capacities come from the table validator (= the App. C limit).

Backward is remat-based: B re-runs the stage forward under ``jax.grad`` of a
scalarized objective (CE at the last stage, <y, g_in> elsewhere), so no
activation stack is kept beyond each microbatch's stage input.

Named scopes put the device trace's ops under the tick's op (``tick.F``,
``tick.B``, ``tick.W``, ``tick.idle``), the ring exchange (``tick.exchange``)
and, inside an op, ``embed``, ``layers``, ``ce_loss`` and a backward's
``recompute``.

Collective-order consistency across a stage row (the paper's §4.2 constraint)
holds by construction: the table is uniform across the ``data`` axis, so all
ranks of a "TP group" (here: a data row) enter identical branches — data-axis
collectives (MoE all_to_all / vocab CE) are safe inside branches.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.build import ArchModel
from repro.pipeline.sharding import ParamPartition, partition_for
from repro.pipeline.spec import OP_B, OP_F, OP_IDLE, OP_W, ScheduleTable
from repro.pipeline.stagefn import chunked_ce_sum, default_ce_chunk


@dataclasses.dataclass(frozen=True)
class ExecOptions:
    mb_rows: int            # microbatch rows per data shard
    seq_len: int            # decoder/self-attn token length per row
    enc_len: int = 0        # encoder frames (enc-dec archs)
    grad_dtype: Any = jnp.float32   # stage-grad accumulators
    io_grad_dtype: Any = jnp.bfloat16  # embed/head accumulators (huge)
    flat_dtype: Any = jnp.bfloat16  # ZeRO-1 reduce-scatter payload
    ce_chunk: int = 0       # 0 -> auto from vocab size
    loss_scale: float = 1.0  # applied to the backward seed
    dp_axes: tuple = ("data",)
    multi_pod: bool = False

    @property
    def all_dp_axes(self) -> tuple:
        return (("pod",) + self.dp_axes) if self.multi_pod else self.dp_axes


def _eff_seq(model: ArchModel, opts: ExecOptions) -> int:
    return opts.seq_len + (opts.enc_len if model.cfg.encoder_layers else 0)


def _ce_chunk(model: ArchModel, opts: ExecOptions) -> int:
    return default_ce_chunk(model.cfg, opts.ce_chunk)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------
def make_train_fn(
    model: ArchModel,
    table: ScheduleTable,
    mesh,
    opts: ExecOptions,
    partition: ParamPartition,
):
    """Returns fn(stage_params, io_params, batch) -> (metrics, grad_shard,
    expert_grads) as a shard_map over the production mesh.

    ``grad_shard`` is the ZeRO-1 reduce-scattered flat fp32 vector of all
    data-replicated grads (stage + io); ``expert_grads`` holds the
    data-sharded leaves (EP/TP experts), locally reduced by construction.
    """
    cfg = model.cfg
    S = model.num_stages
    occ = table.validate()
    K_act = max(1, occ["act_span"])
    K_res = max(1, occ["res_span"])
    K_grad = max(1, occ["grad_span"])
    M = table.spec.num_microbatches
    T = table.num_ticks
    eff_seq = _eff_seq(model, opts)
    d = cfg.d_model
    mb_rows = opts.mb_rows
    ce_chunk = _ce_chunk(model, opts)
    dp_axes = opts.all_dp_axes
    dp_total = int(np.prod([mesh.shape[a] for a in dp_axes]))
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    bwd_perm = [(i, i - 1) for i in range(1, S)]
    ops_arr = jnp.asarray(table.ops, jnp.int32)
    mbs_arr = jnp.asarray(table.mbs, jnp.int32)
    rows_all = {k: jnp.asarray(v) for k, v in model.all_rows().items()}
    data_size = mesh.shape["data"]

    def device_fn(stage_params, io, batch):
        stage = jax.lax.axis_index("model")
        sp = jax.tree.map(lambda x: x[0], stage_params)  # drop stage dim
        rows = {k: v[stage] for k, v in rows_all.items()}
        tokens = batch["tokens"]  # [B_loc, seq]
        labels = batch["labels"]
        aux: dict[str, Any] = {
            "positions": jnp.broadcast_to(
                jnp.arange(eff_seq, dtype=jnp.int32)[None], (mb_rows, eff_seq)),
            "data_size": data_size,
            "moe_layout": model.moe_layout,
        }
        if cfg.encoder_layers:
            aux["dec_len"] = opts.seq_len

        def batch_mb(mb):
            out = {
                "tokens": jax.lax.dynamic_slice(
                    tokens, (mb * mb_rows, 0), (mb_rows, opts.seq_len)),
                "labels": jax.lax.dynamic_slice(
                    labels, (mb * mb_rows, 0), (mb_rows, opts.seq_len)),
            }
            if "embeds" in batch:
                e = batch["embeds"]
                out["embeds"] = jax.lax.dynamic_slice(
                    e, (mb * mb_rows, 0, 0), (mb_rows,) + e.shape[1:])
            if "mrope" in batch:
                mr = batch["mrope"]
                out["mrope"] = jax.lax.dynamic_slice(
                    mr, (0, mb * mb_rows, 0), (3, mb_rows, mr.shape[2]))
            if "enc_embeds" in batch:
                e = batch["enc_embeds"]
                out["enc_embeds"] = jax.lax.dynamic_slice(
                    e, (mb * mb_rows, 0, 0), (mb_rows,) + e.shape[1:])
            return out

        def aux_mb(bm):
            a = dict(aux)
            if "mrope" in bm:
                a["mrope"] = bm["mrope"]
            return a

        @jax.named_scope("embed")
        def pipeline_embed(io_, bm):
            if cfg.embed_input:
                x = bm["embeds"].astype(cfg.dtype)
            else:
                x = io_["embed"][bm["tokens"]]
            if cfg.encoder_layers:
                x = jnp.concatenate(
                    [x, bm["enc_embeds"].astype(cfg.dtype)], axis=1)
            return x

        @jax.named_scope("layers")
        def layers(sp_, io_, x, a):
            return model.stage_forward(sp_, io_, x, a, rows)

        def loss_of(io_, y, bm):
            if cfg.encoder_layers:
                y = y[:, : opts.seq_len]
            return chunked_ce_sum(model, io_, y, bm["labels"], ce_chunk)

        dt = cfg.dtype
        zero_state = {
            "act_buf": jnp.zeros((K_act, mb_rows, eff_seq, d), dt),
            "grad_buf": jnp.zeros((K_grad, mb_rows, eff_seq, d), dt),
            "res_buf": jnp.zeros((K_res, mb_rows, eff_seq, d), dt),
            "send_act": (jnp.zeros((mb_rows, eff_seq, d), dt),
                         jnp.zeros((), jnp.int32), jnp.zeros((), jnp.bool_)),
            "send_grad": (jnp.zeros((mb_rows, eff_seq, d), dt),
                          jnp.zeros((), jnp.int32), jnp.zeros((), jnp.bool_)),
            "d_stage": jax.tree.map(
                lambda x: jnp.zeros(x.shape, opts.grad_dtype), sp),
            "d_io": jax.tree.map(
                lambda x: jnp.zeros(x.shape, opts.io_grad_dtype), io),
            "loss": jnp.zeros((), jnp.float32),
        }

        # ---- per-op branches ------------------------------------------
        def idle_fn(state, mb):
            return state

        def f_fn(state, mb):
            bm = batch_mb(mb)
            a = aux_mb(bm)
            x_in = jax.lax.cond(
                stage == 0,
                lambda: pipeline_embed(io, bm).astype(dt),
                lambda: jax.lax.dynamic_index_in_dim(
                    state["act_buf"], mb % K_act, 0, keepdims=False),
            )
            y = layers(sp, io, x_in, a)
            loss_inc = jax.lax.cond(
                stage == S - 1,
                lambda: loss_of(io, y, bm),
                lambda: jnp.zeros((), jnp.float32),
            )
            res_buf = jax.lax.dynamic_update_index_in_dim(
                state["res_buf"], x_in, mb % K_res, 0)
            return {
                **state,
                "res_buf": res_buf,
                "loss": state["loss"] + loss_inc,
                "send_act": (y, mb, stage < S - 1),
            }

        @jax.named_scope("recompute")
        def scalar_objective(sp_, io_, x, g_in, bm, a):
            x0 = jax.lax.cond(
                stage == 0, lambda: pipeline_embed(io_, bm).astype(dt), lambda: x)
            y = layers(sp_, io_, x0, a)
            return jax.lax.cond(
                stage == S - 1,
                lambda: loss_of(io_, y, bm) * opts.loss_scale,
                lambda: jnp.sum(
                    y.astype(jnp.float32) * g_in.astype(jnp.float32)),
            )

        def b_fn(state, mb):
            bm = batch_mb(mb)
            a = aux_mb(bm)
            g_in = jax.lax.dynamic_index_in_dim(
                state["grad_buf"], mb % K_grad, 0, keepdims=False)
            x_in = jax.lax.dynamic_index_in_dim(
                state["res_buf"], mb % K_res, 0, keepdims=False)
            argnums = (2,) if table.spec.split_backward else (0, 1, 2)
            grads = jax.grad(scalar_objective, argnums=argnums)(
                sp, io, x_in, g_in, bm, a)
            if table.spec.split_backward:
                (dx,) = grads
                new = {}
            else:
                dsp, dio, dx = grads
                new = {
                    "d_stage": jax.tree.map(
                        lambda acc, g: acc + g.astype(opts.grad_dtype),
                        state["d_stage"], dsp),
                    "d_io": jax.tree.map(
                        lambda acc, g: acc + g.astype(opts.io_grad_dtype),
                        state["d_io"], dio),
                }
            return {
                **state, **new,
                "send_grad": (dx.astype(dt), mb, stage > 0),
            }

        def w_fn(state, mb):
            if not table.spec.split_backward:
                return state
            bm = batch_mb(mb)
            a = aux_mb(bm)
            g_in = jax.lax.dynamic_index_in_dim(
                state["grad_buf"], mb % K_grad, 0, keepdims=False)
            x_in = jax.lax.dynamic_index_in_dim(
                state["res_buf"], mb % K_res, 0, keepdims=False)
            dsp, dio = jax.grad(scalar_objective, argnums=(0, 1))(
                sp, io, x_in, g_in, bm, a)
            return {
                **state,
                "d_stage": jax.tree.map(
                    lambda acc, g: acc + g.astype(opts.grad_dtype),
                    state["d_stage"], dsp),
                "d_io": jax.tree.map(
                    lambda acc, g: acc + g.astype(opts.io_grad_dtype),
                    state["d_io"], dio),
            }

        branches = [jax.named_scope(name)(fn) for name, fn in (
            ("tick.idle", idle_fn), ("tick.F", f_fn), ("tick.B", b_fn),
            ("tick.W", w_fn))]

        def tick_body(t, state):
            # deliver messages sent at t-1 (one ring hop per direction)
            pa, pm, pv = state["send_act"]
            with jax.named_scope("tick.exchange"):
                ra = jax.lax.ppermute(pa, "model", fwd_perm)
                rm = jax.lax.ppermute(pm, "model", fwd_perm)
                rv = jax.lax.ppermute(
                    pv.astype(jnp.int32), "model", fwd_perm) > 0
            cur = jax.lax.dynamic_index_in_dim(
                state["act_buf"], rm % K_act, 0, keepdims=False)
            act_buf = jax.lax.dynamic_update_index_in_dim(
                state["act_buf"], jnp.where(rv, ra, cur), rm % K_act, 0)
            ga, gm, gv = state["send_grad"]
            with jax.named_scope("tick.exchange"):
                rga = jax.lax.ppermute(ga, "model", bwd_perm)
                rgm = jax.lax.ppermute(gm, "model", bwd_perm)
                rgv = jax.lax.ppermute(
                    gv.astype(jnp.int32), "model", bwd_perm) > 0
            curg = jax.lax.dynamic_index_in_dim(
                state["grad_buf"], rgm % K_grad, 0, keepdims=False)
            grad_buf = jax.lax.dynamic_update_index_in_dim(
                state["grad_buf"], jnp.where(rgv, rga, curg), rgm % K_grad, 0)
            state = {
                **state,
                "act_buf": act_buf,
                "grad_buf": grad_buf,
                "send_act": (pa, pm, jnp.zeros((), jnp.bool_)),
                "send_grad": (ga, gm, jnp.zeros((), jnp.bool_)),
            }
            op = ops_arr[stage, t]
            mb = mbs_arr[stage, t]
            return jax.lax.switch(op, branches, state, mb)

        state = jax.lax.fori_loop(0, T, tick_body, zero_state)

        # ---- reductions -----------------------------------------------
        loss_sum = jax.lax.psum(state["loss"], ("model",) + dp_axes)

        def rs(leaf):
            """Per-leaf ZeRO-1 reduce-scatter over the DP axes."""
            v = leaf.astype(opts.flat_dtype).reshape(-1)
            v = jnp.pad(v, (0, (-v.size) % dp_total))
            return jax.lax.psum_scatter(
                v.reshape(dp_total, -1), dp_axes, scatter_dimension=0,
                tiled=False)[None]

        grad_shards = {}
        expert_grads = {}
        for (path, leaf), (_, flag) in zip(
                jax.tree_util.tree_leaves_with_path(state["d_stage"]),
                jax.tree_util.tree_leaves_with_path(
                    partition.stage_data_sharded)):
            k = jax.tree_util.keystr(path)
            if flag:
                # expert (data-sharded) grads stay local
                expert_grads[k] = leaf[None]
            else:
                grad_shards[k] = rs(leaf)
        for path, leaf in jax.tree_util.tree_leaves_with_path(state["d_io"]):
            # io grads: stage-masked contributions -> sum over model first
            g = jax.lax.psum(leaf, "model")
            grad_shards["io:" + jax.tree_util.keystr(path)] = rs(g)
        metrics = {
            "loss_sum": loss_sum,
            "loss": loss_sum / (M * mb_rows * opts.seq_len * dp_total),
        }
        return metrics, grad_shards, expert_grads

    # ---- shard_map wrapper ------------------------------------------------
    batch_specs = make_batch_specs(model, opts)

    expert_out_specs = {
        jax.tree_util.keystr(path): spec
        for (path, spec), (_, flag) in zip(
            jax.tree_util.tree_leaves_with_path(partition.stage_specs),
            jax.tree_util.tree_leaves_with_path(partition.stage_data_sharded))
        if flag
    }

    fn = jax.shard_map(
        device_fn,
        mesh=mesh,
        in_specs=(partition.stage_specs, partition.io_specs, batch_specs),
        out_specs=(
            {"loss_sum": P(), "loss": P()},
            grad_shard_specs(model, partition, opts),
            expert_out_specs,
        ),
        check_vma=False,
    )
    return fn, batch_specs


def grad_shard_specs(model: ArchModel, partition: ParamPartition,
                     opts: ExecOptions):
    """Out-spec dict for the per-leaf ZeRO-1 grad shards."""
    spec = P("model", opts.all_dp_axes)
    out = {}
    for (path, _), (_, flag) in zip(
            jax.tree_util.tree_leaves_with_path(partition.stage_specs),
            jax.tree_util.tree_leaves_with_path(
                partition.stage_data_sharded)):
        if not flag:
            out[jax.tree_util.keystr(path)] = spec
    for path, _ in jax.tree_util.tree_leaves_with_path(partition.io_specs):
        out["io:" + jax.tree_util.keystr(path)] = spec
    return out


def make_batch_specs(model: ArchModel, opts: ExecOptions):
    cfg = model.cfg
    specs = {"tokens": P(opts.all_dp_axes), "labels": P(opts.all_dp_axes)}
    if cfg.embed_input:
        specs["embeds"] = P(opts.all_dp_axes)
    if cfg.mrope:
        specs["mrope"] = P(None, opts.all_dp_axes)
    if cfg.encoder_layers:
        specs["enc_embeds"] = P(opts.all_dp_axes)
    return specs

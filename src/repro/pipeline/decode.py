"""Serve-path executor: pipelined single-token decode with stage-local KV.

``serve_step`` advances every sequence in the batch by one token: M
micro-groups of the batch staircase through the S stages (F-only table),
caches updated in place.  For ``long_500k`` (batch 1) the attention caches
are sequence-sharded over the ``data`` axis and combined with the
distributed flash-decode (``sp_mode``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.models.build import ArchModel
from repro.models.layers import rmsnorm
from repro.pipeline.spec import OP_F, ScheduleTable


@dataclasses.dataclass(frozen=True)
class DecodeOptions:
    mb_rows: int          # rows per micro-group per data shard
    cache_len: int        # max KV length
    enc_len: int = 0
    sp_mode: bool = False  # sequence-parallel caches (long_500k, batch=1)
    dp_axes: tuple = ("data",)
    multi_pod: bool = False

    @property
    def all_dp_axes(self) -> tuple:
        return (("pod",) + self.dp_axes) if self.multi_pod else self.dp_axes


def cache_specs(model: ArchModel, opts: DecodeOptions):
    """PartitionSpecs for the stacked [S, l_max, b, ...] cache pytree."""
    one = model.init_layer_cache(1, 2, enc_len=max(1, opts.enc_len))

    def spec_for(path, leaf):
        names = [getattr(p, "key", getattr(p, "name", "")) for p in path]
        nd = leaf.ndim + 2  # + (S, l_max)
        extra = [None] * (nd - 1)
        if opts.sp_mode:
            # attention caches: [S, l_max, b, seq, kv, hd] -> shard seq
            if names and names[-1] in ("k", "v", "xk", "xv"):
                extra[2] = opts.all_dp_axes
        else:
            extra[1] = opts.all_dp_axes  # shard batch
        return P("model", *extra)

    return jax.tree_util.tree_map_with_path(spec_for, one)


def make_serve_fn(model: ArchModel, mesh, opts: DecodeOptions, num_groups: int):
    """Returns fn(stage_params, io, caches, batch, pos) ->
    (next_tokens, new_caches).  ``batch`` carries tokens [B_loc] (or embeds
    [B_loc, 1, d] for embed_input archs); pos is the current position."""
    cfg = model.cfg
    S = model.num_stages
    M = num_groups
    T = M + S - 1
    d = cfg.d_model
    mb_rows = opts.mb_rows
    fwd_perm = [(i, i + 1) for i in range(S - 1)]
    rows_all = {k: jnp.asarray(v) for k, v in model.all_rows().items()}
    data_size = mesh.shape["data"]

    def device_fn(stage_params, io, caches, batch, pos):
        stage = jax.lax.axis_index("model")
        sp = jax.tree.map(lambda x: x[0], stage_params)
        my_cache = jax.tree.map(lambda x: x[0], caches)
        rows = {k: v[stage] for k, v in rows_all.items()}
        aux: dict[str, Any] = {
            "data_size": data_size,
            "moe_layout": model.moe_layout,
        }
        if opts.sp_mode:
            aux["sp_axis"] = "data"

        def embed_group(mb):
            if cfg.embed_input:
                e = jax.lax.dynamic_slice(
                    batch["embeds"], (mb * mb_rows, 0, 0), (mb_rows, 1, d))
                return e.astype(cfg.dtype)
            toks = jax.lax.dynamic_slice(batch["tokens"], (mb * mb_rows,),
                                         (mb_rows,))
            return io["embed"][toks][:, None]

        state = {
            "cache": my_cache,
            "send": (jnp.zeros((mb_rows, 1, d), cfg.dtype),
                     jnp.zeros((), jnp.int32), jnp.zeros((), jnp.bool_)),
            "act_buf": jnp.zeros((min(M, S) + 1, mb_rows, 1, d), cfg.dtype),
            "out_tokens": jnp.zeros((M * mb_rows,), jnp.int32),
        }
        K = state["act_buf"].shape[0]

        def tick_body(t, state):
            pa, pm, pv = state["send"]
            ra = jax.lax.ppermute(pa, "model", fwd_perm)
            rm = jax.lax.ppermute(pm, "model", fwd_perm)
            rv = jax.lax.ppermute(pv.astype(jnp.int32), "model", fwd_perm) > 0
            cur = jax.lax.dynamic_index_in_dim(
                state["act_buf"], rm % K, 0, keepdims=False)
            act_buf = jax.lax.dynamic_update_index_in_dim(
                state["act_buf"], jnp.where(rv, ra, cur), rm % K, 0)
            state = {**state, "act_buf": act_buf,
                     "send": (pa, pm, jnp.zeros((), jnp.bool_))}
            mb = t - stage
            run = (mb >= 0) & (mb < M)

            def do_f(state):
                mb_c = jnp.clip(mb, 0, M - 1)
                x = jax.lax.cond(
                    stage == 0,
                    lambda: embed_group(mb_c),
                    lambda: jax.lax.dynamic_index_in_dim(
                        state["act_buf"], mb_c % K, 0, keepdims=False),
                )
                # slice this micro-group's cache rows
                if opts.sp_mode:
                    cache_mb = state["cache"]  # batch=1: no slicing
                else:
                    cache_mb = jax.tree.map(
                        lambda c: jax.lax.dynamic_slice_in_dim(
                            c, mb_c * mb_rows, mb_rows, axis=1),
                        state["cache"])
                y, cache_mb = model.stage_decode(
                    sp, io, x, cache_mb, pos, aux, rows)
                if opts.sp_mode:
                    cache = cache_mb
                else:
                    cache = jax.tree.map(
                        lambda c, u: jax.lax.dynamic_update_slice_in_dim(
                            c, u, mb_c * mb_rows, axis=1),
                        state["cache"], cache_mb)
                # last stage: greedy next token
                def emit(state_tokens):
                    h = y[:, : 1]
                    logits = (rmsnorm(h, io["final_ln"], cfg.norm_eps)
                              @ io["head"].T).astype(jnp.float32)
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    return jax.lax.dynamic_update_slice_in_dim(
                        state_tokens, nxt, mb_c * mb_rows, axis=0)

                out_tokens = jax.lax.cond(
                    stage == S - 1, emit, lambda ot: ot, state["out_tokens"])
                return {**state, "cache": cache, "out_tokens": out_tokens,
                        "send": (y, mb_c, stage < S - 1)}

            return jax.lax.cond(run, do_f, lambda s: s, state)

        state = jax.lax.fori_loop(0, T, tick_body, state)
        # out tokens live on the last stage row; broadcast via psum (masked)
        out = jnp.where(stage == S - 1, state["out_tokens"], 0)
        out = jax.lax.psum(out, "model")
        return out, jax.tree.map(lambda x: x[None], state["cache"])

    cspecs = cache_specs(model, opts)
    batch_specs: dict = {}
    if cfg.embed_input:
        batch_specs["embeds"] = P(opts.all_dp_axes if not opts.sp_mode else None)
    else:
        batch_specs["tokens"] = P(opts.all_dp_axes if not opts.sp_mode else None)

    from repro.pipeline.sharding import partition_for  # specs only

    def wrap(partition):
        return jax.shard_map(
            device_fn,
            mesh=mesh,
            in_specs=(partition.stage_specs, partition.io_specs, cspecs,
                      batch_specs, P()),
            out_specs=(
                P(opts.all_dp_axes if not opts.sp_mode else None),
                cspecs,
            ),
            check_vma=False,
        )

    return wrap, cspecs, batch_specs

"""Arch registry: ArchConfig -> ArchModel, the uniform interface the pipeline
executor, dry-run and smoke tests consume.

Every architecture exposes the same contract:

* stacked per-stage layer parameters with a *union* structure across the
  arch's layer types (lax.switch selects the branch per slot; uneven
  layers-per-stage handled with enabled flags — DESIGN §3),
* ``stage_forward(stage_params, io, x, aux, rows)`` — the pipelined F body,
* ``stage_decode`` — the serve-path body with stacked per-layer caches,
* io params (embedding / head / final norm / shared blocks) that live
  outside the stage stacking,
* a single-device ``reference_forward`` used by tests,
* analytic FLOP/param accounting for the roofline.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models import xlstm as xlstm_lib
from repro.models.common import (
    ArchConfig,
    ShapeCell,
    dense_init,
    global_layer_index,
    keygen,
    stage_layout,
)
from repro.models.layers import (
    attention_block,
    decode_attention_block,
    decoder_layer,
    decoder_layer_decode,
    ffn_block,
    init_attention,
    init_decoder_layer,
    init_ffn,
    rmsnorm,
)


def _shared_block(io, x, positions, cfg: ArchConfig):
    """The shared attention block (zamba2), under its own scope."""
    with jax.named_scope("shared_blk"):
        return decoder_layer(io["shared_blk"], x, positions, cfg)


def _tree_stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


@dataclasses.dataclass
class ArchModel:
    cfg: ArchConfig
    num_stages: int
    counts: np.ndarray  # [S] true layers per stage
    l_max: int
    type_ids: np.ndarray  # [S, l_max] index into layer_types, -1 disabled
    shared_flags: np.ndarray  # [S, l_max] apply-shared-block-before-slot
    layer_types: tuple[str, ...]
    moe_layout: str = "none"  # none | ep | tp (over the data axis)

    # ------------------------------------------------------------------
    @property
    def d_model(self) -> int:
        return self.cfg.d_model

    def rows(self, stage: int) -> dict[str, np.ndarray]:
        return {
            "type_id": np.maximum(self.type_ids[stage], 0),
            "enabled": (self.type_ids[stage] >= 0).astype(np.int32),
            "shared": self.shared_flags[stage].astype(np.int32),
        }

    def all_rows(self) -> dict[str, np.ndarray]:
        return {
            "type_id": np.maximum(self.type_ids, 0),
            "enabled": (self.type_ids >= 0).astype(np.int32),
            "shared": self.shared_flags.astype(np.int32),
        }

    # ------------------------------------------------------------------
    # params
    # ------------------------------------------------------------------
    def init_layer_params(self, key) -> dict:
        """Union parameter struct covering every layer type of this arch."""
        cfg = self.cfg
        keys = keygen(key)
        p: dict[str, Any] = {}
        types = set(self.layer_types)
        if types & {"attn", "attn_local", "attn_global", "enc", "dec"}:
            p["blk"] = init_decoder_layer(keys, cfg)
        if "dec" in types:
            p["cross_ln"] = jnp.zeros((cfg.d_model,), cfg.dtype)
            p["cross"] = init_attention(keys, cfg, cross=True)
        if types & {"moe", "dense"}:
            p["ln1"] = jnp.zeros((cfg.d_model,), cfg.dtype)
            p["attn"] = init_attention(keys, cfg)
            p["ln2"] = jnp.zeros((cfg.d_model,), cfg.dtype)
            if "moe" in types:
                p["moe"] = moe_lib.init_moe_ffn(keys, cfg)
            if "dense" in types:
                p["dense_ffn"] = init_ffn(keys, cfg, cfg.moe.dense_d_ff)
        if "mamba" in types:
            p["mamba"] = ssm_lib.init_mamba_layer(keys, cfg)
        if "mlstm" in types:
            p["mlstm"] = xlstm_lib.init_mlstm_layer(keys, cfg)
        if "slstm" in types:
            p["slstm"] = xlstm_lib.init_slstm_layer(keys, cfg)
        return p

    def init_stage_params(self, key):
        """[S, l_max, ...] stacked union params.  A layer's key is its
        global index, so one key gives the same model under any stage split
        (disabled slots get keys past the last layer)."""
        gli = global_layer_index(self.counts)
        spare = self.cfg.num_layers
        slots = []
        for s in range(self.num_stages):
            row = []
            for i in range(self.l_max):
                g = int(gli[s, i])
                if g < 0:
                    g, spare = spare, spare + 1
                row.append(self.init_layer_params(jax.random.fold_in(key, g)))
            slots.append(_tree_stack(row))
        return _tree_stack(slots)

    def init_io_params(self, key):
        cfg = self.cfg
        keys = keygen(key)
        v = cfg.padded_vocab()
        io: dict[str, Any] = {
            "embed": dense_init(next(keys), (v, cfg.d_model), cfg.dtype, scale=0.02),
            "head": dense_init(next(keys), (v, cfg.d_model), cfg.dtype),
            "final_ln": jnp.zeros((cfg.d_model,), cfg.dtype),
        }
        if cfg.shared_attn_period:
            io["shared_blk"] = init_decoder_layer(keys, cfg)
        return io

    # ------------------------------------------------------------------
    # layer dispatch
    # ------------------------------------------------------------------
    def _branch(self, kind: str) -> Callable:
        cfg = self.cfg

        def attn_like(p, io, x, aux, window: int, causal: bool = True):
            return decoder_layer(
                p["blk"], x, aux["positions"], cfg, causal=causal, window=window,
                mrope_pos=aux.get("mrope"),
            )

        if kind == "attn":
            return lambda p, io, x, aux: attn_like(p, io, x, aux, cfg.sliding_window)
        if kind == "attn_local":
            return lambda p, io, x, aux: attn_like(p, io, x, aux, cfg.sliding_window or 1024)
        if kind == "attn_global":
            return lambda p, io, x, aux: attn_like(p, io, x, aux, 0)
        if kind == "enc":

            def enc_fn(p, io, x, aux):
                # x = concat(dec_zeros, enc); encoder transforms the enc part
                dec_len = aux["dec_len"]
                enc = x[:, dec_len:]
                pos = jnp.broadcast_to(
                    jnp.arange(enc.shape[1])[None], enc.shape[:2])
                enc = decoder_layer(p["blk"], enc, pos, cfg, causal=False)
                return jnp.concatenate([x[:, :dec_len], enc], axis=1)

            return enc_fn
        if kind == "dec":

            def dec_fn(p, io, x, aux):
                dec_len = aux["dec_len"]
                dec, enc = x[:, :dec_len], x[:, dec_len:]
                pos = jnp.broadcast_to(jnp.arange(dec_len)[None], dec.shape[:2])
                h = rmsnorm(dec, p["blk"]["ln1"], cfg.norm_eps)
                dec = dec + attention_block(p["blk"]["attn"], h, pos, cfg)
                h = rmsnorm(dec, p["cross_ln"], cfg.norm_eps)
                dec = dec + attention_block(
                    p["cross"], h, pos, cfg, causal=False, kv_src=enc, rope=False)
                h = rmsnorm(dec, p["blk"]["ln2"], cfg.norm_eps)
                dec = dec + ffn_block(p["blk"]["ffn"], h, cfg.act)
                return jnp.concatenate([dec, enc], axis=1)

            return dec_fn
        if kind in ("moe", "dense"):

            def moe_fn(p, io, x, aux, kind=kind):
                h = rmsnorm(x, p["ln1"], cfg.norm_eps)
                x = x + attention_block(p["attn"], h, aux["positions"], cfg)
                h = rmsnorm(x, p["ln2"], cfg.norm_eps)
                if kind == "dense":
                    return x + ffn_block(p["dense_ffn"], h, cfg.act)
                return x + moe_lib.moe_ffn(
                    p["moe"], h, cfg, layout=aux.get("moe_layout", "none"),
                    axis_name="data", axis_size=aux.get("data_size", 1))

            return moe_fn
        if kind == "mamba":
            return lambda p, io, x, aux: ssm_lib.mamba_layer(p["mamba"], x, cfg)
        if kind == "mlstm":
            return lambda p, io, x, aux: xlstm_lib.mlstm_layer(p["mlstm"], x, cfg)
        if kind == "slstm":
            return lambda p, io, x, aux: xlstm_lib.slstm_layer(p["slstm"], x, cfg)
        raise ValueError(kind)

    # ------------------------------------------------------------------
    # forward
    # ------------------------------------------------------------------
    def stage_forward(self, stage_params, io, x, aux, rows, remat: bool = True):
        """Apply this stage's layer slots.  stage_params leaves [l_max, ...];
        rows: dict of [l_max] int arrays (type_id / enabled / shared).

        Each slot is rematerialized under autodiff (``remat``): the stage
        VJP then stores one activation per layer instead of every layer's
        internals — the memory term that makes 32k-seq stages fit HBM.
        """
        cfg = self.cfg
        branches = [self._branch(k) for k in self.layer_types]

        def slot_compute(p_slot, io, x, tid, en, sh):
            if cfg.shared_attn_period:
                x = jax.lax.cond(
                    (sh > 0) & (en > 0),
                    lambda x: _shared_block(io, x, aux["positions"], cfg),
                    lambda x: x,
                    x,
                )
            if len(branches) == 1:
                y = branches[0](p_slot, io, x, aux)
            else:
                y = jax.lax.switch(
                    tid, [lambda p, x, b=b: b(p, io, x, aux) for b in branches],
                    p_slot, x)
            return jnp.where(en > 0, y, x)

        # Static specialization: when rows are concrete (per-op roofline
        # costing, reference forward), branch in Python so HloCostAnalysis
        # doesn't count untaken cond/switch branches (a real TPU skips them
        # at runtime; the SPMD executor passes traced rows and keeps the
        # dynamic path).
        static = isinstance(rows["type_id"], np.ndarray)
        if static:

            def slot_static(p_slot, io, x, tid, en, sh):
                if not en:
                    return x
                if cfg.shared_attn_period and sh:
                    x = _shared_block(io, x, aux["positions"], cfg)
                return branches[tid](p_slot, io, x, aux)

            policy = (jax.checkpoint_policies.save_only_these_names(
                "moe_dispatched") if cfg.family == "moe" else None)
            body = jax.checkpoint(slot_static, static_argnums=(3, 4, 5),
                                  policy=policy) if remat else slot_static
        elif remat:
            policy = (jax.checkpoint_policies.save_only_these_names(
                "moe_dispatched") if cfg.family == "moe" else None)
            slot_compute = jax.checkpoint(slot_compute, policy=policy)

        # NOTE: the slot loop is python-unrolled (l_max <= ~6), NOT lax.scan:
        # scan's linearization partial-eval hoists the attention kernels'
        # "known" mask blocks into per-step stacked residuals (measured 59 GB
        # at 32k seq for a length-1 scan vs 6.9 GB unrolled) — see
        # EXPERIMENTS.md §Perf iteration log.
        l_max = jax.tree.leaves(stage_params)[0].shape[0]
        if static:
            for i in range(l_max):
                p_slot = jax.tree.map(lambda p: p[i], stage_params)
                x = body(p_slot, io, x, int(rows["type_id"][i]),
                         bool(rows["enabled"][i]), bool(rows["shared"][i]))
            return x
        tid = jnp.asarray(rows["type_id"])
        en = jnp.asarray(rows["enabled"])
        sh = jnp.asarray(rows["shared"])
        for i in range(l_max):
            p_slot = jax.tree.map(lambda p: p[i], stage_params)
            x = slot_compute(p_slot, io, x, tid[i], en[i], sh[i])
        return x

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def init_layer_cache(self, batch: int, seq: int, enc_len: int = 0) -> dict:
        """Union cache struct for one layer slot."""
        cfg = self.cfg
        c: dict[str, Any] = {}
        types = set(self.layer_types)
        kv = cfg.num_kv_heads
        hd = cfg.resolved_head_dim
        if types & {"attn", "attn_local", "attn_global", "dec", "moe", "dense"} or cfg.shared_attn_period:
            c["k"] = jnp.zeros((batch, seq, kv, hd), cfg.dtype)
            c["v"] = jnp.zeros((batch, seq, kv, hd), cfg.dtype)
        if "dec" in types:
            c["xk"] = jnp.zeros((batch, enc_len, kv, hd), cfg.dtype)
            c["xv"] = jnp.zeros((batch, enc_len, kv, hd), cfg.dtype)
        if "mamba" in types:
            c["mamba"] = ssm_lib.init_mamba_cache(batch, cfg)
        if "mlstm" in types:
            c["mlstm"] = xlstm_lib.init_mlstm_cache(batch, cfg)
        if "slstm" in types:
            c["slstm"] = xlstm_lib.init_slstm_cache(batch, cfg)
        return c

    def init_stage_cache(self, batch: int, seq: int, enc_len: int = 0):
        one = self.init_layer_cache(batch, seq, enc_len)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(
                x[None, None], (self.num_stages, self.l_max) + x.shape
            ),
            one,
        )

    def _decode_branch(self, kind: str) -> Callable:
        cfg = self.cfg

        def attn_like(p, io, x, cache, pos, aux, window):
            kvc = {"k": cache["k"], "v": cache["v"]}
            y, kvc = decoder_layer_decode(
                p["blk"], x, kvc, pos, cfg, window=window,
                axis_name=aux.get("sp_axis"))
            return y, {**cache, **kvc}

        if kind == "attn":
            return lambda p, io, x, c, pos, aux: attn_like(
                p, io, x, c, pos, aux, cfg.sliding_window)
        if kind == "attn_local":
            return lambda p, io, x, c, pos, aux: attn_like(
                p, io, x, c, pos, aux, cfg.sliding_window or 1024)
        if kind == "attn_global":
            return lambda p, io, x, c, pos, aux: attn_like(p, io, x, c, pos, aux, 0)
        if kind == "dec":

            def dec_fn(p, io, x, cache, pos, aux):
                kvc = {"k": cache["k"], "v": cache["v"]}
                h = rmsnorm(x, p["blk"]["ln1"], cfg.norm_eps)
                a, kvc = decode_attention_block(p["blk"]["attn"], h, kvc, pos, cfg)
                x = x + a
                # cross attention against the pre-filled encoder KV cache
                h = rmsnorm(x, p["cross_ln"], cfg.norm_eps)
                b = x.shape[0]
                q, _, _ = (
                    h @ p["cross"]["wq"],
                    None,
                    None,
                )
                q = q.reshape(b, 1, cfg.num_heads, cfg.resolved_head_dim)
                enc_len = cache["xk"].shape[1]
                o = ops.decode_attention(q, cache["xk"], cache["xv"], enc_len)
                x = x + o.reshape(b, 1, -1) @ p["cross"]["wo"]
                h = rmsnorm(x, p["blk"]["ln2"], cfg.norm_eps)
                x = x + ffn_block(p["blk"]["ffn"], h, cfg.act)
                return x, {**cache, **kvc}

            return dec_fn
        if kind == "enc":
            # encoder layers are inert at decode time (context pre-filled)
            return lambda p, io, x, c, pos, aux: (x, c)
        if kind in ("moe", "dense"):

            def moe_fn(p, io, x, cache, pos, aux, kind=kind):
                kvc = {"k": cache["k"], "v": cache["v"]}
                h = rmsnorm(x, p["ln1"], cfg.norm_eps)
                a, kvc = decode_attention_block(p["attn"], h, kvc, pos, cfg)
                x = x + a
                h = rmsnorm(x, p["ln2"], cfg.norm_eps)
                if kind == "dense":
                    y = ffn_block(p["dense_ffn"], h, cfg.act)
                else:
                    y = moe_lib.moe_ffn(
                        p["moe"], h, cfg, layout=aux.get("moe_layout", "none"),
                        axis_name="data", axis_size=aux.get("data_size", 1))
                return x + y, {**cache, **kvc}

            return moe_fn
        if kind == "mamba":

            def mamba_fn(p, io, x, cache, pos, aux):
                y, mc = ssm_lib.mamba_layer_decode(p["mamba"], x, cache["mamba"], cfg)
                return y, {**cache, "mamba": mc}

            return mamba_fn
        if kind == "mlstm":

            def mlstm_fn(p, io, x, cache, pos, aux):
                y, mc = xlstm_lib.mlstm_layer_decode(p["mlstm"], x, cache["mlstm"], cfg)
                return y, {**cache, "mlstm": mc}

            return mlstm_fn
        if kind == "slstm":

            def slstm_fn(p, io, x, cache, pos, aux):
                y, sc = xlstm_lib.slstm_layer_decode(p["slstm"], x, cache["slstm"], cfg)
                return y, {**cache, "slstm": sc}

            return slstm_fn
        raise ValueError(kind)

    def stage_decode(self, stage_params, io, x, stage_cache, pos, aux, rows):
        """x: [b, 1, d]; stage_cache leaves [l_max, ...]."""
        cfg = self.cfg
        branches = [self._decode_branch(k) for k in self.layer_types]

        def slot(x, scan_in):
            p_slot, cache_slot, tid, en, sh = scan_in
            if cfg.shared_attn_period:
                # the shared block's KV cache rides in the slot's k/v fields
                @jax.named_scope("shared_blk")
                def shared_apply(x, kvc):
                    return decoder_layer_decode(io["shared_blk"], x, kvc, pos, cfg)

                kvc = {"k": cache_slot["k"], "v": cache_slot["v"]}
                x, kvc = jax.lax.cond(
                    (sh > 0) & (en > 0), shared_apply,
                    lambda x, kvc: (x, kvc), x, kvc)
                cache_slot = {**cache_slot, **kvc}
            if len(branches) == 1:
                y, c = branches[0](p_slot, io, x, cache_slot, pos, aux)
            else:
                y, c = jax.lax.switch(
                    tid,
                    [lambda p, x, cc, b=b: b(p, io, x, cc, pos, aux) for b in branches],
                    p_slot, x, cache_slot)
            y = jnp.where(en > 0, y, x)
            c = jax.tree.map(lambda new, old: jnp.where(en > 0, new, old), c, cache_slot)
            return y, c

        # python-unrolled like stage_forward (uniform memory behaviour)
        l_max = jax.tree.leaves(stage_params)[0].shape[0]
        tid = jnp.asarray(rows["type_id"])
        en_r = jnp.asarray(rows["enabled"])
        sh = jnp.asarray(rows["shared"])
        new_slots = []
        for i in range(l_max):
            p_slot = jax.tree.map(lambda p: p[i], stage_params)
            c_slot = jax.tree.map(lambda c: c[i], stage_cache)
            x, c_new = slot(x, (p_slot, c_slot, tid[i], en_r[i], sh[i]))
            new_slots.append(c_new)
        new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *new_slots)
        return x, new_cache

    # ------------------------------------------------------------------
    # embedding / head (pure versions; the executor adds vocab parallelism)
    # ------------------------------------------------------------------
    def embed(self, io, batch: dict):
        if self.cfg.embed_input:
            return batch["embeds"].astype(self.cfg.dtype)
        return io["embed"][batch["tokens"]]

    def head_logits(self, io, x):
        h = rmsnorm(x, io["final_ln"], self.cfg.norm_eps)
        return h @ io["head"].T

    # ------------------------------------------------------------------
    # reference single-device forward (tests)
    # ------------------------------------------------------------------
    def reference_forward(self, stage_params, io, batch: dict, aux: dict):
        x = self.embed(io, batch)
        for s in range(self.num_stages):
            sp = jax.tree.map(lambda p: p[s], stage_params)
            x = self.stage_forward(sp, io, x, aux, self.rows(s))
        return self.head_logits(io, x)

    # ------------------------------------------------------------------
    # analytic accounting
    # ------------------------------------------------------------------
    def model_flops(self, cell: ShapeCell) -> dict[str, float]:
        """MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE), N excl. embed."""
        cfg = self.cfg
        tokens = cell.seq_len * cell.global_batch if cell.step == "train" else cell.global_batch
        n_active = cfg.active_param_count() + cfg.padded_vocab() * cfg.d_model
        n_total = cfg.param_count(include_embed=False) + cfg.padded_vocab() * cfg.d_model
        mult = 6 if cell.step == "train" else 2
        # attention context FLOPs (not in 6ND): 12*s*ctx*d_attn per layer
        attn_layers = sum(
            1 for k in cfg.pattern
            if k in ("attn", "attn_global", "moe", "dense", "dec", "enc")
        ) + (len([1 for f in self.shared_flags.ravel() if f]) if cfg.shared_attn_period else 0)
        local_layers = sum(1 for k in cfg.pattern if k == "attn_local")
        hq, hd = cfg.num_heads, cfg.resolved_head_dim
        if cell.step == "train":
            ctx = cell.seq_len / 2
            attn_flops = mult * cell.global_batch * cell.seq_len * (
                attn_layers * ctx + local_layers * min(cfg.sliding_window or 1024, ctx)
            ) * 2 * hq * hd
        else:
            ctx = cell.seq_len
            attn_flops = mult * cell.global_batch * (
                attn_layers * ctx + local_layers * min(cfg.sliding_window or 1024, ctx)
            ) * 2 * hq * hd
        return {
            "model_flops": mult * n_active * tokens + attn_flops,
            "model_flops_total_params": mult * n_total * tokens + attn_flops,
            "tokens": tokens,
            "n_active": n_active,
            "n_total": n_total,
        }


# ---------------------------------------------------------------------------
# builder
# ---------------------------------------------------------------------------
def build(cfg: ArchConfig, num_stages: int = 16) -> ArchModel:
    counts, l_max = stage_layout(cfg.num_layers, num_stages)
    gli = global_layer_index(counts)  # [S, l_max], -1 disabled
    pattern = cfg.pattern
    types = cfg.layer_types()
    type_ids = np.full((num_stages, l_max), -1, dtype=np.int64)
    shared = np.zeros((num_stages, l_max), dtype=np.int64)
    for s in range(num_stages):
        for i in range(l_max):
            g = gli[s, i]
            if g >= 0:
                type_ids[s, i] = types.index(pattern[g])
                if cfg.shared_attn_period and g % cfg.shared_attn_period == 0:
                    shared[s, i] = 1
    layout = "none"
    if cfg.family == "moe":
        assert cfg.moe is not None
        layout = "ep" if cfg.moe.num_experts >= 16 else "tp"
    return ArchModel(
        cfg=cfg,
        num_stages=num_stages,
        counts=counts,
        l_max=l_max,
        type_ids=type_ids,
        shared_flags=shared,
        layer_types=types,
        moe_layout=layout,
    )

"""Branch+fusion multimodal pipeline model (real params, DAG topology).

Stage layout for an encoder branch of ``E`` stages and an LM chain of
``L`` stages (fusion first)::

    enc_0 -> enc_1 -> ... -> enc_{E-1} --\
                                          +--> fusion -> lm_1 -> ... -> lm_{L-1}
    text frontend ------------------------/

* **encoder stages** (vision patches / audio frames): non-causal
  transformer layers at width ``d_enc`` over *variable-length* token
  sequences.  Attention is computed in a bitwise padding-invariant form
  (every reduction along the variable axis is a ``dot_general``; the
  softmax max is ``stop_gradient``-ed), so padding a microbatch up to a
  shape bucket changes neither outputs nor gradients at valid positions —
  the property the bucketing parity tests pin down.
* **text frontend**: token embedding + causal decoder layers at
  ``d_model`` (built from ``models.layers``).
* **fusion stage**: segment-pools the encoder branch's valid positions
  into ``fusion_slots`` tokens, projects ``d_enc -> d_model``, prepends
  them to the text hidden states, then runs causal LM layers over the
  fused sequence.  Its forward has **two message predecessors** (the DAG
  fan-in); its backward emits one input gradient per branch (fan-out).
* **LM tail stages**: causal decoder layers; the last stage carries the
  LM head and the token cross-entropy over the text positions.

``multimodal_config`` derives all widths from a registered arch config
(``qwen2-vl-2b`` → vision modality, ``seamless-m4t-large-v2`` → audio),
reduced for CPU smoke runs or full-size.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core.taskgraph import PipelineSpec, StageGraph
from repro.data.lengths import VISION_SIGMA
from repro.models.common import ArchConfig, dense_init, keygen
from repro.models.layers import (
    NEG_INF,
    attention_qkv,
    decoder_layer,
    ffn_block,
    init_decoder_layer,
    rmsnorm,
)

#: registered archs this subsystem knows how to lower onto the DAG
MULTIMODAL_ARCHS = {
    "qwen2-vl-2b": "vision",
    "seamless-m4t-large-v2": "audio",
}


@dataclasses.dataclass(frozen=True)
class MultimodalConfig:
    """Static description of one branch+fusion multimodal pipeline."""

    name: str
    modality: str            # "vision" | "audio"
    enc_stages: int          # encoder-branch stages (>= 1)
    lm_stages: int           # fusion + decoder-chain stages (>= 1)
    enc_layers_per_stage: int
    lm_layers_per_stage: int
    d_enc: int
    enc_heads: int
    d_model: int
    vocab_size: int
    text_seq: int
    fusion_slots: int        # pooled modality tokens entering the LM
    mean_enc_tokens: int     # mean encoder tokens per microbatch sample
    enc_sigma: float         # lognormal sigma of the per-mb length skew
    buckets: tuple[int, ...]  # padded encoder-length buckets (ascending)
    #: the LM-side ArchConfig the decoder layers are built from
    lm_cfg: ArchConfig = None  # type: ignore[assignment]

    def __post_init__(self) -> None:
        if self.enc_stages < 1 or self.lm_stages < 1:
            raise ValueError("need >= 1 encoder and >= 1 LM stage")
        if not self.buckets or list(self.buckets) != sorted(self.buckets):
            raise ValueError("buckets must be a non-empty ascending tuple")
        if self.fusion_slots < 1:
            raise ValueError("fusion_slots must be >= 1")

    @property
    def num_stages(self) -> int:
        return self.enc_stages + 1 + self.lm_stages

    @property
    def text_stage(self) -> int:
        return self.enc_stages

    @property
    def fusion_stage(self) -> int:
        return self.enc_stages + 1

    @property
    def fused_seq(self) -> int:
        return self.fusion_slots + self.text_seq

    @property
    def enc_cfg(self) -> ArchConfig:
        """Layer-shim config for the encoder width (GELU, no GQA)."""
        return dataclasses.replace(
            self.lm_cfg, d_model=self.d_enc, num_heads=self.enc_heads,
            num_kv_heads=self.enc_heads, head_dim=0,
            d_ff=max(4 * self.d_enc, 8), act="gelu", qkv_bias=False,
            mrope=False, layer_pattern=None)

    # ---- topology ----------------------------------------------------------
    def stage_graph(self) -> StageGraph:
        E, S = self.enc_stages, self.num_stages
        edges = [(s, s + 1) for s in range(E - 1)]          # encoder chain
        edges.append((E - 1, self.fusion_stage))            # branch fan-in
        edges.append((self.text_stage, self.fusion_stage))  # text fan-in
        edges += [(s, s + 1) for s in range(self.fusion_stage, S - 1)]
        return StageGraph(S, tuple(edges))

    def spec(self, num_microbatches: int,
             split_backward: bool = False) -> PipelineSpec:
        return PipelineSpec(self.num_stages, num_microbatches,
                            split_backward=split_backward,
                            graph=self.stage_graph())

    def roles(self) -> dict[str, tuple[int, ...]]:
        """Stage-id sets per role (consumed by chaos modality profiles)."""
        return {
            "encoder": tuple(range(self.enc_stages)),
            "text": (self.text_stage,),
            "fusion": (self.fusion_stage,),
            "decoder": tuple(range(self.fusion_stage, self.num_stages)),
        }

    def fanin_edges(self) -> tuple[tuple[int, int], ...]:
        return ((self.enc_stages - 1, self.fusion_stage),
                (self.text_stage, self.fusion_stage))

    def role_of(self, stage: int) -> str:
        if stage < self.enc_stages:
            return "encoder"
        if stage == self.text_stage:
            return "text"
        if stage == self.fusion_stage:
            return "fusion"
        return "lm"


def multimodal_config(
    arch: str,
    *,
    enc_stages: int = 2,
    lm_stages: int = 2,
    enc_layers_per_stage: int = 2,
    lm_layers_per_stage: int = 2,
    text_seq: int = 32,
    fusion_slots: int = 4,
    mean_enc_tokens: int = 24,
    buckets: tuple[int, ...] = (16, 32, 48),
    reduced: bool = True,
    num_layers: int | None = None,
) -> MultimodalConfig:
    """Lower a registered multimodal arch onto the branch+fusion pipeline."""
    if arch not in MULTIMODAL_ARCHS:
        raise ValueError(
            f"{arch!r} is not a multimodal arch; available: "
            f"{sorted(MULTIMODAL_ARCHS)}")
    modality = MULTIMODAL_ARCHS[arch]
    cfg = registry.model_config(arch, num_layers, full_size=not reduced)
    # encoder width: half the LM width (rounded to a head multiple) — cheap
    # per-token relative to the decoder, like a ViT/conformer frontend
    enc_heads = max(1, cfg.num_heads // 2)
    d_enc = max(8 * enc_heads, (cfg.d_model // 2) // enc_heads * enc_heads)
    # audio frames arrive longer but less spread than dynamic-res images
    sigma = VISION_SIGMA if modality == "vision" else 0.4
    return MultimodalConfig(
        name=cfg.name,
        modality=modality,
        enc_stages=enc_stages,
        lm_stages=lm_stages,
        enc_layers_per_stage=enc_layers_per_stage,
        lm_layers_per_stage=lm_layers_per_stage,
        d_enc=d_enc,
        enc_heads=enc_heads,
        d_model=cfg.d_model,
        vocab_size=cfg.vocab_size,
        text_seq=text_seq,
        fusion_slots=fusion_slots,
        mean_enc_tokens=mean_enc_tokens,
        enc_sigma=sigma,
        buckets=tuple(sorted(buckets)),
        lm_cfg=cfg,
    )


# ---------------------------------------------------------------------------
# bitwise padding-invariant encoder attention
# ---------------------------------------------------------------------------
#
# Why the inner block runs at a fixed length: XLA's lowering of a matmul /
# reduction is shape-dependent, and a shape-dependent lowering may change
# the floating-point accumulation order — measured on the CPU backend,
# `einsum("bhqk,bkhd->bqhd")` produces different bits for the same logical
# rows at k=49 vs k=64 even when the padding is exact zeros.  Position-wise
# ops (projections, norms, FFN) are bitwise-stable under row-count changes,
# but any op whose *sequence axis participates in a reduction or sets the
# output tile* must therefore run at one fixed shape.  So the attention
# inner block (and the fusion pooling) pads q/k/v up to ``pad_to`` — the
# largest bucket — computes at that fixed shape (identical lowering for
# every bucket ⇒ bitwise identity), and slices the result back.  The
# position-wise majority of the FLOPs still scales with the bucket.
def masked_encoder_attention(p, x, length, cfg: ArchConfig, pad_to: int):
    """Non-causal self-attention over a variable-length padded sequence.

    ``length``: [] valid token count; ``pad_to``: static inner length
    (>= x.shape[1]).  Valid positions' outputs — and all gradients — are
    bitwise independent of x's padded length.
    """
    b, s, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = attention_qkv(p, x, x, cfg)   # [b, s, h, hd] (bucket-sized)
    pad = ((0, 0), (0, pad_to - s), (0, 0), (0, 0))
    qf = jnp.pad((q * hd**-0.5).astype(jnp.float32), pad)
    kf = jnp.pad(k.astype(jnp.float32), pad)
    vf = jnp.pad(v.astype(jnp.float32), pad)
    valid = jnp.arange(pad_to) < length
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf)      # [b, h, K, K]
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(scores, axis=-1, keepdims=True))
    probs = jnp.exp(scores - m) * valid[None, None, None, :]
    ones = jnp.ones((pad_to,), jnp.float32)
    denom = jnp.einsum("bhqk,k->bhq", probs, ones)      # [b, h, K]
    num = jnp.einsum("bhqk,bkhd->bqhd", probs, vf)
    out = num / jnp.transpose(denom, (0, 2, 1))[..., None]
    out = out[:, :s].astype(x.dtype).reshape(b, s, -1)
    return out @ p["wo"]


def _fixed_len_rmsnorm(x, scale, eps: float, pad_to: int):
    """rmsnorm whose scale-gradient reduces at the fixed inner length.

    The norm itself is position-wise, but its scale VJP sums over the
    token axis; padding that reduction up to ``pad_to`` keeps the summed
    positions (valid rows + exact-zero rows) identical across buckets.
    """
    s = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (0, pad_to - s), (0, 0)))
    return rmsnorm(xp, scale, eps)[:, :s]


def encoder_layer(p, x, length, cfg: ArchConfig, pad_to: int):
    """Pre-norm encoder block: masked attention + FFN (GELU)."""
    h = _fixed_len_rmsnorm(x, p["ln1"], cfg.norm_eps, pad_to)
    x = x + masked_encoder_attention(p["attn"], h, length, cfg, pad_to)
    h = _fixed_len_rmsnorm(x, p["ln2"], cfg.norm_eps, pad_to)
    return x + ffn_block(p["ffn"], h, cfg.act)


def pool_weights(length, bucket: int, slots: int):
    """[slots, bucket] segment-mean pooling weights over valid positions.

    Integer segment assignment + exact-zero weights at padding: the pooled
    tokens are bitwise independent of the bucket size (the pooling matmul
    itself runs at the fixed inner length — see ``fusion_forward``).
    """
    pos = jnp.arange(bucket)
    length = jnp.maximum(length, 1)
    seg = jnp.minimum((pos * slots) // length, slots - 1)     # [bucket]
    valid = pos < length
    w = (seg[None, :] == jnp.arange(slots)[:, None]) & valid[None, :]
    w = w.astype(jnp.float32)
    count = jnp.einsum("sk,k->s", w, jnp.ones((bucket,), jnp.float32))
    return w / jnp.maximum(count, 1.0)[:, None]


# ---------------------------------------------------------------------------
# the model: params + pure per-stage forward bodies
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class MultimodalModel:
    cfg: MultimodalConfig

    # ---- params --------------------------------------------------------
    def init_stage_params(self, key) -> list[dict]:
        """One parameter pytree per pipeline stage (heterogeneous)."""
        cfg = self.cfg
        enc_cfg, lm_cfg = cfg.enc_cfg, cfg.lm_cfg
        dtype = lm_cfg.dtype
        out: list[dict] = []
        for s in range(cfg.num_stages):
            keys = keygen(jax.random.fold_in(key, s))
            role = cfg.role_of(s)
            p: dict[str, Any] = {}
            if role == "encoder":
                if s == 0:
                    p["pos_embed"] = dense_init(
                        next(keys), (max(cfg.buckets), cfg.d_enc), dtype,
                        scale=0.02)
                p["layers"] = [
                    init_decoder_layer(keys, enc_cfg)
                    for _ in range(cfg.enc_layers_per_stage)]
            elif role == "text":
                p["embed"] = dense_init(
                    next(keys), (cfg.vocab_size, cfg.d_model), dtype,
                    scale=0.02)
                p["layers"] = [
                    init_decoder_layer(keys, lm_cfg)
                    for _ in range(cfg.lm_layers_per_stage)]
            else:  # fusion / lm
                if role == "fusion":
                    p["proj_w"] = dense_init(
                        next(keys), (cfg.d_enc, cfg.d_model), dtype)
                    p["proj_b"] = jnp.zeros((cfg.d_model,), dtype)
                p["layers"] = [
                    init_decoder_layer(keys, lm_cfg)
                    for _ in range(cfg.lm_layers_per_stage)]
                if s == cfg.num_stages - 1:
                    p["final_ln"] = jnp.zeros((cfg.d_model,), dtype)
                    p["head"] = dense_init(
                        next(keys), (cfg.vocab_size, cfg.d_model), dtype)
            out.append(p)
        return out

    @property
    def num_stages(self) -> int:
        return self.cfg.num_stages

    def param_count(self) -> int:
        key = jax.random.key(0)
        return sum(x.size for x in jax.tree.leaves(self.init_stage_params(key)))

    # ---- per-stage forward bodies (pure; jitted by MultimodalStageFns) --
    def encoder_forward(self, stage: int, p, x, length):
        """x: [rows, bucket, d_enc]; length: [] valid token count."""
        cfg = self.cfg
        if stage == 0:
            x = x + p["pos_embed"][:x.shape[1]][None]
        for lp in p["layers"]:
            x = encoder_layer(lp, x, length, cfg.enc_cfg, max(cfg.buckets))
        return x

    def text_forward(self, p, tokens):
        """tokens: [rows, text_seq] -> [rows, text_seq, d_model]."""
        cfg = self.cfg
        x = p["embed"][tokens]
        pos = jnp.broadcast_to(
            jnp.arange(cfg.text_seq, dtype=jnp.int32)[None], tokens.shape)
        for lp in p["layers"]:
            x = decoder_layer(lp, x, pos, cfg.lm_cfg)
        return x

    def fusion_forward(self, p, x_enc, length, x_txt):
        """Pool + project the branch, prepend to text, run LM layers."""
        cfg = self.cfg
        pad_to = max(cfg.buckets)
        x_full = jnp.pad(
            x_enc.astype(jnp.float32),
            ((0, 0), (0, pad_to - x_enc.shape[1]), (0, 0)))
        w = pool_weights(length, pad_to, cfg.fusion_slots)
        pooled = jnp.einsum("sk,bkd->bsd", w, x_full)
        pooled = pooled.astype(x_enc.dtype)
        slots = pooled @ p["proj_w"] + p["proj_b"]
        x = jnp.concatenate([slots, x_txt], axis=1)     # [rows, fused, d]
        return self._lm_layers(p, x)

    def lm_forward(self, p, x):
        return self._lm_layers(p, x)

    def _lm_layers(self, p, x):
        cfg = self.cfg
        pos = jnp.broadcast_to(
            jnp.arange(cfg.fused_seq, dtype=jnp.int32)[None],
            (x.shape[0], cfg.fused_seq))
        for lp in p["layers"]:
            x = decoder_layer(lp, x, pos, cfg.lm_cfg)
        return x

    def loss_sum(self, p, y, labels):
        """Token cross-entropy (sum) over the text positions of ``y``."""
        cfg = self.cfg
        h = rmsnorm(y[:, cfg.fusion_slots:], p["final_ln"],
                    cfg.lm_cfg.norm_eps)
        logits = (h @ p["head"].T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        w = (labels >= 0).astype(jnp.float32)
        return jnp.sum((lse - pick) * w)


def multimodal_model(arch: str, **kw) -> MultimodalModel:
    return MultimodalModel(multimodal_config(arch, **kw))

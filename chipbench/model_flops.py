"""Model FLOPs of one training step, from a configuration file's sizes.

The arithmetic of the program's ``ArchModel.model_flops``, copied so that no
change to the program moves the yardstick: 6 N D for the parameters a token
touches (embedding lookups excluded, the output head included), plus the
attention context, 6 x rows x seq x (seq / 2) x 2 x heads x head size per
attention layer.  Recomputation is not counted.
"""
from __future__ import annotations


def padded_vocab(conf: dict) -> int:
    return -(-conf["vocab_size"] // 16) * 16


def attention_block_params(conf: dict) -> int:
    """One pre-norm attention + FFN block (GELU: two matrices; SwiGLU:
    three), with its two norm scales."""
    d, width = conf["d_model"], conf["n_heads"] * conf["d_head"]
    mats = 3 if conf["activation"] == "swiglu" else 2
    return 4 * d * width + mats * d * conf["d_ff"] + 2 * d


def mamba_params(conf: dict) -> int:
    d, ds = conf["d_model"], conf["d_state"]
    nh = conf["ssm_heads"]
    di = nh * conf["ssm_head_dim"]
    in_proj = d * (2 * di + 2 * ds + nh)
    conv = conf["d_conv"] * (di + 2 * ds)
    return in_proj + conv + di * d + 3 * nh + di + d


def step_flops(conf: dict, rows: int, seq: int) -> float:
    """FLOPs of one training step over ``rows`` rows of ``seq`` tokens."""
    layers = conf["n_layers"]
    if conf["reference"] == "zamba2":
        uses = len(range(0, layers, conf["shared_period"]))
        n = layers * mamba_params(conf) + uses * attention_block_params(conf)
        attn_layers = uses
    else:
        n = layers * attention_block_params(conf)
        attn_layers = layers
    n += conf["d_model"] + padded_vocab(conf) * conf["d_model"]
    tokens = rows * seq
    attn = 6 * rows * seq * attn_layers * (seq / 2) * 2 * conf["n_heads"] \
        * conf["d_head"]
    return 6 * n * tokens + attn

"""Plain float32 GPT decoder, as the repository builds GPT-3 Large.

Pre-norm residual layers: x += Wo attn(rope(Wq h), rope(Wk h), Wv h) with
h = rmsnorm(x), then x += W2 gelu(W1 rmsnorm(x)); the loss is the mean
token cross-entropy of rmsnorm(x) @ head.T.  Departures from the published
GPT-3, all shared with the program: RMSNorm in place of LayerNorm, rotary
positions in place of learned ones, dense attention in every layer, no
biases, an untied head.

Weights are a flat dict: ``L<g>/blk/attn/wq`` for layer g, ``io/embed``.
"""
from __future__ import annotations

import jax

from chipbench.reference.common import (causal_attention,
                                        cross_entropy_sum, einsum, gelu_tanh,
                                        rmsnorm, rope)


def layer(p: dict, x, conf: dict, precision: str):
    """One decoder layer on x [b, s, d]; ``p`` holds that layer's weights
    under ``blk/...``."""
    b, s, d = x.shape
    nh, hd = conf["n_heads"], conf["d_head"]
    h = rmsnorm(x, p["blk/ln1"])
    q = einsum("bsd,de->bse", h, p["blk/attn/wq"], precision)
    k = einsum("bsd,de->bse", h, p["blk/attn/wk"], precision)
    v = einsum("bsd,de->bse", h, p["blk/attn/wv"], precision)
    q, k, v = (t.reshape(b, s, nh, hd) for t in (q, k, v))
    o = causal_attention(rope(q), rope(k), v, precision).reshape(b, s, nh * hd)
    x = x + einsum("bse,ed->bsd", o, p["blk/attn/wo"], precision)
    h = rmsnorm(x, p["blk/ln2"])
    u = gelu_tanh(einsum("bsd,df->bsf", h, p["blk/ffn/wi"], precision))
    return x + einsum("bsf,fd->bsd", u, p["blk/ffn/wo"], precision)


def loss_sum(params: dict, tokens, labels, conf: dict, precision: str):
    """Summed token cross-entropy of one row block [b, s]."""
    x = params["io/embed"][tokens]
    for g in range(conf["n_layers"]):
        pre = f"L{g}/"
        p = {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}
        x = jax.checkpoint(
            lambda p_, x_: layer(p_, x_, conf, precision))(p, x)
    h = rmsnorm(x, params["io/final_ln"]).reshape(-1, x.shape[-1])
    return cross_entropy_sum(h, params["io/head"], labels.reshape(-1),
                             precision)


"""Plain float32 Zamba2, as the repository builds Zamba2-1.2B.

A stack of Mamba-2 layers; before every ``shared_period``-th layer (0, 6, ...)
one shared pre-norm attention + SwiGLU block, with the same weights at every
use.  The Mamba-2 layer:

    h = rmsnorm(x);  z | xBC | dt = h @ in_proj
    xBC = silu(causal depthwise conv(xBC));  x_s | B | C = xBC
    dt = softplus(dt + dt_bias);  A = -exp(a_log)
    y_i = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) dt_j x_j + D x_i,
          cum = cumsum(A dt)          (the SSD recurrence in its dual form)
    x += rmsnorm(y * silu(z), gate_ln) @ out_proj

Departures from the published Zamba2-1.2B, all shared with the program: the
shared block reads the hidden state alone (the published block reads it
concatenated with the input embedding, 2 x d_model wide) and has no per-use
LoRA adapters; attention is 32 heads of 64.

Weights are a flat dict: ``L<g>/mamba/in_proj``, ``io/shared_blk/attn/wq``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.reference.common import (causal_attention, cross_entropy_sum,
                                        einsum, rmsnorm, rope)

HEAD_BLOCK = 16  # most SSD heads in one block of the dual form: bounds memory


def shared_block(p: dict, x, conf: dict, precision: str):
    b, s, _ = x.shape
    nh, hd = conf["n_heads"], conf["d_head"]
    h = rmsnorm(x, p["ln1"])
    q, k, v = (einsum("bsd,de->bse", h, p[f"attn/{w}"], precision)
               .reshape(b, s, nh, hd) for w in ("wq", "wk", "wv"))
    o = causal_attention(rope(q), rope(k), v, precision).reshape(b, s, -1)
    x = x + einsum("bse,ed->bsd", o, p["attn/wo"], precision)
    h = rmsnorm(x, p["ln2"])
    gate = einsum("bsd,df->bsf", h, p["ffn/wg"], precision)
    up = einsum("bsd,df->bsf", h, p["ffn/wi"], precision)
    return x + einsum("bsf,fd->bsd", jax.nn.silu(gate) * up, p["ffn/wo"],
                      precision)


def ssd(xs, dt, A, B, C, D, precision: str):
    """xs [b, s, nh, hd]; dt [b, s, nh]; A, D [nh]; B, C [b, s, ds]."""
    b, s, nh, hd = xs.shape
    cb = einsum("bid,bjd->bij", C, B, precision)  # [b, s, s]
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]

    @jax.checkpoint
    def heads(args):
        x_h, dt_h, a_h = args  # [b, s, H, hd], [b, s, H], [H]
        cum = jnp.cumsum(dt_h * a_h, axis=1)  # [b, s, H]
        diff = cum[:, :, None, :] - cum[:, None, :, :]  # [b, i, j, H]
        w = jnp.where(causal[None, :, :, None], jnp.exp(
            jnp.where(causal[None, :, :, None], diff, 0.0)), 0.0)
        w = w * cb[..., None] * dt_h[:, None, :, :]
        return einsum("bijh,bjhd->bihd", w, x_h, precision)

    blk = math.gcd(nh, HEAD_BLOCK)
    split = lambda t, ax: jnp.moveaxis(  # noqa: E731
        t.reshape(t.shape[:ax] + (nh // blk, blk) + t.shape[ax + 1:]), ax, 0)
    y = jax.lax.map(heads, (split(xs, 2), split(dt, 2), split(A, 0)))
    y = jnp.moveaxis(y, 0, 2).reshape(b, s, nh, hd)
    return y + D[None, None, :, None] * xs


def mamba_layer(p: dict, x, conf: dict, precision: str):
    b, s, d = x.shape
    ds, nh, hd = conf["d_state"], conf["ssm_heads"], conf["ssm_head_dim"]
    di = nh * hd
    h = rmsnorm(x, p["ln"])
    proj = einsum("bsd,de->bse", h, p["in_proj"], precision)
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * ds],
                  proj[..., 2 * di + 2 * ds:])
    k = p["conv_w"].shape[0]
    pad = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    conv = sum(pad[:, i:i + s] * p["conv_w"][i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"])
    xs, B, C = xbc[..., :di], xbc[..., di:di + ds], xbc[..., di + ds:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd(xs.reshape(b, s, nh, hd), dt, -jnp.exp(p["a_log"]), B, C,
            p["d_skip"], precision).reshape(b, s, di)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_ln"])
    return x + einsum("bse,ed->bsd", y, p["out_proj"], precision)


def loss_sum(params: dict, tokens, labels, conf: dict, precision: str):
    """Summed token cross-entropy of one row block [b, s]."""
    shared = {k[len("io/shared_blk/"):]: w for k, w in params.items()
              if k.startswith("io/shared_blk/")}
    x = params["io/embed"][tokens]
    for g in range(conf["n_layers"]):
        if g % conf["shared_period"] == 0:
            x = jax.checkpoint(
                lambda p_, x_: shared_block(p_, x_, conf, precision))(
                    shared, x)
        pre = f"L{g}/mamba/"
        p = {k[len(pre):]: w for k, w in params.items() if k.startswith(pre)}
        x = jax.checkpoint(
            lambda p_, x_: mamba_layer(p_, x_, conf, precision))(p, x)
    h = rmsnorm(x, params["io/final_ln"]).reshape(-1, x.shape[-1])
    return cross_entropy_sum(h, params["io/head"], labels.reshape(-1),
                             precision)

"""Mamba-2 block (zamba2's backbone layer) in pure JAX.

Train path uses the chunked SSD contraction (Pallas kernel or the XLA
equivalent via ``repro.kernels.ops.ssd``); decode keeps a (conv, ssm) state
pair per layer, so long_500k decode is O(1) in sequence length.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.models.common import ArchConfig, dense_init


def init_mamba_layer(keys, cfg: ArchConfig) -> dict:
    ssm = cfg.ssm
    d = cfg.d_model
    di = ssm.d_inner(d)
    nh = ssm.num_heads(d)
    ds = ssm.d_state
    conv_dim = di + 2 * ds
    return {
        "ln": jnp.zeros((d,), cfg.dtype),
        "in_proj": dense_init(next(keys), (d, 2 * di + 2 * ds + nh), cfg.dtype),
        "conv_w": dense_init(next(keys), (ssm.d_conv, conv_dim), cfg.dtype),
        "conv_b": jnp.zeros((conv_dim,), cfg.dtype),
        "a_log": jnp.zeros((nh,), jnp.float32),  # A = -exp(a_log)
        "dt_bias": jnp.zeros((nh,), jnp.float32),
        "d_skip": jnp.ones((nh,), jnp.float32),
        "gate_ln": jnp.zeros((di,), cfg.dtype),
        "out_proj": dense_init(next(keys), (di, d), cfg.dtype),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv over seq.  x: [b, s, c]; w: [k, c]."""
    k = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    out = sum(xp[:, i : i + x.shape[1]] * w[i][None, None, :] for i in range(k))
    return out + b[None, None, :]


def _split_proj(proj, cfg: ArchConfig):
    ssm = cfg.ssm
    di = ssm.d_inner(cfg.d_model)
    nh = ssm.num_heads(cfg.d_model)
    ds = ssm.d_state
    z, xbc_dt = jnp.split(proj, [di], axis=-1)
    xbc, dt = jnp.split(xbc_dt, [di + 2 * ds], axis=-1)
    return z, xbc, dt, (di, nh, ds)


@jax.named_scope("mamba")
def mamba_layer(p, x, cfg: ArchConfig):
    """x: [b, s, d] -> [b, s, d] (pre-norm residual handled here)."""
    from repro.models.layers import rmsnorm

    b, s, d = x.shape
    ssm = cfg.ssm
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    proj = h @ p["in_proj"]
    z, xbc, dt, (di, nh, ds) = _split_proj(proj, cfg)
    xbc = jax.nn.silu(_causal_conv(xbc, p["conv_w"], p["conv_b"]))
    xs, B, C = jnp.split(xbc, [di, di + ds], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    with jax.named_scope("ssd"):
        y = ops.ssd(
            xs.reshape(b, s, nh, ssm.head_dim), dt, A, B, C, p["d_skip"],
            chunk=ssm.chunk,
        ).reshape(b, s, di)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_ln"], cfg.norm_eps)
    return x + y @ p["out_proj"]


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def init_mamba_cache(batch: int, cfg: ArchConfig, dtype=None):
    ssm = cfg.ssm
    d = cfg.d_model
    di = ssm.d_inner(d)
    nh = ssm.num_heads(d)
    ds = ssm.d_state
    dtype = dtype or cfg.dtype
    return {
        "conv": jnp.zeros((batch, ssm.d_conv - 1, di + 2 * ds), dtype),
        "ssm": jnp.zeros((batch, nh, ssm.head_dim, ds), jnp.float32),
    }


def mamba_layer_decode(p, x, cache, cfg: ArchConfig):
    """x: [b, 1, d]; cache: {conv [b,k-1,c], ssm [b,nh,hd,ds]}."""
    from repro.models.layers import rmsnorm

    b = x.shape[0]
    ssm = cfg.ssm
    h = rmsnorm(x, p["ln"], cfg.norm_eps)
    proj = h @ p["in_proj"]
    z, xbc, dt, (di, nh, ds) = _split_proj(proj[:, 0], cfg)
    # rolling conv state
    window = jnp.concatenate([cache["conv"], xbc[:, None]], axis=1)  # [b,k,c]
    conv_out = jnp.einsum("bkc,kc->bc", window, p["conv_w"]) + p["conv_b"]
    xbc_t = jax.nn.silu(conv_out)
    xs, B, C = jnp.split(xbc_t, [di, di + ds], axis=-1)
    dt_t = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    A = -jnp.exp(p["a_log"])
    y, ssm_state = ops.ssd_decode_step(
        cache["ssm"], xs.reshape(b, nh, ssm.head_dim), dt_t, A, B, C, p["d_skip"]
    )
    y = y.reshape(b, di)
    y = rmsnorm(y * jax.nn.silu(z), p["gate_ln"], cfg.norm_eps)
    out = x + (y @ p["out_proj"])[:, None]
    return out, {"conv": window[:, 1:], "ssm": ssm_state}

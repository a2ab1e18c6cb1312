"""Pallas TPU fused RMSNorm (memory-bound row kernel)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)  # [br, d]
    scale = s_ref[...].astype(jnp.float32)  # [1, d]
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * (1.0 + scale)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x: [..., d]; scale: [d]."""
    shape = x.shape
    d = shape[-1]
    rows = x.size // d
    x2 = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    nr = -(-rows // block_rows)
    pad = nr * block_rows - rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(nr,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nr * block_rows, d), x.dtype),
        interpret=interpret,
        name="rmsnorm",
    )(x2, scale.reshape(1, d))
    return out[:rows].reshape(shape)

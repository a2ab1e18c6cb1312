"""Pallas TPU flash decode: one query position against a long KV cache.

Grid: (batch, q_heads, kv_blocks), kv innermost; online-softmax state in VMEM
scratch.  The valid cache length arrives via scalar prefetch (SMEM) so the
same compiled kernel serves every decode position.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            window: int, block_k: int, num_k_blocks: int):
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[0]
    k_start = ki * block_k
    run = k_start < length
    if window > 0:
        run = jnp.logical_and(run, k_start + block_k > length - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)  # [1, hd]
        k = k_ref[0, 0].astype(jnp.float32)  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [1, bk]
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        mask = k_pos < length
        if window > 0:
            mask &= k_pos >= length - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("window", "block_k", "interpret")
)
def flash_decode(q, k_cache, v_cache, length, *, window: int = 0,
                 block_k: int = 512, interpret: bool = False):
    """q: [b, hq, 1, hd]; caches: [b, hkv, S, hd]; length: [] int32 scalar.

    Scale must be pre-applied to q.  Returns [b, hq, 1, hd].
    """
    b, hq, _, hd = q.shape
    _, hkv, S, _ = k_cache.shape
    g = hq // hkv
    block_k = min(block_k, S)
    nk = -(-S // block_k)
    pad = nk * block_k - S
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, 0), (0, pad), (0, 0)))

    kernel = functools.partial(
        _kernel, window=window, block_k=block_k, num_k_blocks=nk
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, hq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hd), lambda bi, hi, ki, _len: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, _len, g=g: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, block_k, hd), lambda bi, hi, ki, _len, g=g: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, hd), lambda bi, hi, ki, _len: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hq, 1, hd), q.dtype),
        interpret=interpret,
        name="flash_decode",
    )(jnp.asarray(length, jnp.int32).reshape(1), q, k_cache, v_cache)
    return out

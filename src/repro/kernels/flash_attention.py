"""Pallas TPU flash attention (forward), MXU-aligned BlockSpec tiling.

Grid: (batch, q_heads, q_blocks, kv_blocks) with kv innermost so the
(m, l, acc) online-softmax state lives in VMEM scratch across kv steps.
GQA maps query head h to kv head h // (hq // hkv) in the k/v index maps.
Layout: [b, h, s, hd] (transposed from the model's [b, s, h, hd] by ops.py).

Blocks default to ``BLOCK`` = 1024 rows of q and of k/v, at most half a
sliding window and never more than the sequence (``default_block``), so a
causal call at seq 2048 is 4 grid steps a head, 3 of them on or below the
diagonal.  On a v5e these beat 512-row blocks at 1 x 16 x 2048 x 96 and
1 x 8 x 4096 x 128, and half-window blocks beat them under gemma3-4b's
1024-wide window at head 256 (PERF.md, section 6).

``kv_block_range`` gives the kv blocks a q block needs (none above the
causal diagonal, none before the first the window reaches); the k/v index
maps clamp into that range, so a step outside it repeats the previous block
index and Pallas copies nothing for it, and ``pl.when`` skips its compute.
Only blocks that straddle the diagonal, the window's edge or the padded tail
build the iota mask.

Both matmuls take the inputs' own dtype on the MXU with float32
accumulation: Q K^T from the stored q and k, P V from ``p`` cast to v's
dtype (as ``models.layers.blocked_attention`` does).  The softmax state
(m, l, acc) stays float32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
BLOCK = 1024


def default_block(seq: int, window: int) -> int:
    """Rows of a q or kv block: ``BLOCK``, at most half a sliding window in
    whole 128s (so a q block computes few keys outside its window), never
    more than ``seq``."""
    block = BLOCK
    if window > 0:
        block = max(128, min(BLOCK, window // 2) // 128 * 128)
    return min(block, seq)


def kv_block_range(qi, *, block_q: int, block_k: int, num_k_blocks: int,
                   causal: bool, window: int):
    """First and last kv block that q block ``qi`` attends to (inclusive).

    Causal: no block wholly above the diagonal.  Window: no block wholly
    before the first key the block's first query reaches.
    """
    q_start = qi * block_q
    lo = 0
    hi = num_k_blocks - 1
    if causal:
        hi = jnp.minimum(hi, (q_start + block_q - 1) // block_k)
    if window > 0:
        lo = jnp.maximum(q_start - window + 1, 0) // block_k
    return lo, hi


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            causal: bool, window: int, sk: int, block_q: int, block_k: int,
            num_k_blocks: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_k
    lo, hi = kv_block_range(qi, block_q=block_q, block_k=block_k,
                            num_k_blocks=num_k_blocks, causal=causal,
                            window=window)
    run = jnp.logical_and(ki >= lo, ki <= hi)
    # A block needs the mask if some (q, k) in it lies above the diagonal,
    # at or past the window's edge, or in the padded tail of k.
    edge = k_start + block_k > sk
    if causal:
        edge |= k_start + block_k - 1 > q_start
    if window > 0:
        edge |= q_start + block_q - 1 - k_start >= window

    def body(masked: bool):
        s = jax.lax.dot_general(
            q_ref[0, 0], k_ref[0, 0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bq, bk]
        if masked:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            mask = k_pos < sk
            if causal:
                mask &= q_pos >= k_pos
            if window > 0:
                mask &= q_pos - k_pos < window
            s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        v = v_ref[0, 0]
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[...] = m_new

    pl.when(run & edge)(functools.partial(body, True))
    pl.when(run & jnp.logical_not(edge))(functools.partial(body, False))

    @pl.when(ki == num_k_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (
            acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int | None = None, block_k: int | None = None,
                        interpret: bool = False):
    """q: [b, hq, sq, hd]; k, v: [b, hkv, sk, hd] -> [b, hq, sq, hd].

    Scale (hd**-0.5) must be pre-applied to q by the caller (ops.py does).
    ``block_q``/``block_k`` default to ``default_block``; neither exceeds
    its sequence.
    """
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    g = hq // hkv
    block_q = min(block_q or default_block(sq, window), sq)
    block_k = min(block_k or default_block(sk, window), sk)
    nq = -(-sq // block_q)
    nk = -(-sk // block_k)
    q_pad = nq * block_q - sq
    k_pad = nk * block_k - sk
    if q_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, q_pad), (0, 0)))
    if k_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, k_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, k_pad), (0, 0)))

    kv_range = functools.partial(
        kv_block_range, block_q=block_q, block_k=block_k, num_k_blocks=nk,
        causal=causal, window=window)

    def kv_map(bi, hi, qi, ki):
        lo, last = kv_range(qi)
        return bi, hi // g, jnp.clip(ki, lo, last), 0

    kernel = functools.partial(
        _kernel, causal=causal, window=window, sk=sk,
        block_q=block_q, block_k=block_k, num_k_blocks=nk,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
            pl.BlockSpec((1, 1, block_k, hd), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, nq * block_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out[:, :, :sq, :]

"""Pallas TPU Mamba-2 SSD chunked scan (zamba2's compute hot spot).

Grid: (batch, heads, chunks) with chunks innermost; the [hd, ds] inter-chunk
state lives in VMEM scratch.  Per chunk: dense intra-chunk attention-like
contraction (MXU) + rank-1 state update — the TPU-native re-blocking of the
paper-adjacent GPU SSD kernel (HBM->VMEM streaming instead of warp shuffles).

Layout: x is head-major [b, nh, s, hd] (ops.py transposes), so every block's
last two dims are (chunk, hd) and tile the TPU's (8, 128) vregs.  The
per-head decay inputs arrive in two layouts that also tile: columns
[b, s, nh] (one lane per head, selected in-kernel) and rows [b, nh, nc, chunk]
(the whole head resident, one sublane per chunk).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _head_column(ref, hi):
    """[Q, nh] block -> [Q, 1] column of head ``hi`` (masked lane sum)."""
    blk = ref[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 1)
    return jnp.sum(jnp.where(lane == hi, blk, 0.0), axis=1, keepdims=True)


def _kernel(x_ref, dt_ref, cumc_ref, cumr_ref, b_ref, c_ref, d_ref, y_ref,
            state_scr, *, chunk: int):
    hi = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_scr[...] = jnp.zeros_like(state_scr)

    x = x_ref[0, 0].astype(jnp.float32)       # [Q, hd]
    dt = _head_column(dt_ref, hi)             # [Q, 1]
    cum = _head_column(cumc_ref, hi)          # [Q, 1] inclusive log decay
    cum_row = cumr_ref[0, 0, pl.ds(ci, 1), :]  # [1, Q] the same, as a row
    B = b_ref[0].astype(jnp.float32)          # [Q, ds]
    C = c_ref[0].astype(jnp.float32)          # [Q, ds]
    D = d_ref[hi]

    # intra-chunk: y[i] += sum_{j<=i} (C_i.B_j) exp(cum_i - cum_j) dt_j x_j
    g = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, Q]
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # masked before exp, as in ops._ssd_xla_chunked: no exp of the positive
    # differences above the diagonal
    decay = jnp.exp(jnp.where(ii >= jj, cum - cum_row, -jnp.inf))
    y = jax.lax.dot_general(g * decay, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [Q, hd]
    # inter-chunk: y[i] += exp(cum_i) * C_i @ state^T
    state = state_scr[...]                    # [hd, ds]
    y += jnp.exp(cum) * jax.lax.dot_general(
        C, state, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    # state update: state = exp(cum_Q) state + sum_j exp(cum_Q - cum_j) dt_j x_j B_j^T
    last = cum_row[:, chunk - 1:chunk]        # [1, 1]
    wj = dt * jnp.exp(last - cum)             # [Q, 1]
    # (broadcast lanes first, then sublanes: Mosaic does one at a time)
    keep = jnp.exp(jnp.broadcast_to(last, (1, state.shape[1])))  # [1, ds]
    state_scr[...] = keep * state + jax.lax.dot_general(
        x * wj, B, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    y_ref[0, 0] = (y + D * x).astype(y_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, D, *, chunk: int = 128, interpret: bool = False):
    """Chunked SSD.  x: [b, nh, s, hd] (head-major); dt: [b, s, nh];
    A, D: [nh]; B, C: [b, s, ds].  Returns y: [b, nh, s, hd].  Requires
    s % chunk == 0 (ops.py pads).
    """
    b, nh, s, hd = x.shape
    ds = B.shape[-1]
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    dt = dt.astype(jnp.float32)
    # chunk-local inclusive cumsum of the log decay (cheap, elementwise XLA)
    cum = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(b, nc, chunk, nh),
                     axis=2)
    cum_cols = cum.reshape(b, s, nh)
    cum_rows = jnp.transpose(cum, (0, 3, 1, 2))  # [b, nh, nc, chunk]

    return pl.pallas_call(
        functools.partial(_kernel, chunk=chunk),
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, chunk, nh), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, nh), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, 1, nc, chunk), lambda bi, hi, ci: (bi, hi, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, chunk, ds), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, hd),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nh, s, hd), x.dtype),
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, cum_cols, cum_rows, B, C, D.astype(jnp.float32))

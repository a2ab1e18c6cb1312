"""Public kernel API: one entry per op, the implementation chosen by platform.

A program lowered for a TPU runs the Pallas kernels (``pl.pallas_call``,
forward custom-vjp'd onto an XLA recompute backward); on every other platform
it runs the pure-jnp blocked implementations.  The choice is made when the
computation is lowered (``jax.lax.platform_dependent``), so a program compiled
from a CPU process for a described TPU topology takes the kernels too.

``backend=`` pins one side for tests: ``"xla"`` the jnp path, ``"interpret"``
the Pallas kernels under the interpreter (any platform).
"""
from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import flash_decode as _fd
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd
from repro.kernels import ref as _ref

Backend = Literal["xla", "interpret"]


def _dispatch(backend: Backend | None, kernel, xla, *args):
    """``kernel(*args)`` on TPU, ``xla(*args)`` elsewhere, unless pinned."""
    if backend == "xla":
        return xla(*args)
    if backend == "interpret":
        return kernel(*args, interpret=True)
    assert backend is None, backend
    return jax.lax.platform_dependent(*args, tpu=kernel, default=xla)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------
def flash_attention(q, k, v, positions, *, causal: bool = True, window: int = 0,
                    backend: Backend | None = None):
    """q: [b, sq, hq, hd]; k, v: [b, sk, hkv, hd]; positions: [b, sq]."""

    def xla(q, k, v, positions):
        from repro.models.layers import blocked_attention

        return blocked_attention(q, k, v, positions, causal, window, 256)

    def kernel(q, k, v, positions, interpret=False):
        # Pallas path assumes training self-attention: positions == arange(sq).
        hd = q.shape[-1]
        qt = jnp.swapaxes(q, 1, 2) * (hd ** -0.5)
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out = _pallas_attention(qt.astype(q.dtype), kt, vt, causal, window,
                                interpret)
        return jnp.swapaxes(out, 1, 2)

    return _dispatch(backend, kernel, xla, q, k, v, positions)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _pallas_attention(q, k, v, causal, window, interpret):
    return _fa.flash_attention_fwd(q, k, v, causal=causal, window=window,
                                   interpret=interpret)


def _pallas_attention_fwd(q, k, v, causal, window, interpret):
    return _pallas_attention(q, k, v, causal, window, interpret), (q, k, v)


def _pallas_attention_bwd(causal, window, interpret, res, g):
    q, k, v = res
    # Recompute-based backward through the XLA oracle (same math).
    def f(q_, k_, v_):
        b, h, sq, hd = q_.shape
        pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
        o = _ref.attention_ref(
            jnp.swapaxes(q_ * hd**0.5, 1, 2), jnp.swapaxes(k_, 1, 2),
            jnp.swapaxes(v_, 1, 2), pos, causal, window)
        return jnp.swapaxes(o, 1, 2)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


_pallas_attention.defvjp(_pallas_attention_fwd, _pallas_attention_bwd)


def decode_attention(q, k_cache, v_cache, length, *, window: int = 0,
                     backend: Backend | None = None):
    """q: [b, 1, hq, hd]; caches: [b, S, hkv, hd]; length: scalar int."""

    def xla(q, k_cache, v_cache, length):
        lengths = jnp.full((q.shape[0],), length, jnp.int32)
        return _ref.decode_ref(q, k_cache, v_cache, lengths, window=window)

    def kernel(q, k_cache, v_cache, length, interpret=False):
        hd = q.shape[-1]
        qt = jnp.swapaxes(q, 1, 2) * (hd ** -0.5)
        out = _fd.flash_decode(
            qt.astype(q.dtype), jnp.swapaxes(k_cache, 1, 2),
            jnp.swapaxes(v_cache, 1, 2), length, window=window,
            interpret=interpret)
        return jnp.swapaxes(out, 1, 2)

    return _dispatch(backend, kernel, xla, q, k_cache, v_cache,
                     jnp.asarray(length, jnp.int32))


# ---------------------------------------------------------------------------
# SSD (Mamba-2)
# ---------------------------------------------------------------------------
def ssd(x, dt, A, B, C, D, *, chunk: int = 128, backend: Backend | None = None):
    """x: [b, s, nh, hd]; dt: [b, s, nh]; A, D: [nh]; B, C: [b, s, ds]."""

    def xla(x, dt, A, B, C, D):
        return _ssd_xla_chunked(x, dt, A, B, C, D, chunk)

    def kernel(x, dt, A, B, C, D, interpret=False):
        s = x.shape[1]
        pad = (-s) % chunk
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
            dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
            B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
            C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        y = _pallas_ssd(x, dt, A, B, C, D, chunk, interpret)
        return y[:, :s]

    return _dispatch(backend, kernel, xla, x, dt, A, B, C, D)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _pallas_ssd(x, dt, A, B, C, D, chunk, interpret):
    # the kernel is head-major; the model is sequence-major
    y = _ssd.ssd_scan(jnp.swapaxes(x, 1, 2), dt, A, B, C, D, chunk=chunk,
                      interpret=interpret)
    return jnp.swapaxes(y, 1, 2)


def _pallas_ssd_fwd(x, dt, A, B, C, D, chunk, interpret):
    return _pallas_ssd(x, dt, A, B, C, D, chunk, interpret), (x, dt, A, B, C, D)


def _pallas_ssd_bwd(chunk, interpret, res, g):
    x, dt, A, B, C, D = res
    _, vjp = jax.vjp(lambda *a: _ssd_xla_chunked(*a, chunk), x, dt, A, B, C, D)
    return vjp(g)


_pallas_ssd.defvjp(_pallas_ssd_fwd, _pallas_ssd_bwd)


def _ssd_xla_chunked(x, dt, A, B, C, D, chunk: int):
    """Chunked SSD in pure jnp (same algorithm as the kernel, batched).

    NOT inner-checkpointed: the executor already remats per layer slot, and
    a nested checkpoint made B recompute the scan 3x (EXPERIMENTS §Perf).
    Contractions run in bf16 with fp32 accumulation (gates/cumsums fp32).
    """
    b, s, nh, hd = x.shape
    ds = B.shape[-1]
    pad = (-s) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
    sp = x.shape[1]
    nc = sp // chunk
    ct = jnp.bfloat16 if x.dtype == jnp.bfloat16 else jnp.float32
    xc = x.reshape(b, nc, chunk, nh, hd)
    dtf = dt.astype(jnp.float32).reshape(b, nc, chunk, nh)
    Bc = B.astype(ct).reshape(b, nc, chunk, ds)
    Cc = C.astype(ct).reshape(b, nc, chunk, ds)
    Af = A.astype(jnp.float32)

    a = Af[None, None, None, :] * dtf  # [b, nc, Q, nh]
    cum = jnp.cumsum(a, axis=2)
    g = jnp.einsum("bcid,bcjd->bcij", Cc, Bc,
                   preferred_element_type=jnp.float32)  # [b, nc, Q, Q]
    ii = jnp.arange(chunk)
    tri = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    # the exponent is masked before exp: above the diagonal cum_i - cum_j is
    # a positive sum of up to chunk - 1 dts, whose exp overflows float32 past
    # 88.7, and the backward would multiply that inf by a zero
    decay = jnp.exp(jnp.where(
        tri, cum[:, :, :, None, :] - cum[:, :, None, :, :],
        -jnp.inf))  # [b, nc, Q, Q, nh]
    w = (g[..., None] * decay * dtf[:, :, None, :, :]).astype(ct)
    y_intra = jnp.einsum("bcijn,bcjnd->bcind", w, xc.astype(ct),
                         preferred_element_type=jnp.float32)

    # inter-chunk state passing (scan over chunks)
    xf = xc.astype(jnp.float32)
    Bf = Bc.astype(jnp.float32)
    Cf = Cc.astype(jnp.float32)
    chunk_in = jnp.einsum(
        "bcjn,bcjnd,bcjs->bcnds", dtf * jnp.exp(cum[:, :, -1:, :] - cum), xf, Bf
    )  # [b, nc, nh, hd, ds]
    total_decay = jnp.exp(cum[:, :, -1])  # [b, nc, nh]

    def scan_fn(h, inp):
        dec, cin = inp
        h_new = h * dec[..., None, None] + cin
        return h_new, h  # emit the state *entering* this chunk

    h0 = jnp.zeros((b, nh, hd, ds), jnp.float32)
    _, h_in = jax.lax.scan(
        scan_fn, h0,
        (jnp.moveaxis(total_decay, 1, 0), jnp.moveaxis(chunk_in, 1, 0)),
    )
    h_in = jnp.moveaxis(h_in, 0, 1)  # [b, nc, nh, hd, ds]
    y_inter = jnp.einsum(
        "bcis,bcnds,bcin->bcind", Cf, h_in, jnp.exp(cum)
    )
    y = (y_intra + y_inter).reshape(b, sp, nh, hd)
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y[:, :s].astype(x.dtype) if pad else y.astype(x.dtype)


def ssd_decode_step(state, x, dt, A, B, C, D):
    """Single-token SSD update.  state: [b, nh, hd, ds]; x: [b, nh, hd];
    dt: [b, nh]; B, C: [b, ds].  Returns (y [b, nh, hd], new_state)."""
    decay = jnp.exp(A.astype(jnp.float32)[None, :] * dt.astype(jnp.float32))
    upd = jnp.einsum("bnh,bs->bnhs", x.astype(jnp.float32) * dt[..., None], B.astype(jnp.float32))
    state = state * decay[..., None, None] + upd
    y = jnp.einsum("bnhs,bs->bnh", state, C.astype(jnp.float32))
    y = y + D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype), state


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def rmsnorm(x, scale, *, eps: float = 1e-5, backend: Backend | None = None):

    def xla(x, scale):
        return _ref.rmsnorm_ref(x, scale, eps)

    def kernel(x, scale, interpret=False):
        return _rn.rmsnorm(x, scale, eps=eps, interpret=interpret)

    return _dispatch(backend, kernel, xla, x, scale)

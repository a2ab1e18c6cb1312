"""Plain pieces shared by the references: matrix products at a stated
precision, RMSNorm, rotary embedding, causal attention, cross-entropy, AdamW.

Nothing here imports the program.  ``precision`` is ``"f32"`` for the
reference itself (float32 at ``highest``, so that a TPU does not round the
operands to bfloat16) or ``"fp8"`` for the control: every matrix product
takes its operands rounded to float8 e4m3 with one scale per tensor, and its
backward the output gradient rounded to float8 e5m2, as fp8 training does.

Every rounding is an explicit ``reduce_precision``: XLA may drop a pair of
converts to a narrower type and back (``xla_allow_excess_precision``, on by
default on a TPU), which would leave the values unrounded.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
E4M3, E5M2 = (4, 3), (5, 2)  # exponent and mantissa bits


def round_to(x, dtype):
    """float32 x rounded to the nearest value of ``dtype``."""
    fi = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=fi.nexp,
                                    mantissa_bits=fi.nmant)


def _fp8(x, bits):
    """x rounded to an 8-bit float of ``bits`` under a per-tensor scale that
    maps its largest magnitude to the format's largest finite number (with
    IEEE-style infinities: 240 for e4m3, 57344 for e5m2)."""
    e, m = bits
    top = (2.0 - 2.0 ** -m) * 2.0 ** (2 ** (e - 1) - 1)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    q = jax.lax.reduce_precision(x * scale, exponent_bits=e, mantissa_bits=m)
    return q / scale


def _einsum(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _einsum8(spec, a, b):
    return _einsum(spec, _fp8(a, E4M3), _fp8(b, E4M3))


def _einsum8_fwd(spec, a, b):
    return _einsum8(spec, a, b), (a, b)


def _einsum8_bwd(spec, res, g):
    a, b = res
    _, vjp = jax.vjp(lambda x, y: _einsum(spec, x, y),
                     _fp8(a, E4M3), _fp8(b, E4M3))
    return vjp(_fp8(g, E5M2))


_einsum8.defvjp(_einsum8_fwd, _einsum8_bwd)


def einsum(spec: str, a, b, precision: str):
    if precision == "f32":
        return _einsum(spec, a, b)
    if precision == "fp8":
        return _einsum8(spec, a, b)
    raise ValueError(precision)


def rmsnorm(x, scale, eps: float = 1e-5):
    """x / rms(x) * (1 + scale)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def rope(x, theta: float = 10_000.0):
    """Rotary embedding of x [b, s, h, d] at positions 0..s-1: the first and
    second halves of each head are the two coordinates of d/2 rotations."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v, precision: str):
    """Softmax attention with a causal mask; q, k, v [b, s, h, d]."""
    d = q.shape[-1]
    s = einsum("bqhd,bkhd->bhqk", q * d ** -0.5, k, precision)
    n = q.shape[1]
    mask = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return einsum("bhqk,bkhd->bqhd", p, v, precision)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def cross_entropy_sum(h, head, labels, precision: str):
    """Sum over tokens of -log softmax(h @ head.T)[label]."""
    logits = einsum("td,vd->tv", h, head, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    pick = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - pick)


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------
def lr_at(opt: dict, step: int) -> float:
    """Linear warm-up, then a cosine from lr down to min_frac * lr."""
    warm = min(1.0, (step + 1) / max(opt["warmup_steps"], 1))
    prog = min(max((step - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    cos = opt["min_frac"] + (1 - opt["min_frac"]) * 0.5 * (
        1 + math.cos(math.pi * prog))
    return opt["lr"] * warm * cos


def adamw(opt: dict, params, grads, m, v, step: int, lr: float):
    """One AdamW step over dicts of float32 arrays.  ``opt["clip"]``: scale
    the gradient to that global norm first (None: no clipping).
    ``opt["store"]``: the dtype the weights are kept in between steps."""
    if opt["clip"] is not None:
        gn = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
        scale = jnp.minimum(1.0, opt["clip"] / (gn + 1e-12))
        grads = {k: g * scale for k, g in grads.items()}
    b1, b2 = opt["beta1"], opt["beta2"]
    new_p, new_m, new_v = {}, {}, {}
    for k, p in params.items():
        g = grads[k]
        new_m[k] = b1 * m[k] + (1 - b1) * g
        new_v[k] = b2 * v[k] + (1 - b2) * g * g
        mh = new_m[k] / (1 - b1 ** (step + 1))
        vh = new_v[k] / (1 - b2 ** (step + 1))
        p = p - lr * (mh / (jnp.sqrt(vh) + opt["eps"])
                      + opt["weight_decay"] * p)
        new_p[k] = round_to(p, opt["store"])
    return new_p, new_m, new_v, grads

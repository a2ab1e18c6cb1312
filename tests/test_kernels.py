"""Per-kernel validation: Pallas (interpret=True) vs pure-jnp oracles.

Sweeps shapes/dtypes per the rubric; hypothesis property tests cover the
online-softmax and chunked-scan invariants.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ModuleNotFoundError:  # deterministic fallback (tests/_hyp_stub.py)
    from _hyp_stub import given, settings, strategies as st

from repro.kernels import flash_attention as fa
from repro.kernels import ops, ref


def rand(rng, *shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,sq,hq,hkv,hd,window",
    [
        (1, 128, 4, 4, 64, 0),      # MHA, exact block multiple
        (2, 200, 8, 2, 64, 0),      # GQA, ragged seq
        (1, 384, 8, 1, 128, 0),     # MQA (granite-style kv=1)
        (2, 160, 4, 4, 64, 64),     # sliding window (gemma3-style)
        (1, 96, 4, 2, 32, 0),       # smaller than one block
        (1, 256, 4, 4, 96, 0),      # head 96 (gpt3-large, the chip cells)
    ],
)
def test_flash_attention_matches_oracle(b, sq, hq, hkv, hd, window, dtype):
    rng = np.random.default_rng(hash((b, sq, hq, window)) % 2**32)
    q = rand(rng, b, sq, hq, hd, dtype=dtype)
    k = rand(rng, b, sq, hkv, hd, dtype=dtype)
    v = rand(rng, b, sq, hkv, hd, dtype=dtype)
    pos = jnp.broadcast_to(jnp.arange(sq)[None], (b, sq))
    want = ref.attention_ref(q, k, v, pos, True, window)
    got = ops.flash_attention(q, k, v, pos, causal=True, window=window,
                              backend="interpret")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "sq,hq,hkv,hd,causal,window,bq,bk",
    [
        (1024, 2, 2, 96, True, 0, 256, 256),   # 4x4 blocks, diagonal clamp
        (1000, 2, 1, 64, True, 0, 256, 128),   # ragged tail, bq > bk, MQA
        (640, 4, 2, 96, True, 0, 128, 256),    # bq < bk, GQA
        (1024, 2, 2, 64, True, 300, 128, 128),  # window spans 4 kv blocks
        (1000, 2, 2, 64, False, 0, 256, 256),  # no causal mask, ragged tail
    ],
)
def test_flash_attention_small_blocks_match_oracle(sq, hq, hkv, hd, causal,
                                                   window, bq, bk, dtype):
    """Several q and kv blocks a head: the kv clamp, the skipped blocks and
    the mask applied only on the blocks that straddle an edge."""
    rng = np.random.default_rng(sq + 7 * window + bq)
    q = rand(rng, 1, sq, hq, hd, dtype=dtype)
    k = rand(rng, 1, sq, hkv, hd, dtype=dtype)
    v = rand(rng, 1, sq, hkv, hd, dtype=dtype)
    pos = jnp.arange(sq)[None]
    want = ref.attention_ref(q, k, v, pos, causal, window)
    t = lambda x: jnp.swapaxes(x, 1, 2)  # noqa: E731
    got = t(fa.flash_attention_fwd(
        (t(q) * hd**-0.5).astype(dtype), t(k), t(v), causal=causal,
        window=window, block_q=bq, block_k=bk, interpret=True))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize(
    "sq,bq,bk,causal,window",
    [(2048, 1024, 1024, True, 0), (2048, 512, 512, True, 0),
     (1000, 256, 128, True, 0),
     (640, 128, 256, True, 0), (4096, 512, 512, True, 1024),
     (1024, 128, 128, True, 300), (1000, 256, 256, False, 0),
     (1000, 128, 256, False, 200), (96, 96, 96, True, 0)],
)
def test_kv_block_range_is_exactly_the_blocks_attended(sq, bq, bk, causal,
                                                       window):
    """The clamped kv range holds every block with an attended (q, k) pair
    of the q block, and no block above the diagonal or before the window."""
    nq, nk = -(-sq // bq), -(-sq // bk)
    qp = np.arange(nq * bq)[:, None]
    kp = np.arange(sq)[None, :]
    attend = np.ones((nq * bq, sq), bool)
    if causal:
        attend &= qp >= kp
    if window:
        attend &= qp - kp < window
    for qi in range(nq):
        lo, hi = (int(x) for x in fa.kv_block_range(
            qi, block_q=bq, block_k=bk, num_k_blocks=nk, causal=causal,
            window=window))
        rows = attend[qi * bq:(qi + 1) * bq]
        used = [ki for ki in range(nk)
                if rows[:, ki * bk:(ki + 1) * bk].any()]
        assert (lo, hi) == (used[0], used[-1]), (qi, lo, hi, used)
        assert used == list(range(lo, hi + 1))


@pytest.mark.parametrize(
    "seq,window,want",
    [(2048, 0, 1024), (4096, 0, 1024), (100, 0, 100), (4096, 1024, 512),
     (4096, 4096, 1024), (4096, 300, 128), (160, 64, 128), (96, 64, 96)],
)
def test_default_block(seq, window, want):
    assert fa.default_block(seq, window) == want


def test_xla_blocked_attention_matches_oracle():
    rng = np.random.default_rng(0)
    q = rand(rng, 2, 200, 8, 64)
    k = rand(rng, 2, 200, 2, 64)
    v = rand(rng, 2, 200, 2, 64)
    pos = jnp.broadcast_to(jnp.arange(200)[None], (2, 200))
    for window in (0, 64):
        want = ref.attention_ref(q, k, v, pos, True, window)
        got = ops.flash_attention(q, k, v, pos, causal=True, window=window,
                                  backend="xla")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_attention_grad_matches_oracle_grad():
    rng = np.random.default_rng(1)
    q = rand(rng, 1, 64, 4, 32)
    k = rand(rng, 1, 64, 2, 32)
    v = rand(rng, 1, 64, 2, 32)
    pos = jnp.broadcast_to(jnp.arange(64)[None], (1, 64))
    g_ref = jax.grad(lambda q: ref.attention_ref(q, k, v, pos, True, 0).sum())(q)
    g_xla = jax.grad(lambda q: ops.flash_attention(q, k, v, pos).sum())(q)
    np.testing.assert_allclose(np.asarray(g_xla), np.asarray(g_ref), atol=2e-4)


@settings(max_examples=15, deadline=None)
@given(
    sq=st.integers(16, 300),
    hq=st.sampled_from([1, 2, 4, 8]),
    g=st.sampled_from([1, 2, 4]),
    hd=st.sampled_from([32, 64]),
    seed=st.integers(0, 99),
)
def test_property_flash_attention(sq, hq, g, hd, seed):
    hkv = max(1, hq // g)
    hq = hkv * g
    rng = np.random.default_rng(seed)
    q = rand(rng, 1, sq, hq, hd)
    k = rand(rng, 1, sq, hkv, hd)
    v = rand(rng, 1, sq, hkv, hd)
    pos = jnp.arange(sq)[None]
    want = ref.attention_ref(q, k, v, pos, True, 0)
    got = ops.flash_attention(q, k, v, pos, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5)


# ---------------------------------------------------------------------------
# flash decode
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,S,hq,hkv,hd,length,window",
    [
        (2, 300, 8, 2, 64, 157, 0),
        (1, 1024, 4, 1, 128, 1024, 0),
        (2, 512, 4, 4, 64, 300, 128),  # windowed decode
        (1, 64, 2, 2, 32, 1, 0),       # first token
    ],
)
def test_flash_decode_matches_oracle(b, S, hq, hkv, hd, length, window, dtype):
    rng = np.random.default_rng(hash((b, S, length)) % 2**32)
    q = rand(rng, b, 1, hq, hd, dtype=dtype)
    kc = rand(rng, b, S, hkv, hd, dtype=dtype)
    vc = rand(rng, b, S, hkv, hd, dtype=dtype)
    want = ref.decode_ref(q, kc, vc, jnp.full((b,), length, jnp.int32), window)
    got = ops.decode_attention(q, kc, vc, length, window=window,
                               backend="interpret")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])


def test_decode_length_is_dynamic():
    """Same compiled kernel must serve any position (scalar prefetch)."""
    rng = np.random.default_rng(3)
    q = rand(rng, 1, 1, 4, 32)
    kc = rand(rng, 1, 256, 2, 32)
    vc = rand(rng, 1, 256, 2, 32)
    for length in (1, 100, 256):
        want = ref.decode_ref(q, kc, vc, jnp.full((1,), length, jnp.int32))
        got = ops.decode_attention(q, kc, vc, length, backend="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "b,s,nh,hd,ds,chunk",
    [
        (2, 256, 4, 32, 16, 64),
        (1, 128, 8, 64, 64, 128),   # zamba2-like state size
        (1, 192, 2, 16, 8, 64),     # non-power-of-two length
        (2, 100, 2, 16, 8, 64),     # needs padding
    ],
)
def test_ssd_matches_sequential_oracle(b, s, nh, hd, ds, chunk, dtype):
    rng = np.random.default_rng(hash((b, s, nh)) % 2**32)
    x = rand(rng, b, s, nh, hd, dtype=dtype)
    dt = jnp.abs(rand(rng, b, s, nh)) * 0.1
    A = -jnp.abs(rand(rng, nh))
    B = rand(rng, b, s, ds)
    C = rand(rng, b, s, ds)
    D = rand(rng, nh)
    want = ref.ssd_ref(x, dt, A, B, C, D)
    got_p = ops.ssd(x, dt, A, B, C, D, chunk=chunk, backend="interpret")
    got_x = ops.ssd(x, dt, A, B, C, D, chunk=chunk, backend="xla")
    # bf16: the XLA path contracts in bf16 (fp32 accumulation) per the
    # §Perf zamba2 iteration — rtol covers bf16 mantissa rounding on values
    # whose magnitude grows with the accumulation length
    atol, rtol = (5e-4, 1e-5) if dtype == jnp.float32 else (6e-2, 3e-2)
    np.testing.assert_allclose(
        np.asarray(got_p, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=rtol)
    np.testing.assert_allclose(
        np.asarray(got_x, np.float32), np.asarray(want, np.float32),
        atol=atol, rtol=rtol)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize(
    "dt_lo,dt_hi",
    [
        (0.0, 0.1),
        (1.5, 2.5),  # |A| dt over 63 positions: 95-236, past f32 exp's 88.7
    ],
)
def test_ssd_grad_matches_sequential_oracle(dt_lo, dt_hi, backend):
    rng = np.random.default_rng(17)
    b, s, nh, hd, ds, chunk = 1, 128, 2, 16, 8, 64
    x = rand(rng, b, s, nh, hd)
    dt = jnp.asarray(rng.uniform(dt_lo, dt_hi, (b, s, nh)), jnp.float32)
    A = -jnp.asarray(rng.uniform(1.0, 1.5, nh), jnp.float32)
    B = rand(rng, b, s, ds)
    C = rand(rng, b, s, ds)
    D = rand(rng, nh)
    g = rand(rng, b, s, nh, hd)

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(fn(*a) * g),
                        argnums=tuple(range(6)))(x, dt, A, B, C, D)

    want = grads(ref.ssd_ref)
    got = grads(lambda *a: ops.ssd(*a, chunk=chunk, backend=backend))
    for name, w, v in zip("x dt A B C D".split(), want, got):
        assert bool(jnp.isfinite(v).all()), name
        np.testing.assert_allclose(np.asarray(v), np.asarray(w), rtol=2e-4,
                                   atol=2e-4 * float(jnp.max(jnp.abs(w))),
                                   err_msg=name)


def test_ssd_decode_step_consistent_with_scan():
    rng = np.random.default_rng(5)
    b, s, nh, hd, ds = 2, 16, 2, 16, 8
    x = rand(rng, b, s, nh, hd)
    dt = jnp.abs(rand(rng, b, s, nh)) * 0.1
    A = -jnp.abs(rand(rng, nh))
    B = rand(rng, b, s, ds)
    C = rand(rng, b, s, ds)
    D = rand(rng, nh)
    want = ref.ssd_ref(x, dt, A, B, C, D)
    state = jnp.zeros((b, nh, hd, ds))
    for t in range(s):
        y_t, state = ops.ssd_decode_step(state, x[:, t], dt[:, t], A, B[:, t],
                                         C[:, t], D)
        np.testing.assert_allclose(
            np.asarray(y_t), np.asarray(want[:, t]), atol=1e-4)


@settings(max_examples=15, deadline=None)
@given(
    s=st.integers(8, 200),
    nh=st.sampled_from([1, 2, 4]),
    chunk=st.sampled_from([32, 64]),
    seed=st.integers(0, 99),
)
def test_property_ssd_chunk_invariance(s, nh, chunk, seed):
    """Chunk size must not change the result (state-passing correctness)."""
    rng = np.random.default_rng(seed)
    x = rand(rng, 1, s, nh, 16)
    dt = jnp.abs(rand(rng, 1, s, nh)) * 0.1
    A = -jnp.abs(rand(rng, nh))
    B = rand(rng, 1, s, 8)
    C = rand(rng, 1, s, 8)
    D = rand(rng, nh)
    a = ops.ssd(x, dt, A, B, C, D, chunk=chunk, backend="xla")
    b_ = ops.ssd(x, dt, A, B, C, D, chunk=16, backend="xla")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=5e-4)


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(4, 100, 128), (1, 256), (3, 7, 512)])
def test_rmsnorm_matches_oracle(shape, dtype):
    rng = np.random.default_rng(0)
    x = rand(rng, *shape, dtype=dtype)
    sc = rand(rng, shape[-1])
    want = ref.rmsnorm_ref(x, sc)
    got = ops.rmsnorm(x, sc, backend="interpret")
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=TOL[dtype], rtol=TOL[dtype])

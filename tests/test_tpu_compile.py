"""The Pallas kernels and a full-width layer, compiled for a described v5e.

Nothing runs: the TPU compiler installed with JAX compiles for a v5e that is
described, not attached, and refuses what the chip would refuse — blocks that
do not tile, kernels that do not lower.  Interpret mode (test_kernels.py)
cannot see those faults.  The topology is described inside a fixture, never
at import: only one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import registry
from repro.kernels import flash_attention, flash_decode, rmsnorm, ssd_scan
from repro.models.common import keygen
from repro.models.layers import decoder_layer, init_decoder_layer

SMOKE = registry.depth_cut("paper-gpt3-large", 8)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the persistent cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _kernel_op(text: str, name: str) -> bool:
    """Whether compiled HLO ``text`` runs the Pallas kernel ``name`` under
    that op name, the name a device trace gives its calls."""
    return re.search(rf"%{name}(\.\d+)? = [^\n]*tpu_custom_call",
                     text) is not None


def _compile(fn, *shapes, sharding=None, dtype=jnp.bfloat16):
    args = [s if isinstance(s, jax.ShapeDtypeStruct)
            else jax.ShapeDtypeStruct(s, dtype, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("b,h,s,hd", [(1, 16, 2048, 96), (1, 8, 4096, 128)])
def test_flash_attention_fwd_compiles(one_chip, b, h, s, hd):
    c = _compile(lambda q, k, v: flash_attention.flash_attention_fwd(q, k, v),
                 (b, h, s, hd), (b, h, s, hd), (b, h, s, hd),
                 sharding=one_chip)
    assert _kernel_op(c.as_text(), "flash_attention_fwd")


@pytest.mark.parametrize("local", [True, False])
def test_flash_attention_fwd_compiles_at_gemma3_widths(one_chip, local):
    """gemma3-4b's local (window 1024) and global layers: head 256, 8 q
    heads on 4 kv heads; the default blocks must fit the chip's VMEM at the
    widest head."""
    cfg = registry.get_arch("gemma3-4b")
    hd, w = cfg.resolved_head_dim, cfg.sliding_window if local else 0
    q = (1, cfg.num_heads, 4096, hd)
    kv = (1, cfg.num_kv_heads, 4096, hd)
    c = _compile(lambda q, k, v: flash_attention.flash_attention_fwd(
        q, k, v, window=w), q, kv, kv, sharding=one_chip)
    assert _kernel_op(c.as_text(), "flash_attention_fwd")


def test_flash_decode_compiles(one_chip):
    length = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    c = _compile(lambda q, k, v, n: flash_decode.flash_decode(q, k, v, n),
                 (8, 32, 1, 128), (8, 8, 4096, 128), (8, 8, 4096, 128),
                 length, sharding=one_chip)
    assert _kernel_op(c.as_text(), "flash_decode")


def test_rmsnorm_compiles(one_chip):
    c = _compile(lambda x, sc: rmsnorm.rmsnorm(x, sc),
                 (2048, SMOKE.d_model), (SMOKE.d_model,), sharding=one_chip)
    assert _kernel_op(c.as_text(), "rmsnorm")


def test_ssd_scan_compiles_at_zamba2_widths(one_chip):
    cfg = registry.get_arch("zamba2-1.2b")
    nh, hd, ds = (cfg.ssm.num_heads(cfg.d_model), cfg.ssm.head_dim,
                  cfg.ssm.d_state)
    b, s = 1, 4096
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32,  # noqa: E731
                                              sharding=one_chip)
    c = _compile(
        lambda x, dt, A, B, C, D: ssd_scan.ssd_scan(
            x, dt, A, B, C, D, chunk=cfg.ssm.chunk),
        (b, nh, s, hd), f32(b, s, nh), f32(nh), (b, s, ds), (b, s, ds),
        f32(nh), sharding=one_chip)
    assert _kernel_op(c.as_text(), "ssd_scan")


def test_smoke_layer_takes_the_kernel_on_tpu_only(one_chip):
    """One decoder layer of the chip smoke's config, forward and backward:
    compiled for the TPU it runs the Pallas attention; traced for the CPU it
    takes the XLA path."""
    params = jax.eval_shape(
        lambda: init_decoder_layer(keygen(jax.random.key(0)), SMOKE))
    seq = 2048

    def loss(p, x):
        pos = jnp.broadcast_to(jnp.arange(seq)[None], (1, seq))
        return decoder_layer(p, x, pos, SMOKE).astype(jnp.float32).sum()

    step = jax.value_and_grad(loss)
    on = lambda sh: (  # noqa: E731
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                    sharding=sh), params),
        jax.ShapeDtypeStruct((1, seq, SMOKE.d_model), SMOKE.dtype,
                             sharding=sh))
    tpu = jax.jit(step).lower(*on(one_chip)).compile().as_text()
    # the attention forward keeps the op name the benchmark's kernel reader
    # (chipbench/kernels/attn_fwd.py) matches inside a whole layer
    assert _kernel_op(tpu, "flash_attention_fwd")
    cpu_sharding = SingleDeviceSharding(jax.devices("cpu")[0])
    cpu = jax.jit(step).lower(*on(cpu_sharding)).as_text()
    assert "tpu_custom_call" not in cpu

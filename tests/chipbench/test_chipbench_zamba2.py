"""Zamba2 in the benchmark, at a small size on the CPU.

With dts so large that exp of a chunk's positive differences above its
diagonal would overflow float32, the program still trains its checked steps
with finite gradients and agrees with the plain reference; and the fp8
control fails ``zamba2.actor-bf``'s limits."""
import dataclasses
import math

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import check, harness, trace, weights
from test_chipbench_reference import AGREE

#: dt = softplus(projection + 1.5), about 1.7 a position: over the 63
#: positions above the diagonal of a 64-position chunk that passes float32
#: exp's 88.7 (A = -1), so an exponent left unmasked there makes the
#: gradients NaN.  Much larger dts make AdamW's first update of the tiny
#: model sensitive to rounding (2.0 reads 4e-3 in ``update``).
DT_BIAS = 1.5
CHUNK = 64


@pytest.fixture
def large_dt(monkeypatch):
    """Weights from the seed with every Mamba layer's ``dt_bias`` at
    DT_BIAS, for the program and the reference alike."""
    draw = weights._draw

    def raised(key, name, shape, dtype):
        if name == "dt_bias":
            return jnp.full(shape, DT_BIAS, dtype)
        return draw(key, name, shape, dtype)

    monkeypatch.setattr(weights, "_draw", raised)


def test_large_dt_trains_finite_and_agrees_with_the_reference(large_dt):
    cfg, conf = tiny.zamba2()
    cfg = dataclasses.replace(cfg, ssm=dataclasses.replace(cfg.ssm,
                                                           chunk=CHUNK))
    cell = {**tiny.cell("actor"), "seq": 2 * CHUNK}
    run = harness.setup(cell, {**conf, "chunk": CHUNK}, 83, cfg=cfg)
    harness.free(run)
    got = run.checked
    assert all(math.isfinite(x) for x in got["losses"]), got["losses"]
    for field in ("grad_norms", "change_norms"):
        bad = [k for k, v in got[field].items() if not math.isfinite(v)]
        assert not bad, (field, bad)
    values = check.numbers(got, harness.reference_record(run))
    assert max(values.values()) < AGREE, values


def test_control_fails_the_cells_limits():
    """The control, the reference with fp8 matrix products, in the program's
    place: ``correct`` has to come out false.  At this size it does, by
    ``grad``; at the cell's own size the control's readings overlap the
    program's on most seeds (PERF.md, section 2)."""
    cfg, conf = tiny.zamba2(jnp.bfloat16)
    limits = harness.load_json("cells", "zamba2.actor-bf")["limits"]
    run = harness.setup(tiny.cell("actor"), conf, 84, cfg=cfg)
    harness.free(run)
    ref = harness.reference_record(run)
    control = check.numbers(harness.reference_record(run, "fp8"), ref)
    ok, rows = check.verdict(control, limits)
    assert not ok, rows


ssd = harness.load_module("kernels", "ssd_fwd")


def test_ssd_counts_by_hand():
    # 1 row, 1 head, 4 positions in 2 chunks of 2, head size 2, state 2.  A
    # chunk has 3 causal pairs: C B^T over them 3 x 2 x 2 = 12 (once, one
    # group), (C B^T o decay)(dt x) 3 x 2 x 2 = 12, C state^T 2 x 2 x 2 x 2 =
    # 16, the state update x^T B 16
    assert ssd.flops(rows=1, heads=1, seq=4, head=2, state=2,
                     chunk=2) == 2 * (12 + 12 + 16 + 16)
    # x read and y written, 8 numbers each in bfloat16 (32 bytes); dt, 4 in
    # float32 (16); B and C, 8 each in bfloat16 (32)
    assert ssd.bytes_moved(rows=1, heads=1, seq=4, head=2, state=2,
                           chunk=2) == 32 + 16 + 32


@pytest.mark.parametrize("hlo,hit", [
    ('%ssd_scan = bf16[1,64,2048,64] custom-call(bf16[1,64,2048,64] %x), '
     'custom_call_target="tpu_custom_call"', True),
    ('%ssd_scan.12 = bf16[1,64,2048,64] custom-call(bf16[1,64,2048,64] %x),'
     ' custom_call_target="tpu_custom_call"', True),
    ('%flash_attention_fwd.1 = bf16[1,32,2048,64] custom-call(), '
     'custom_call_target="tpu_custom_call"', False),
    ('%fusion.7 = f32[1,2048,64] fusion(f32[1,2048,64] %ssd_scan.1)', False),
])
def test_ssd_match_takes_the_kernels_op_name(hlo, hit):
    """The op names the compiled program gives the kernel
    (``%ssd_scan(.N)?``, as ``tests/test_tpu_compile.py`` finds them), as the
    trace's reduction reads them."""
    assert ssd.match(trace.op_name(hlo)) is hit


def test_ssd_roofline_in_the_cell():
    cell = harness.load_json("cells", "zamba2.actor-bf")
    conf = harness.load_json("configs", cell["config"])
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    sh = ssd.shapes(conf, cell)
    least = max(ssd.flops(**sh) / peak["bf16_flops"],
                ssd.bytes_moved(**sh) / peak["hbm_bytes_per_s"])
    # x and y dominate: 2 x 16.8 MB over 819 GB/s, about 42 us a call
    assert 40e-6 < least < 45e-6
    rec = {"trace": {"kernels": {"ssd_fwd": [2 * least / 0.25, 2]}},
           "conf": conf, "cell": cell, "peak": peak}
    assert harness.load_module("metrics", "ssd_fwd_roofline").read(
        rec) == pytest.approx(25.0)
    rec["trace"]["kernels"]["ssd_fwd"] = [0.0, 0]
    assert harness.roofline(rec, "ssd_fwd") is None

"""The benchmark's own FLOP and byte counts: against hand counts at small
shapes, and against the program's ``model_flops`` at the cells' sizes."""
import math

import pytest

from chipbench import harness, model_flops
from repro.configs import registry
from repro.models.build import build
from repro.models.common import ShapeCell

attn = harness.load_module("kernels", "attn_fwd")


def test_attention_counts_by_hand():
    # 1 row, 1 head, 4 positions, head size 2: 10 (q, k) pairs under the
    # causal mask, each a 2-long dot product in Q K^T and in P V
    assert attn.flops(rows=1, heads=1, seq=4, head=2) == 10 * 2 * 2 * 2
    # q, k, v read and o written once, 8 numbers each, 2 bytes a number
    assert attn.bytes_moved(rows=1, heads=1, seq=4, head=2) == 4 * 8 * 2


@pytest.mark.parametrize("name", ["gpt3-large", "zamba2-1.2b"])
def test_step_flops_equal_programs_model_flops(name):
    conf = harness.load_json("configs", name)
    cfg = registry.depth_cut(conf["arch"], conf["n_layers"])
    model = build(cfg, num_stages=4)
    want = model.model_flops(ShapeCell("cell", 2048, 8, "train"))
    assert math.isclose(model_flops.step_flops(conf, 8, 2048),
                        want["model_flops"], rel_tol=1e-12)


def test_gpt3_large_step_is_32_tflop():
    conf = harness.load_json("configs", "gpt3-large")
    assert 32.2e12 < model_flops.step_flops(conf, 8, 2048) < 32.4e12


def test_mfu_reads_model_flops_over_peak():
    mfu = harness.load_module("metrics", "mfu")
    rec = {"steps": 10, "flops_per_step": 197e12, "window_s": 20.0,
           "chips": 1, "peak": {"bf16_flops": 197e12}}
    assert mfu.read(rec) == pytest.approx(50.0)
    assert mfu.read({**rec, "steps": 0}) is None


def test_roofline_is_silent_without_calls():
    conf = harness.load_json("configs", "gpt3-large")
    cell = harness.load_json("cells", "gpt3l.actor-bf")
    rec = {"trace": {"kernels": {"attn_fwd": [0.0, 0]}}, "conf": conf,
           "cell": cell, "peak": {"bf16_flops": 197e12,
                                  "hbm_bytes_per_s": 819e9}}
    assert harness.roofline(rec, "attn_fwd") is None
    rec["trace"]["kernels"]["attn_fwd"] = [2 * 65.4e-6 / 0.5, 2]
    assert harness.roofline(rec, "attn_fwd") == pytest.approx(50.0, rel=0.01)

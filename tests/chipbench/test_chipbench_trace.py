"""The reduction from a profiler trace to busy time, kernel time and idle
gaps, on a small trace recorded on a TPU v5e: two steps, each a jitted
forward (Pallas attention, Pallas SSD scan, a matmul) and its gradient, under
host spans ``step`` > ``fwd``, ``bwd``, with a 2 ms sleep between them."""
import pathlib

import pytest

from chipbench import harness, trace

DATA = pathlib.Path(__file__).resolve().parent / "data" / "small.xplane.pb"
#: the attention forward by its kernel file; the SSD scan's forward by the
#: name of its jitted wrapper, as a kernel file would match it
KERNELS = {"attn_fwd": harness.load_module("kernels", "attn_fwd").match,
           "ssd_fwd": lambda op: op.startswith("ssd_scan")}


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.load(str(DATA)), ("step", "fwd", "bwd"),
                        KERNELS)


def test_window_is_the_host_steps(reduced):
    # first step span 45.548 ms to last 67.055 ms after the trace started
    assert reduced["window_s"] == pytest.approx(21.507e-3, abs=2e-6)


def test_busy_is_the_union_of_device_ops(reduced):
    assert reduced["busy"] == [reduced["busy_s"]]
    assert 0 < reduced["busy_s"] < reduced["window_s"]
    gaps = sum(t for _, t in reduced["idle_gaps"])
    assert gaps + reduced["busy_s"] == pytest.approx(reduced["window_s"],
                                                     rel=1e-9)
    assert {n for n, _ in reduced["idle_gaps"]} <= {"fwd", "bwd", "other"}


def test_kernels_found_by_their_op_names(reduced):
    # the forward runs each kernel once a step; the gradient program needs
    # only the residuals, so XLA drops its copy of the forward kernels
    seconds, calls = reduced["kernels"]["attn_fwd"]
    assert calls == 2 and seconds == pytest.approx(1.56526e-3, rel=1e-6)
    seconds, calls = reduced["kernels"]["ssd_fwd"]
    assert calls == 2 and seconds == pytest.approx(1.915693e-3, rel=1e-6)


def test_device_ops_are_named_by_module(reduced):
    names = [n for n, _ in reduced["device_ops"]]
    assert len(names) == 10
    assert all(n.split("/")[0] in ("jit_f", "jit_g") for n in names)
    assert "jit_f/flash_attention_fwd.1" in names
    times = [t for _, t in reduced["device_ops"]]
    assert times == sorted(times, reverse=True)


def test_op_name():
    assert trace.op_name("%flash_attention_fwd.3 = bf16[1,32] custom-call("
                         "bf16[1] %fusion.218)") == "flash_attention_fwd.3"
    assert not KERNELS["attn_fwd"]("bitcast_flash")

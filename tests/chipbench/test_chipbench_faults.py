"""A whole run, past the look for the chip, with the timed path broken
underneath: ``correct`` comes out false for every fault a cell can have."""
import time

import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import faults, harness, peaks, steps


def run_with(fault_name: str, runtime: str, stages: int):
    cfg, conf = tiny.gpt(jnp.bfloat16)
    limits = harness.load_json("cells", f"gpt3l.{'actor-bf' if runtime == 'actor' else 'table-1stage'}")["limits"]
    cell = tiny.cell(runtime, stages, **limits)
    fault = faults.make(fault_name, conf) if fault_name else None
    t0 = time.perf_counter()
    return harness.measure(harness.benchmark(), cell, conf, 91, 0.3, False,
                           peaks.PEAKS["TPU v5 lite"], steps.Timer(), t0,
                           cfg=cfg, fault=fault)


@pytest.mark.parametrize("fault,runtime,stages", [
    ("state_unchanged", "actor", 2), ("half_batch", "actor", 2),
    ("token", "actor", 2), ("exchange", "actor", 2),
    ("state_unchanged", "table", 1), ("half_batch", "table", 1),
    ("token", "table", 1)])
def test_fault_is_not_correct(fault, runtime, stages):
    result, rows = run_with(fault, runtime, stages)
    assert result["correct"] is False, rows
    assert list(result)[-1] == "checks"

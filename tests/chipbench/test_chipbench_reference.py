"""The plain references agree with the program at a small size in float32,
and the fp8 control does not."""
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import check, harness

#: float32 on the CPU: the program and the reference differ by summation
#: order alone (measured 1e-7 to 5e-6)
AGREE = 1e-4


@pytest.mark.parametrize("model", ["gpt", "zamba2"])
def test_reference_matches_program(model):
    cfg, conf = getattr(tiny, model)()
    run = harness.setup(tiny.cell("actor"), conf, 77, cfg=cfg)
    harness.free(run)
    ref = harness.reference_record(run)
    values = check.numbers(run.checked, ref)
    assert max(values.values()) < AGREE, values
    control = check.numbers(harness.reference_record(run, "fp8"), ref)
    assert max(control.values()) > 10 * AGREE, control


def test_reference_follows_the_table_optimizer():
    cfg, conf = tiny.gpt()
    run = harness.setup(tiny.cell("table", stages=1), conf, 78, cfg=cfg)
    harness.free(run)
    values = check.numbers(run.checked, harness.reference_record(run))
    # the executor accumulates embedding and head gradients in bfloat16
    assert values["loss"] < AGREE and values["update"] < 1e-3, values
    assert values["grad"] < 1e-2, values


@pytest.mark.parametrize("cell", ["gpt3l.actor-bf", "gpt3l.table-1stage"])
def test_control_fails_the_cells_limits(cell):
    """The control, the reference with fp8 matrix products, in the program's
    place: ``correct`` has to come out false."""
    cfg, conf = tiny.gpt(jnp.bfloat16)
    limits = harness.load_json("cells", cell)["limits"]
    run = harness.setup(tiny.cell("actor"), conf, 79, cfg=cfg)
    harness.free(run)
    ref = harness.reference_record(run)
    control = check.numbers(harness.reference_record(run, "fp8"), ref)
    ok, rows = check.verdict(control, limits)
    assert not ok, rows

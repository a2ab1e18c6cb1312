"""The numbers that decide ``correct``: by hand on small records, and the
change of a state that has not moved."""
import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny
from chipbench import check, steps, weights
from repro.models.build import build


def test_unmoved_bfloat16_weights_read_no_change():
    cfg, _ = tiny.gpt(jnp.bfloat16)
    model = build(cfg, num_stages=2)
    init = steps.make_param_init(model)
    key = weights.seed_key(4_000_000_007)
    now = jax.tree.map(jnp.copy, init(key))
    change = check.change_norms(model, now, init, key)
    assert change and all(v == 0.0 for v in change.values()), change


def test_numbers_by_hand():
    ref = {"losses": [2.0, 1.0],
           "grad_norms": {"a": 1.0, "b": 2.0, "c": 4.0, "quiet": 1e-4},
           "change_norms": {"a": 1.0, "b": 2.0, "c": 4.0, "quiet": 1.0}}
    prog = {"losses": [2.0, 1.1],
            "grad_norms": {"a": 1.5, "b": 2.0, "c": 4.0, "quiet": 0.0},
            "change_norms": {"a": 1.0, "b": 2.0, "c": 5.0, "quiet": 9.0}}
    got = check.numbers(prog, ref)
    assert got["loss"] == pytest.approx(0.1)
    # a's gap 0.5 over the median weight's norm (1.5), not over its own 1.0
    assert got["grad"] == pytest.approx(0.5 / 1.5)
    # "quiet" moves by round-off alone and is left out of the change
    assert got["update"] == pytest.approx(1.0 / 4.0)

"""The harness drives the program's own training step: its losses equal
``train.main``'s, bit for bit, from the same weights and batches."""
import jax
import numpy as np
import pytest

import chipbench_tiny as tiny
from chipbench import data, harness, steps
from repro.data.synthetic import synth_batch
from repro.launch import train
from repro.models.build import build

SEED = 2_147_483_659  # wider than 32 signed bits
STEPS = 4


def test_feed_is_the_programs_batch():
    cfg, conf = tiny.gpt()
    got = data.batch(conf["vocab_size"], 4, 32, seed=SEED, step=3)
    want = synth_batch(cfg, 4, 32, seed=SEED, step=3)
    for k in ("tokens", "labels"):
        np.testing.assert_array_equal(got[k], want[k])


@pytest.mark.parametrize("runtime,stages", [("actor", 2), ("table", 1)])
def test_step_matches_train_main(runtime, stages):
    cfg, conf = tiny.gpt()
    cell = tiny.cell(runtime, stages)
    # 20 steps: warm-up 20, so main's learning rates equal the harness's
    argv = ["--arch", "paper-gpt3-large", "--layers", "4", "--runtime",
            runtime, "--stages", str(stages), "--microbatches", "4",
            "--seq", "32", "--steps", "20", "--seed", str(SEED),
            "--devices", "1"]
    want = train.main(argv).losses[:STEPS]
    params = train.init_params(build(cfg, num_stages=stages))
    run = harness.setup(cell, conf, SEED, cfg=cfg, params=params)
    got = list(run.checked["losses"])
    for i in range(harness.CHECKED_STEPS, STEPS):
        got.append(run.step.step(i, run.batch(i)))
    assert got == want


def test_actor_step_records_host_spans():
    cfg, conf = tiny.gpt()
    run = harness.setup(tiny.cell("actor"), conf, 5, cfg=cfg)
    rec = harness.window(run, 0.5)
    assert rec["steps"] >= 1 and rec["window_compiles"] == 0
    for name in ("step", "batch", "programs", "run_threaded", "grad_stack",
                 "host_update", "loss_read"):
        assert len(rec["spans"][name]) == rec["steps"], name
    assert len(rec["actor"]) == rec["steps"]
    assert set(rec["spans"]) <= set(steps.SPANS)
    jax.block_until_ready(run.step.params)

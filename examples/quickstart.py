"""Quickstart: the RRFP runtime in 60 seconds.

1. Simulate a jittery, imbalanced 8-stage pipeline with the faithful engine:
   pre-committed 1F1B vs readiness-first RRFP (the paper's contrast).
2. Synthesize the RRFP-realized order into a static schedule table and train
   a tiny model with the compiled SPMD executor on forced host devices.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/quickstart.py
"""
import os

if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax.numpy as jnp

from repro.core import (
    CostModel, EngineConfig, HintKind, PipelineSpec,
    multimodal_stage_flops, run_iteration,
)

S, M = 8, 32
spec = PipelineSpec(S, M)
costs = CostModel.from_stage_flops(
    multimodal_stage_flops(5e12, 2e12, S), comm_base=2e-3, seed=0)

r_fixed = run_iteration(spec, costs, EngineConfig(mode="precommitted",
                                                  fixed_order="1f1b"))
r_rrfp = run_iteration(spec, costs, EngineConfig(mode="hint",
                                                 hint=HintKind.BF))
print("== engine: one iteration under jitter + stage imbalance ==")
print(f"pre-committed 1F1B: {r_fixed.makespan:.3f}s  "
      f"(blocking {r_fixed.breakdown()['blocking']:.3f}s)")
print(f"RRFP (BF hint):     {r_rrfp.makespan:.3f}s  "
      f"(blocking {r_rrfp.breakdown()['blocking']:.3f}s)  "
      f"speedup {r_fixed.makespan / r_rrfp.makespan:.2f}x")

print("\n== compiled executor: train a tiny LM with the RRFP table ==")
from repro.configs import registry
from repro.launch.train import build_trainer
from repro.data.synthetic import synth_batch

t = build_trainer(registry.reduced_config("deepseek-7b", num_layers=8),
                  data=2, stages=4, mb_rows=1,
                  microbatches=8, seq=64, schedule="rrfp")
sp, io, opt = t["stage_params"], t["io_params"], t["opt_state"]
for step in range(5):
    batch = synth_batch(t["cfg"], t["batch_size"], t["seq"], step=step)
    sp, io, opt, m = t["train_step"](sp, io, opt, batch,
                                     jnp.asarray(step, jnp.int32))
    print(f"step {step}  loss {float(m['loss']):.4f}")
print("table bubble fraction:", round(t["table"].bubble_fraction(), 3))

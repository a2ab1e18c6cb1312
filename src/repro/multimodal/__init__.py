"""Heterogeneous multimodal pipelines on the readiness-driven runtime.

The paper's headline (up to 2.77×) result is multimodal: cheap,
variable-length vision-encoder stages misaligned with LM-decoder stages —
the regime where consuming the schedule as a non-binding hint pays most.
This package makes that regime executable end to end:

  model    -- branch+fusion DAG topology (encoder branch ∥ text frontend →
              fusion → LM chain) with real per-stage parameters built from
              ``models/layers.py``; bitwise padding-invariant encoder math
  stagefn  -- the model hooks of ``pipeline.stagefn.StageFns`` for the
              DAG: per-(stage, op) jitted callables with shape bucketing
              (compile cache bounded by bucket count), run by the chain's
              ``ActorStageProgram`` (fan-in/fan-out payloads, BFW split
              backward, deterministic reduction)
  costs    -- DES cost models of the same topologies for the simulation
              substrate and the multimodal benchmark

See ``docs/multimodal.md`` for the DAG task-graph semantics.
"""
from repro.multimodal.costs import multimodal_dag_costs
from repro.multimodal.model import (
    MULTIMODAL_ARCHS,
    MultimodalConfig,
    MultimodalModel,
    multimodal_config,
    multimodal_model,
)
from repro.multimodal.stagefn import MultimodalStageFns

__all__ = [
    "MULTIMODAL_ARCHS",
    "MultimodalConfig",
    "MultimodalModel",
    "MultimodalStageFns",
    "multimodal_config",
    "multimodal_dag_costs",
    "multimodal_model",
]

"""Host spans on the profiler's clock.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` when JAX is
already imported, and a null context otherwise, so code that may run without
JAX (the actor runtime's thread loop) can be annotated without importing it.
With the profiler off an annotation costs about a microsecond; under
``jax.profiler.trace`` it lands on the host plane beside the device's ops,
its keyword arguments as the event's stats.

The spans the program emits (see ``docs/observability.md``):

  rrfp.run          ``ActorDriver.run_threaded``
  rrfp.wait         one ``Mailbox.wait_for_work`` (``StageStats.wait``)
  rrfp.F/B/W        one stage callable call, ``stage=``/``mb=``
                    (``StageStats.compute``)
  rrfp.complete     completion and its sends (part of ``StageStats.runtime``)
  stage.init        ``ActorStageProgram`` set-up: zeroed accumulators
  stage.accumulate  the per-microbatch grad and loss adds
"""
from __future__ import annotations

import contextlib
import sys

_NULL = contextlib.nullcontext()


def span(name: str, **args):
    """A context manager that records ``name`` on the profiler's host plane
    while JAX is loaded; costs nothing more than a dict lookup otherwise."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return _NULL
    return profiler.TraceAnnotation(name, **args)

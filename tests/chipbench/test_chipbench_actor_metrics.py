"""The actor runtime's thread-split metrics read the window's ``RunResult``s,
and stay silent where the program counts no split."""
import types

import pytest

from chipbench import harness
from repro.core import PipelineSpec
from repro.core.engine import RunResult, StageStats


def _result(makespan, splits):
    """A run of 2 stages x 2 microbatches (8 tasks) with (wait, runtime)
    per stage."""
    stats = [StageStats(compute=0.5, wait=w, runtime=rt) for w, rt in splits]
    return RunResult(makespan=makespan, stage_stats=stats, start={}, end={},
                     spec=PipelineSpec(2, 2))


RECORD = {"actor": [_result(1.0, [(0.1, 0.002), (0.3, 0.004)]),
                    _result(2.0, [(0.2, 0.001), (0.4, 0.009)])]}


def read(name: str, rec: dict):
    return harness.load_module("metrics", name).read(rec)


def test_ready_wait_share_by_hand():
    # (0.1 + 0.3 + 0.2 + 0.4) / (2 x 1.0 + 2 x 2.0) = 1.0 / 6.0
    assert read("actor.ready_wait_share", RECORD) == pytest.approx(
        100.0 / 6.0)


def test_runtime_us_by_hand():
    # (0.002 + 0.004 + 0.001 + 0.009) s over 16 tasks = 1000 us a task
    assert read("actor.runtime_us", RECORD) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", ["actor.ready_wait_share",
                                  "actor.runtime_us"])
def test_silent_without_the_split(name):
    """No actor results (the compiled executor's cell), or stage stats from a
    program that counts no wait or runtime: no number, and no error."""
    assert read(name, {"actor": []}) is None
    assert read(name, {}) is None
    old = types.SimpleNamespace(compute=0.5, blocking=0.5)
    rec = {"actor": [RunResult(makespan=1.0, stage_stats=[old, old],
                               start={}, end={}, spec=PipelineSpec(2, 2))]}
    assert read(name, rec) is None

"""Where a stage thread's time goes (``StageStats.wait``/``runtime``), and the
profiler spans at the same boundaries (``repro.obs.spans``)."""
import glob
import os
import pathlib
import re
import subprocess
import sys
import time

import pytest

import repro
from repro.core import CostModel, Kind, PipelineSpec
from repro.runtime.rrfp import ActorConfig, ActorDriver, StageActor

SRC = str(pathlib.Path(repro.__file__).resolve().parents[1])


def _sleepy(task, payload):
    """A slow first stage, so that later stages wait most of the run."""
    if task.kind == Kind.F:
        time.sleep(0.02 if task.stage == 0 else 0.003)
    else:
        time.sleep(0.006)


@pytest.mark.parametrize("mode", ["hint", "precommitted"])
def test_stage_time_closes_to_the_makespan(mode, monkeypatch):
    started = {}  # stage -> the run's clock as its thread starts
    run_thread = StageActor.run_thread

    def timed(self, work_fn, transport, clock, **kw):
        started[self.idx] = clock()
        return run_thread(self, work_fn, transport, clock, **kw)

    monkeypatch.setattr(StageActor, "run_thread", timed)
    spec = PipelineSpec(4, 8)
    r = ActorDriver(spec, None, ActorConfig(
        mode=mode, fixed_order="1f1b")).run_threaded(_sleepy)
    for s, st in enumerate(r.stage_stats):
        tail = r.makespan - max(e for t, e in r.end.items() if t.stage == s)
        # everything outside the callable from the thread's start to its
        # last task's end is counted as wait or runtime; the last
        # completion's bookkeeping runs after that end, on top
        assert st.wait + st.runtime >= st.blocking - tail - 1e-9
        assert st.wait + st.runtime <= st.blocking - tail + 0.05
        total = st.wait + st.runtime + st.compute + tail
        # the makespan, short by the thread's start latency
        assert total == pytest.approx(r.makespan - started[s], abs=0.05)
        assert st.runtime > 0
        if s > 0:  # a later stage waits for stage 0's forwards
            assert st.wait > 0.1


def test_sim_substrate_leaves_the_thread_split_at_zero():
    spec = PipelineSpec(2, 4)
    r = ActorDriver(spec, CostModel.uniform(2), ActorConfig()).run()
    assert all(st.wait == 0.0 and st.runtime == 0.0 for st in r.stage_stats)
    assert sum(st.compute for st in r.stage_stats) > 0


def test_runtime_imports_and_runs_without_jax():
    code = (
        "import sys\n"
        "import repro.runtime.rrfp as rt\n"
        "from repro.core import PipelineSpec\n"
        "assert 'jax' not in sys.modules\n"
        "r = rt.ActorDriver(PipelineSpec(2, 2), None, rt.ActorConfig())"
        ".run_threaded(lambda task, payload: None)\n"
        "assert all(st.runtime > 0 for st in r.stage_stats)\n"
        "assert 'jax' not in sys.modules, 'a span imported jax'\n")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={**os.environ, "PYTHONPATH": SRC})
    assert p.returncode == 0, p.stderr


def test_spans_and_module_names_on_the_profilers_clock(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from repro.configs import registry
    from repro.models.build import build
    from repro.pipeline.stagefn import (ActorStageProgram, StageFnOptions,
                                        StageFns, microbatch, warm_up)

    S, M, seq = 2, 2, 16
    model = build(registry.reduced_config("paper-gpt3-large", 2),
                  num_stages=S)
    key = jax.random.key(0)
    sp = model.init_stage_params(key)
    io = model.init_io_params(jax.random.fold_in(key, 1))
    toks = jax.random.randint(key, (M, seq), 0, model.cfg.vocab_size)
    batch = {"tokens": toks, "labels": toks}
    fns = StageFns(model, StageFnOptions(mb_rows=1, seq_len=seq))
    stages = [jax.tree.map(lambda x, s=s: x[s], sp) for s in range(S)]
    warm_up(fns, stages, io, batch)

    module = fns.forward(0).lower(stages[0], io, None,
                                  microbatch(batch, 0, 1)).as_text()
    assert "module @jit_stage0_F" in module
    assert "module @jit_stage1_B" in fns.backward(1).lower(
        stages[1], io, jnp.zeros((1, seq, model.cfg.d_model), model.cfg.dtype),
        None, microbatch(batch, 0, 1)).as_text()

    with jax.profiler.trace(str(tmp_path)):
        programs = [ActorStageProgram(fns, s, stages[s], io, batch)
                    for s in range(S)]
        ActorDriver(PipelineSpec(S, M), None, ActorConfig(
            deadlock_timeout=120.0)).run_threaded(programs)
        jax.block_until_ready([p.d_stage for p in programs])

    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = [(ev.name, dict(ev.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events]
    names = {n for n, _ in events}
    assert {"rrfp.run", "rrfp.F", "rrfp.B", "rrfp.wait", "rrfp.complete",
            "stage.init", "stage.accumulate"} <= names
    for kind in ("rrfp.F", "rrfp.B"):
        got = sorted((a["stage"], a["mb"]) for n, a in events if n == kind)
        assert got == [(s, j) for s in range(S) for j in range(M)]
    assert sorted(a["stage"] for n, a in events if n == "stage.init") == [0, 1]


def test_named_scopes_in_the_lowered_programs():
    """The scopes a device trace's ops are summed by reach the compiled
    programs' op metadata: the stage callables' and the executor's."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.launch.train import build_trainer
    from repro.models.build import build
    from repro.pipeline.stagefn import StageFnOptions, StageFns

    cfg = registry.reduced_config("paper-gpt3-large", 2)
    seq = 16
    model = build(cfg, num_stages=1)
    key = jax.random.key(0)
    sp = jax.tree.map(lambda x: x[0], model.init_stage_params(key))
    io = model.init_io_params(key)
    toks = jnp.zeros((1, seq), jnp.int32)
    bm = {"tokens": toks, "labels": toks}
    fns = StageFns(model, StageFnOptions(mb_rows=1, seq_len=seq))
    fwd = fns.forward(0).lower(sp, io, None, bm).as_text(debug_info=True)
    assert all(f"/{s}/" in fwd for s in ("embed", "layers", "ce_loss"))
    assert "recompute" not in fwd
    g = jnp.zeros((1, seq, cfg.d_model), cfg.dtype)
    bwd = fns.backward(0).lower(sp, io, None, g, bm).as_text(debug_info=True)
    assert all(f"jvp(recompute)/{s}/" in bwd for s in ("embed", "layers",
                                                       "ce_loss"))

    t = build_trainer(cfg, data=1, stages=1, mb_rows=1, microbatches=2,
                      seq=seq)
    batch = {"tokens": jnp.zeros((2, seq), jnp.int32),
             "labels": jnp.zeros((2, seq), jnp.int32)}
    step = t["train_step"].lower(
        t["stage_params"], t["io_params"], t["opt_state"], batch,
        jnp.asarray(0, jnp.int32)).as_text(debug_info=True)
    # (an idle tick, and W under a fused backward, run no op to carry one)
    for scope in ("tick.F/", "tick.B/", "tick.exchange/",
                  "tick.B/jvp(recompute)/", "ce_loss/", "optimizer/"):
        assert scope in step, scope


def test_zamba2_scopes_in_the_lowered_stage_programs():
    """A hybrid model's stage callables carry ``shared_blk`` around the
    shared attention block, ``mamba`` around each Mamba-2 layer and ``ssd``
    around its scan, forward and in the backward's re-run forward."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models.build import build
    from repro.pipeline.stagefn import StageFnOptions, StageFns

    cfg = dataclasses.replace(registry.reduced_config("zamba2-1.2b", 2),
                              layer_pattern=("mamba",) * 2)
    seq = 16
    model = build(cfg, num_stages=1)
    key = jax.random.key(0)
    sp = jax.tree.map(lambda x: x[0], model.init_stage_params(key))
    io = model.init_io_params(key)
    toks = jnp.zeros((1, seq), jnp.int32)
    bm = {"tokens": toks, "labels": toks}
    fns = StageFns(model, StageFnOptions(mb_rows=1, seq_len=seq))
    fwd = fns.forward(0).lower(sp, io, None, bm).as_text(debug_info=True)
    g = jnp.zeros((1, seq, cfg.d_model), cfg.dtype)
    bwd = fns.backward(0).lower(sp, io, None, g, bm).as_text(debug_info=True)
    for text, under in ((fwd, "jit(stage0_F)/layers/"),
                        (bwd, "jit(stage0_B)/jvp(recompute)/layers/")):
        for scope in ("shared_blk/", "mamba/", "mamba/ssd/"):
            assert re.search(re.escape(under) + r"(checkpoint/)?"
                             + re.escape(scope), text), (under, scope)

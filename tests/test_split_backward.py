"""Split-backward (BFW) end-to-end: numerics, runtime W path, deferral cap."""
import threading
import time

import pytest

from repro.core import (
    CostModel,
    EngineConfig,
    HintKind,
    JitterModel,
    Kind,
    PipelineSpec,
    Task,
    run_iteration,
)
from repro.runtime.rrfp import ActorConfig, ActorDriver, run_actor_iteration


def det_costs(S, f=1.0, b=1.0, w=1.0, comm=1e-6, **kw):
    return CostModel.uniform(
        S, f=f, b=b, w=w, comm_base=comm,
        compute_jitter=JitterModel(), comm_jitter=JitterModel(), **kw,
    )


def _w_backlog_max(tasks_in_completion_order):
    """Max running (B done - W done) over one stage's completion sequence."""
    d = mx = 0
    for t in tasks_in_completion_order:
        if t.kind == Kind.B:
            d += 1
        elif t.kind == Kind.W:
            d -= 1
        mx = max(mx, d)
    return mx


# ---------------------------------------------------------------------------
# Numerics: B(dX) + W(dW) must reproduce the fused backward
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_split_backward_matches_fused_gradients():
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models.build import build
    from repro.pipeline.stagefn import StageFnOptions, StageFns, microbatch

    S, mb_rows, seq = 2, 2, 16
    cfg = registry.reduced_config("deepseek-7b", num_layers=4)
    model = build(cfg, num_stages=S)
    key = jax.random.key(0)
    sp = model.init_stage_params(key)
    io = model.init_io_params(jax.random.fold_in(key, 1))
    batch = {
        "tokens": jax.random.randint(
            jax.random.key(2), (mb_rows, seq), 0, cfg.vocab_size),
        "labels": jax.random.randint(
            jax.random.key(3), (mb_rows, seq), 0, cfg.vocab_size),
    }
    fns = StageFns(model, StageFnOptions(mb_rows=mb_rows, seq_len=seq))
    bm = microbatch(batch, 0, mb_rows)
    sp0 = jax.tree.map(lambda x: x[0], sp)
    sp1 = jax.tree.map(lambda x: x[1], sp)
    y0, _ = fns.forward(0)(sp0, io, None, bm)
    g_in = jnp.zeros_like(y0)  # last stage: CE is the objective, g_in unused

    dx_f, dsp_f, dio_f = fns.backward(1)(sp1, io, y0, g_in, bm)
    dx_s = fns.backward_dx(1)(sp1, io, y0, g_in, bm)
    dsp_s, dio_s = fns.weight_grad(1)(sp1, io, y0, g_in, bm)

    def max_diff(a, b):
        return max(
            float(jnp.max(jnp.abs(
                x.astype(jnp.float32) - y.astype(jnp.float32))))
            for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    # same objective, same remat recipe -> bitwise-equal partials
    assert max_diff(dx_f, dx_s) == 0.0
    assert max_diff(dsp_f, dsp_s) == 0.0
    assert max_diff(dio_f, dio_s) == 0.0


@pytest.mark.slow
def test_threaded_bfw_matches_fused_run():
    """BFW split-backward through the real threaded runtime reproduces the
    fused run's loss and accumulated parameter grads, and honors the cap."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models.build import build
    from repro.pipeline.stagefn import (
        ActorStageProgram, StageFnOptions, StageFns)

    S, M, mb_rows, seq, cap = 2, 4, 2, 16, 2
    cfg = registry.reduced_config("deepseek-7b", num_layers=4)
    model = build(cfg, num_stages=S)
    key = jax.random.key(0)
    sp = model.init_stage_params(key)
    io = model.init_io_params(jax.random.fold_in(key, 1))
    B_rows = M * mb_rows
    batch = {
        "tokens": jax.random.randint(
            jax.random.key(2), (B_rows, seq), 0, cfg.vocab_size),
        "labels": jax.random.randint(
            jax.random.key(3), (B_rows, seq), 0, cfg.vocab_size),
    }
    tokens = B_rows * seq
    fns = StageFns(model, StageFnOptions(
        mb_rows=mb_rows, seq_len=seq, loss_scale=1.0 / tokens))

    def run(split: bool):
        spec = PipelineSpec(S, M, split_backward=split)
        programs = [
            ActorStageProgram(
                fns, s, jax.tree.map(lambda x, s=s: x[s], sp), io, batch,
                split_backward=split)
            for s in range(S)
        ]
        acfg = ActorConfig(
            mode="hint", hint=HintKind.BFW if split else HintKind.BF,
            w_defer_cap=cap if split else 0, deadlock_timeout=300.0)
        r = ActorDriver(spec, None, acfg).run_threaded(list(programs))
        assert set(r.end) == set(spec.tasks())  # W tasks really executed
        return programs

    fused = run(split=False)
    bfw = run(split=True)

    # loss is accumulated on device; one materialization here
    loss_f = sum(p.loss_sum for p in fused) / tokens
    loss_w = sum(p.loss_sum for p in bfw) / tokens
    assert abs(loss_f - loss_w) < 1e-5 * max(1.0, abs(loss_f))

    for pf, pw in zip(fused, bfw):
        assert pw.w_high_water <= cap
        assert pw.w_outstanding() == 0  # every stash was consumed by its W
        for gf, gw in zip(jax.tree.leaves(pf.d_stage),
                          jax.tree.leaves(pw.d_stage)):
            scale = float(jnp.max(jnp.abs(gf.astype(jnp.float32)))) + 1e-8
            diff = float(jnp.max(jnp.abs(
                gf.astype(jnp.float32) - gw.astype(jnp.float32))))
            assert diff <= 1e-5 * scale, (diff, scale)


def test_fused_program_rejects_w_task():
    import numpy as np

    from repro.pipeline.stagefn import ActorStageProgram, microbatch

    prog = ActorStageProgram.__new__(ActorStageProgram)
    prog.split_backward = False
    prog.stage = 0
    prog.batch = {"tokens": np.zeros((2, 4), np.int32)}
    prog.fns = type("F", (), {"microbatch": staticmethod(
        lambda batch, mb, stage: microbatch(batch, mb, 1))})()
    with pytest.raises(ValueError, match="split_backward=True"):
        ActorStageProgram.__call__(prog, Task(Kind.W, 0, 0), None)


# ---------------------------------------------------------------------------
# W-deferral cap (activation-memory backpressure)
# ---------------------------------------------------------------------------
class TestWDeferCap:
    def test_cap_never_exceeded_in_sim(self):
        S, M, cap = 4, 16, 3
        spec = PipelineSpec(S, M, split_backward=True)
        cm = det_costs(S, f=1.0, b=0.5, w=0.5, comm=1e-3)
        r = run_actor_iteration(spec, cm, ActorConfig(
            mode="hint", hint=HintKind.BFW, w_defer_cap=cap))
        assert set(r.end) == set(spec.tasks())
        for s in range(S):
            ev = [t for _, t in sorted(
                (r.end[t], t) for t in r.end if t.stage == s)]
            assert _w_backlog_max(ev) <= cap

    def test_cap_never_exceeded_in_threaded_run(self):
        S, M, cap = 3, 8, 2
        spec = PipelineSpec(S, M, split_backward=True)
        lock = threading.Lock()
        completion: dict[int, list[Task]] = {s: [] for s in range(S)}

        def work(task, payload):
            time.sleep(0.001)
            with lock:
                completion[task.stage].append(task)
            return None

        r = ActorDriver(spec, None, ActorConfig(
            mode="hint", hint=HintKind.BFW,
            w_defer_cap=cap)).run_threaded(work)
        assert len(r.end) == spec.total_tasks()
        for s in range(S):
            assert _w_backlog_max(completion[s]) <= cap

    def test_uncapped_deferral_can_exceed_cap_value(self):
        """Sanity: with w_defer_cap=0 (unbounded) the same workload defers
        more than the cap would allow — the knob is load-bearing."""
        S, M, cap = 4, 16, 3
        spec = PipelineSpec(S, M, split_backward=True)
        cm = det_costs(S, f=1.0, b=0.5, w=0.5, comm=1e-3)
        r = run_actor_iteration(spec, cm, ActorConfig(
            mode="hint", hint=HintKind.BFW, w_defer_cap=0))
        worst = max(
            _w_backlog_max([t for _, t in sorted(
                (r.end[t], t) for t in r.end if t.stage == s)])
            for s in range(S))
        assert worst > cap

    def test_cap_does_not_apply_to_precommitted(self):
        """Precommitted zb fixes W placement in its order; the cap knob is a
        hint-mode memory bound and must not perturb fixed-order runs."""
        S, M = 4, 8
        spec = PipelineSpec(S, M, split_backward=True)
        cm = det_costs(S)
        a = run_actor_iteration(spec, cm, ActorConfig(
            mode="precommitted", fixed_order="zb", w_defer_cap=1))
        b = run_actor_iteration(spec, cm, ActorConfig(
            mode="precommitted", fixed_order="zb", w_defer_cap=0))
        assert a.stage_orders() == b.stage_orders()


# ---------------------------------------------------------------------------
# Consistency validation: hint mode on a split spec requires the BFW hint
# ---------------------------------------------------------------------------
class TestSplitSpecValidation:
    def test_actor_driver_rejects_non_bfw_hint(self):
        spec = PipelineSpec(2, 2, split_backward=True)
        with pytest.raises(ValueError, match="BFW"):
            ActorDriver(spec, det_costs(2), ActorConfig(
                mode="hint", hint=HintKind.BF))

    def test_engine_rejects_non_bfw_hint(self):
        spec = PipelineSpec(2, 2, split_backward=True)
        with pytest.raises(ValueError, match="BFW"):
            run_iteration(spec, det_costs(2), EngineConfig(
                mode="hint", hint=HintKind.FB))

    def test_straggler_replan_from_split_backward_trace(self):
        """A split-backward RunResult must feed the straggler monitor's EMA
        without tripping synthesis (which models fused backward and is fed
        the fused twin of the spec, as launch.train does)."""
        from repro.runtime.straggler import StragglerMonitor

        S, M = 4, 8
        spec = PipelineSpec(S, M, split_backward=True)
        skewed = CostModel.uniform(S, b=0.5, w=0.5, comm_base=1e-4)
        skewed.f_cost[2] *= 4.0
        r = run_actor_iteration(spec, skewed, ActorConfig(
            mode="hint", hint=HintKind.BFW, w_defer_cap=4))
        mon = StragglerMonitor(
            spec=PipelineSpec(S, M), costs=CostModel.uniform(S),
            min_steps_between_replans=1, decay=0.0)
        table = mon.observe_result(r)
        assert mon.replans == 1 and table is not None
        table.validate()

    def test_w_is_stage_local_in_taskgraph(self):
        spec = PipelineSpec(4, 4, num_chunks=2, split_backward=True)
        for t in spec.tasks():
            if t.kind == Kind.W:
                assert spec.message_successor(t) is None
                assert spec.message_predecessor(t) is None
                assert spec.local_predecessor(t) == Task(
                    Kind.B, t.stage, t.mb, t.chunk)

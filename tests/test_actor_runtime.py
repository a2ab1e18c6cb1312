"""Actor runtime (repro.runtime.rrfp): unit + parity + behaviour tests."""
import threading
import time

import numpy as np
import pytest

from repro.core import (
    CostModel,
    DeadlockError,
    EngineConfig,
    HintKind,
    JitterModel,
    Kind,
    PipelineSpec,
    Task,
    multimodal_stage_flops,
    run_iteration,
)
from repro.runtime.rrfp import (
    ActorConfig,
    ActorDriver,
    Envelope,
    Mailbox,
    StageActor,
    TPGroup,
    envelopes_for,
    run_actor_iteration,
)


def det_costs(S, f=1.0, b=2.0, w=0.0, comm=1e-6, **kw):
    return CostModel.uniform(
        S, f=f, b=b, w=w, comm_base=comm,
        compute_jitter=JitterModel(), comm_jitter=JitterModel(), **kw,
    )


# ---------------------------------------------------------------------------
# Mailbox
# ---------------------------------------------------------------------------
class TestMailbox:
    def test_fifo_per_kind_ordering(self):
        mb = Mailbox(stage=1)
        t1, t2 = Task(Kind.F, 1, 3), Task(Kind.F, 1, 0)
        b1 = Task(Kind.B, 1, 5)
        for t in (t1, b1, t2):
            mb.deliver(Envelope(task=t, src_stage=0, dst_stage=1))
        # per-kind buffers keep arrival order; kinds enumerate F then B
        assert mb.buffers[Kind.F] == [t1, t2]
        assert mb.buffers[Kind.B] == [b1]
        assert mb.arrived_tasks() == [t1, t2, b1]

    def test_consume_removes_and_returns_payload(self):
        mb = Mailbox(stage=1)
        t = Task(Kind.F, 1, 0)
        mb.deliver(Envelope(task=t, src_stage=0, dst_stage=1, payload="act"))
        assert mb.consume(t) == "act"
        assert mb.arrived_tasks() == []

    def test_deliver_wakes_waiter(self):
        mb = Mailbox(stage=0)
        got = []

        def waiter():
            with mb.cond:
                while not mb.arrived_tasks():
                    mb.wait_for_work(1.0)
                got.append(mb.arrived_tasks()[0])

        th = threading.Thread(target=waiter)
        th.start()
        time.sleep(0.05)
        mb.deliver_local(Task(Kind.F, 0, 0))
        th.join(timeout=5)
        assert got == [Task(Kind.F, 0, 0)]


# ---------------------------------------------------------------------------
# TP group admission (§4.2)
# ---------------------------------------------------------------------------
class TestTPGroup:
    def test_all_ranks_gate(self):
        g = TPGroup(stage=2, tp_degree=3)
        t = Task(Kind.F, 2, 0)
        envs = envelopes_for(t, src_stage=1, tp_degree=3)
        assert g.offer(envs[0], now=1.0) is None
        assert g.offer(envs[1], now=1.5) is None
        assert g.pending() == {(t, 1): 1}
        adm = g.offer(envs[2], now=2.0)
        assert adm is not None and adm.task == t
        assert adm.spread == pytest.approx(1.0)
        assert adm.deferred and g.deferrals == 1
        assert g.pending() == {}

    def test_simultaneous_arrival_not_deferred(self):
        g = TPGroup(stage=0, tp_degree=2)
        t = Task(Kind.B, 0, 1)
        for env in envelopes_for(t, src_stage=1, tp_degree=2):
            adm = g.offer(env, now=3.0)
        assert adm is not None and not adm.deferred
        assert g.deferrals == 0

    def test_duplicate_rank_delivery_idempotent(self):
        g = TPGroup(stage=0, tp_degree=2)
        t = Task(Kind.F, 0, 0)
        e0 = Envelope(task=t, src_stage=1, dst_stage=0, rank=0)
        assert g.offer(e0, now=0.0) is None
        assert g.offer(e0, now=9.0) is None  # duplicate: first arrival wins
        adm = g.offer(
            Envelope(task=t, src_stage=1, dst_stage=0, rank=1), now=1.0)
        assert adm.spread == pytest.approx(1.0)

    def test_mailbox_admits_only_after_all_ranks(self):
        mb = Mailbox(stage=1, tp_degree=2)
        t = Task(Kind.F, 1, 0)
        e0, e1 = envelopes_for(t, src_stage=0, tp_degree=2)
        assert mb.deliver(e0) is None
        assert mb.arrived_tasks() == []
        assert mb.deliver(e1) is not None
        assert mb.arrived_tasks() == [t]


# ---------------------------------------------------------------------------
# Parity with the DES engine (acceptance criterion)
# ---------------------------------------------------------------------------
class TestDESParity:
    def test_precommitted_zero_jitter_matches_dispatch_order(self):
        """PRECOMMITTED + zero jitter reproduces the DES per-stage dispatch
        order (and timing) on a 4-stage / 8-microbatch spec.  The DES
        baseline uses async sends (sync_sends=False): the actor runtime's
        transport is message-driven in both consumption modes (§4.1)."""
        spec = PipelineSpec(4, 8)
        cm = det_costs(4, comm=1e-4)
        des = run_iteration(spec, cm, EngineConfig(
            mode="precommitted", fixed_order="1f1b", sync_sends=False))
        act = run_actor_iteration(spec, cm, ActorConfig(
            mode="precommitted", fixed_order="1f1b"))
        assert des.stage_orders() == act.stage_orders()
        assert act.makespan == pytest.approx(des.makespan, rel=1e-9)
        for t in spec.tasks():
            assert act.start[t] == pytest.approx(des.start[t], abs=1e-9)

    def test_hint_zero_jitter_matches_des(self):
        spec = PipelineSpec(4, 8)
        cm = det_costs(4, comm=1e-4)
        des = run_iteration(spec, cm, EngineConfig(mode="hint"))
        act = run_actor_iteration(spec, cm, ActorConfig(mode="hint"))
        assert des.stage_orders() == act.stage_orders()
        assert act.makespan == pytest.approx(des.makespan, rel=1e-9)

    def test_hint_beats_precommitted_on_same_sampled_latencies(self):
        """Acceptance: BF hint under heavy-tailed jitter strictly beats
        precommitted 1F1B.  Sampling is CRN-keyed per task, so both modes
        see the same realized compute/comm draws."""
        S, M = 8, 32
        spec = PipelineSpec(S, M)
        cm = CostModel.from_stage_flops(
            multimodal_stage_flops(4e12, 2e12, S), comm_base=2e-3, seed=3)
        m_pre = run_actor_iteration(spec, cm, ActorConfig(
            mode="precommitted", fixed_order="1f1b", seed=11)).makespan
        m_hint = run_actor_iteration(spec, cm, ActorConfig(
            mode="hint", seed=11)).makespan
        assert m_hint < m_pre

    def test_all_tasks_execute_exactly_once(self):
        spec = PipelineSpec(6, 10, split_backward=True)
        cm = det_costs(6, w=0.5)
        r = run_actor_iteration(
            spec, cm, ActorConfig(mode="hint", hint=HintKind.BFW))
        assert set(r.end) == set(spec.tasks())

    def test_dependencies_respected_in_trace(self):
        spec = PipelineSpec(6, 8)
        cm = CostModel.from_stage_flops(
            multimodal_stage_flops(4e12, 2e12, 6), comm_base=1e-3, seed=9)
        r = run_actor_iteration(spec, cm, ActorConfig(mode="hint", seed=4))
        for t in spec.tasks():
            for p in spec.predecessors(t):
                assert r.start[t] >= r.end[p] - 1e-12, (t, p)


# ---------------------------------------------------------------------------
# Backpressure (App. C)
# ---------------------------------------------------------------------------
class TestBackpressure:
    def test_backward_only_drain_bounds_inflight(self):
        S, M, limit = 4, 32, 3
        spec = PipelineSpec(S, M)
        cm = det_costs(S, f=1.0, b=0.1)  # cheap B: F wants to run far ahead
        r = run_actor_iteration(
            spec, cm, ActorConfig(mode="hint", buffer_limit=limit))
        ev = sorted((r.end[t], t.kind, t.stage) for t in r.end)
        d = 0
        for _, k, s in ev:
            if s == 0 and k == Kind.F:
                d += 1
            if s == 0 and k == Kind.B:
                d -= 1
            assert d <= limit + 1  # Thm C.1

    def test_interleaved_drain_completes(self):
        spec = PipelineSpec(4, 8, num_chunks=2)
        cm = det_costs(4, f=1.0, b=0.2, comm=1e-3)
        r = run_actor_iteration(
            spec, cm, ActorConfig(mode="hint", buffer_limit=2))
        assert set(r.end) == set(spec.tasks())


# ---------------------------------------------------------------------------
# TP coordination in the driver
# ---------------------------------------------------------------------------
class TestTPRuntime:
    def test_tp_coordination_overhead_small_but_nonzero(self):
        S, M = 8, 32
        spec = PipelineSpec(S, M)
        cm = CostModel.from_stage_flops(
            multimodal_stage_flops(4e12, 2e12, S), seed=2)
        r = run_actor_iteration(spec, cm, ActorConfig(mode="hint", tp_degree=2))
        bd = r.breakdown()
        assert bd["tp_coord"] > 0
        assert bd["tp_coord"] < 0.05 * bd["iter"]
        r1 = run_actor_iteration(spec, cm, ActorConfig(mode="hint", tp_degree=1))
        assert r1.breakdown()["tp_coord"] == 0.0

    def test_rank_divergence_counted(self):
        S = 4
        spec = PipelineSpec(S, 8)
        cm = CostModel.uniform(S, comm_base=1e-3)  # default comm jitter: spread
        r = run_actor_iteration(spec, cm, ActorConfig(mode="hint", tp_degree=2))
        assert sum(s.deferrals for s in r.stage_stats) > 0


# ---------------------------------------------------------------------------
# Deadlock detection
# ---------------------------------------------------------------------------
class TestDeadlock:
    @staticmethod
    def _deadlocked_orders(spec):
        """Stage 0 insists on B[0] first, which can never arrive."""
        M = spec.num_microbatches
        o0 = [Task(Kind.B, 0, 0)] + [Task(Kind.F, 0, j) for j in range(M)] + [
            Task(Kind.B, 0, j) for j in range(1, M)]
        rest = [
            [Task(Kind.F, s, j) for j in range(M)]
            + [Task(Kind.B, s, j) for j in range(M)]
            for s in range(1, spec.num_stages)
        ]
        return [o0] + rest

    def test_sim_deadlock_raises_with_starved_stage(self):
        spec = PipelineSpec(3, 4)
        cm = det_costs(3)
        cfg = ActorConfig(mode="precommitted",
                          custom_orders=self._deadlocked_orders(spec))
        with pytest.raises(DeadlockError) as ei:
            run_actor_iteration(spec, cm, cfg)
        assert "starved" in str(ei.value)

    def test_thread_deadlock_raises_on_starved_stage(self):
        spec = PipelineSpec(3, 4)
        cfg = ActorConfig(mode="precommitted",
                          custom_orders=self._deadlocked_orders(spec),
                          deadlock_timeout=0.3)
        driver = ActorDriver(spec, None, cfg)
        with pytest.raises(DeadlockError) as ei:
            driver.run_threaded(lambda task, payload: None)
        assert "starved" in str(ei.value)


# ---------------------------------------------------------------------------
# Thread transport with synthetic work
# ---------------------------------------------------------------------------
class TestThreaded:
    def test_all_tasks_run_and_dependencies_hold(self):
        spec = PipelineSpec(4, 6)
        done_log = []
        lock = threading.Lock()

        def work(task, payload):
            time.sleep(0.001)
            with lock:
                done_log.append(task)
            return f"out-{task}"

        r = ActorDriver(spec, None, ActorConfig(mode="hint")).run_threaded(work)
        assert set(r.end) == set(spec.tasks())
        for t in spec.tasks():
            for p in spec.predecessors(t):
                assert r.end[p] <= r.start[t] + 1e-9, (t, p)

    def test_payloads_flow_downstream(self):
        spec = PipelineSpec(3, 2)
        seen = {}

        def work(task, payload):
            seen[task] = payload
            return (task.stage, task.mb, task.kind)

        ActorDriver(spec, None, ActorConfig(mode="hint")).run_threaded(work)
        # F at stage>0 received the upstream F's payload
        assert seen[Task(Kind.F, 1, 0)] == (0, 0, Kind.F)
        assert seen[Task(Kind.B, 1, 1)] == (2, 1, Kind.B)
        # locally-enabled tasks carry no message payload
        assert seen[Task(Kind.F, 0, 0)] is None
        assert seen[Task(Kind.B, 2, 0)] is None

    def test_precommitted_threaded_order_respected(self):
        spec = PipelineSpec(2, 4)
        order_log = {0: [], 1: []}
        lock = threading.Lock()

        def work(task, payload):
            with lock:
                order_log[task.stage].append(task)
            return None

        r = ActorDriver(spec, None, ActorConfig(
            mode="precommitted", fixed_order="1f1b")).run_threaded(work)
        from repro.core.hints import one_f_one_b_order

        for s in range(2):
            assert order_log[s] == one_f_one_b_order(spec, s)
        assert len(r.end) == spec.total_tasks()


# ---------------------------------------------------------------------------
# Event-driven wakeups: no poll loop, so shutdown must notify
# ---------------------------------------------------------------------------
class TestShutdownWakeups:
    """With condition-variable wakeups there is no 50 ms poll period to fall
    back on: a blocked actor wakes only on notify or at its (long)
    starvation deadline.  These tests pin the notify paths with join/elapsed
    bounds far below the deadlock timeout."""

    def test_stop_notifies_blocked_waiter(self):
        mb = Mailbox(stage=0)
        woke = threading.Event()

        def waiter():
            with mb.cond:
                while not mb.stopped:
                    mb.wait_for_work(30.0)
            woke.set()

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        time.sleep(0.05)  # let the waiter block
        mb.stop()
        th.join(timeout=2.0)
        assert woke.is_set() and not th.is_alive(), (
            "Mailbox.stop() did not wake a blocked waiter")

    def test_deliver_wakes_blocked_waiter(self):
        mb = Mailbox(stage=0)
        got = []

        def waiter():
            with mb.cond:
                while not mb.arrived_tasks():
                    mb.wait_for_work(30.0)
                got.extend(mb.drain_arrivals())

        th = threading.Thread(target=waiter, daemon=True)
        th.start()
        time.sleep(0.05)
        t = Task(Kind.F, 0, 0)
        mb.deliver(Envelope(task=t, src_stage=1, dst_stage=0))
        th.join(timeout=2.0)
        assert not th.is_alive() and got == [t]

    def test_worker_error_aborts_all_stages_promptly(self):
        """A raising work_fn must take the whole run down well before any
        sibling's starvation deadline (the driver stops every mailbox)."""
        spec = PipelineSpec(4, 4)

        def work(task, payload):
            if task.stage == 2 and task.kind == Kind.B:
                raise RuntimeError("injected stage failure")
            return None

        driver = ActorDriver(spec, None, ActorConfig(
            mode="hint", deadlock_timeout=30.0))
        t0 = time.monotonic()
        with pytest.raises(RuntimeError, match="injected stage failure"):
            driver.run_threaded(work)
        elapsed = time.monotonic() - t0
        assert elapsed < 10.0, (
            f"abort took {elapsed:.1f}s — sibling actors were not woken "
            f"(deadlock_timeout was 30s)")

    def test_threaded_run_joins_promptly_after_completion(self):
        """Completion itself must not wait out any poll/starvation period."""
        spec = PipelineSpec(3, 4)
        driver = ActorDriver(spec, None, ActorConfig(
            mode="hint", deadlock_timeout=30.0))
        t0 = time.monotonic()
        r = driver.run_threaded(lambda task, payload: None)
        elapsed = time.monotonic() - t0
        assert len(r.end) == spec.total_tasks()
        assert elapsed < 10.0, f"join took {elapsed:.1f}s"


# ---------------------------------------------------------------------------
# Thread transport driving real jitted stage callables
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_threaded_real_model_matches_reference():
    """Thread-per-stage actors over jitted stage callables reproduce the
    single-pass reference loss (pipeline/stagefn factored from executor)."""
    import jax
    import jax.numpy as jnp

    from repro.configs import registry
    from repro.models.build import build
    from repro.pipeline.stagefn import (
        ActorStageProgram, StageFnOptions, StageFns, chunked_ce_sum)

    S, M, mb_rows, seq = 2, 4, 2, 16
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
    programs = [
        ActorStageProgram(
            fns, s, jax.tree.map(lambda x, s=s: x[s], sp), io, batch)
        for s in range(S)
    ]
    spec = PipelineSpec(S, M)
    r = ActorDriver(spec, None, ActorConfig(
        mode="hint", deadlock_timeout=300.0)).run_threaded(list(programs))
    assert set(r.end) == set(spec.tasks())
    loss = sum(p.loss_sum for p in programs) / tokens

    aux = {"positions": jnp.broadcast_to(jnp.arange(seq)[None], (B_rows, seq)),
           "data_size": 1, "moe_layout": "none"}
    x = model.embed(io, batch)
    for s in range(S):
        spl = jax.tree.map(lambda p, s=s: p[s], sp)
        x = model.stage_forward(spl, io, x, aux, model.rows(s))
    ref = float(chunked_ce_sum(model, io, x, batch["labels"],
                               fns.ce_chunk) / tokens)
    assert abs(loss - ref) < 2e-3 * max(1, abs(ref)), (loss, ref)
    # every stage accumulated nonzero parameter grads
    for p in programs:
        mass = sum(float(jnp.abs(leaf).sum())
                   for leaf in jax.tree.leaves(p.d_stage))
        assert mass > 0


# ---------------------------------------------------------------------------
# Gradients folded into donated accumulators inside the B/W callable
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def real_model():
    """The reduced deepseek-7b pipeline of the tests above: 2 stages, 4
    microbatches of 2 rows, seq 16."""
    import jax

    from repro.configs import registry
    from repro.models.build import build

    S, M, mb_rows, seq = 2, 4, 2, 16
    model = build(registry.reduced_config("deepseek-7b", num_layers=4),
                  num_stages=S)
    key = jax.random.key(0)
    sp = model.init_stage_params(key)
    io = model.init_io_params(jax.random.fold_in(key, 1))
    batch = {k: jax.random.randint(jax.random.key(i), (M * mb_rows, seq), 0,
                                   model.cfg.vocab_size)
             for i, k in ((2, "tokens"), (3, "labels"))}
    stages = [jax.tree.map(lambda x, s=s: x[s], sp) for s in range(S)]
    return dict(model=model, S=S, M=M, mb_rows=mb_rows, seq=seq,
                stages=stages, io=io, batch=batch)


def _fns(m):
    from repro.pipeline.stagefn import StageFnOptions, StageFns

    tokens = m["M"] * m["mb_rows"] * m["seq"]
    return StageFns(m["model"], StageFnOptions(
        mb_rows=m["mb_rows"], seq_len=m["seq"], loss_scale=1.0 / tokens))


def _run_step(m, fns, *, split, deterministic=False):
    from repro.pipeline.stagefn import ActorStageProgram

    programs = [ActorStageProgram(fns, s, m["stages"][s], m["io"],
                                  m["batch"], split_backward=split,
                                  deterministic_reduction=deterministic)
                for s in range(m["S"])]
    spec = PipelineSpec(m["S"], m["M"], split_backward=split)
    r = ActorDriver(spec, None, ActorConfig(
        mode="hint", hint=HintKind.BFW if split else HintKind.BF,
        w_defer_cap=2 if split else 0,
        deadlock_timeout=300.0)).run_threaded(list(programs))
    assert set(r.end) == set(spec.tasks())
    return [p.finalize() for p in programs]


@pytest.mark.slow
@pytest.mark.parametrize("split", [False, True], ids=["bf", "bfw"])
def test_folded_accumulators_match_the_deterministic_fold(real_model, split):
    """Eager mode folds each microbatch's grads inside the callable; the
    sums equal the deterministic mode's microbatch-order fold of the same
    grads, leaf by leaf in each leaf's dtype, within float32 rounding."""
    import jax
    import jax.numpy as jnp

    m = real_model
    fns = _fns(m)
    eager = _run_step(m, fns, split=split)
    det = _run_step(m, fns, split=split, deterministic=True)
    for pe, pd in zip(eager, det):
        assert pe.folded_in_callable == m["M"]
        assert pd.folded_in_callable == 0
        assert abs(pe.loss_sum - pd.loss_sum) <= 1e-6 * abs(pd.loss_sum)
        for ge, gd in zip(jax.tree.leaves((pe.d_stage, pe.d_io)),
                          jax.tree.leaves((pd.d_stage, pd.d_io))):
            assert ge.dtype == gd.dtype and ge.shape == gd.shape
            scale = float(jnp.max(jnp.abs(gd))) + 1e-30
            assert float(jnp.max(jnp.abs(ge - gd))) <= 1e-5 * scale


@pytest.mark.slow
@pytest.mark.parametrize("split", [False, True], ids=["bf", "bfw"])
def test_fold_donates_the_previous_accumulators(real_model, split):
    """The B (fused) or W (split) call takes the accumulators donated: the
    arrays the program held before the call are deleted after it."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline.stagefn import ActorStageProgram

    m = real_model
    fns = _fns(m)
    prog = ActorStageProgram(fns, 0, m["stages"][0], m["io"], m["batch"],
                             split_backward=split)
    y = prog(Task(Kind.F, 0, 0), None)
    before = jax.tree.leaves((prog.d_stage, prog.d_io))
    prog(Task(Kind.B, 0, 0), jnp.ones_like(y))
    if split:
        assert not any(leaf.is_deleted() for leaf in before)
        prog(Task(Kind.W, 0, 0), None)
    assert all(leaf.is_deleted() for leaf in before)
    assert prog.folded_in_callable == 1
    after = jax.tree.leaves((prog.d_stage, prog.d_io))
    assert [a.dtype for a in after] == [b.dtype for b in before]
    assert sum(float(jnp.abs(a).sum()) for a in after) > 0


@pytest.mark.parametrize("op,name", [("backward_into", "B"),
                                     ("weight_grad_into", "W")])
def test_accumulating_callable_keeps_its_module_name_and_aliases(
        real_model, op, name):
    """The accumulating callables lower as ``jit_stage{s}_B``/``_W`` and
    alias every accumulator leaf to an output."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline.stagefn import microbatch, zero_accumulators

    m = real_model
    fns = _fns(m)
    sp1, io = m["stages"][1], m["io"]
    acc, _ = zero_accumulators(sp1, io)
    x = jnp.zeros((m["mb_rows"], m["seq"], m["model"].cfg.d_model),
                  m["model"].cfg.dtype)
    text = getattr(fns, op)(1).lower(
        acc, sp1, io, x, x, microbatch(m["batch"], 0, m["mb_rows"])).as_text()
    assert f"module @jit_stage1_{name}" in text
    assert text.count("tf.aliasing_output") == len(jax.tree.leaves(acc))


@pytest.mark.slow
@pytest.mark.parametrize("split", [False, True], ids=["bf", "bfw"])
def test_warm_up_leaves_nothing_to_compile(real_model, split):
    """After ``warm_up`` a threaded step compiles nothing: every callable it
    calls was compiled for the step's shapes, accumulators included."""
    import jax

    from repro.pipeline.stagefn import warm_up, zero_accumulators

    m = real_model
    fns = _fns(m)
    warm_up(fns, m["stages"], m["io"], m["batch"], split_backward=split)
    ops = ["forward", "backward_dx", "weight_grad_into"] if split else \
        ["forward", "backward_into"]
    jitted = [zero_accumulators] + [getattr(fns, op)(s) for op in ops
                                    for s in range(m["S"])]
    sizes = [f._cache_size() for f in jitted]
    compiles = []

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            compiles.append(name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        programs = _run_step(m, fns, split=split)
        jax.block_until_ready([(p.d_stage, p.d_io) for p in programs])
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    assert [f._cache_size() for f in jitted] == sizes
    assert compiles == []


def test_embed_rows_backward_sums_each_token_in_float32():
    """``embed_rows`` is the plain gather forward; its table gradient equals
    the gather's in float32 and, in bfloat16 with a frequent token, lies
    closer to the exact sum than the gather's one rounding per position."""
    import jax
    import jax.numpy as jnp

    from repro.pipeline.stagefn import embed_rows

    rng = np.random.default_rng(0)
    vocab, d = 64, 16
    tokens = jnp.asarray(np.where(rng.random((2, 256)) < 0.5, 3,
                                  rng.integers(0, vocab, (2, 256))), jnp.int32)
    ct = rng.standard_normal((2, 256, d)) * 0.1 + 1.0
    table = jnp.asarray(rng.standard_normal((vocab, d)), jnp.float32)

    def grads(lookup, dtype):
        g = jnp.asarray(ct, dtype)
        return np.asarray(jax.jit(jax.grad(lambda t: jnp.sum(
            (lookup(t, tokens) * g).astype(jnp.float32))))(
                table.astype(dtype)), np.float64)

    gather = lambda t, tok: t[tok]  # noqa: E731
    assert np.array_equal(np.asarray(embed_rows(table, tokens)),
                          np.asarray(table[tokens]))
    np.testing.assert_allclose(grads(embed_rows, jnp.float32),
                               grads(gather, jnp.float32), rtol=1e-5,
                               atol=1e-5)
    exact = np.zeros((vocab, d))
    np.add.at(exact, np.asarray(tokens).reshape(-1),
              np.asarray(jnp.asarray(ct, jnp.bfloat16), np.float64)
              .reshape(-1, d))
    err = {f: np.abs(grads(f, jnp.bfloat16) - exact).max()
           for f in (embed_rows, gather)}
    assert err[embed_rows] < err[gather]


# ---------------------------------------------------------------------------
# Straggler monitor feedback from actor traces
# ---------------------------------------------------------------------------
def test_straggler_monitor_consumes_actor_result():
    from repro.runtime.straggler import StragglerMonitor

    S, M = 4, 8
    spec = PipelineSpec(S, M)
    skewed = CostModel.uniform(S, comm_base=1e-4)
    skewed.f_cost[2] *= 4.0  # persistent straggler stage
    r = run_actor_iteration(spec, skewed, ActorConfig(mode="hint"))
    mon = StragglerMonitor(spec=spec, costs=CostModel.uniform(S),
                           min_steps_between_replans=1, decay=0.0)
    table = mon.observe_result(r)
    assert mon.replans == 1 and table is not None
    table.validate()

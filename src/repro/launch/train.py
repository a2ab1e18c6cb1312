"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
        --devices 8 --stages 4 --steps 20 --schedule rrfp --ckpt-dir /tmp/ck

Runs on whatever devices exist (forced host devices for CPU runs), wiring
together: synthetic data prefetch, the schedule-table executor, ZeRO-1
AdamW, checkpoint/restart, straggler-driven re-synthesis, and (optionally)
jitter injection to demonstrate the RRFP loop end-to-end.

``--runtime actor`` (opt-in) swaps the compiled schedule-table executor for
the host actor runtime (``repro.runtime.rrfp``): thread-per-stage actors
dispatch real jitted stage callables by message arrival under hint-order
arbitration, accumulate grads per stage, and feed realized per-task timings
into the straggler monitor's EMA — the paper's runtime loop made executable:

    PYTHONPATH=src python -m repro.launch.train --runtime actor \
        --arch deepseek-7b --stages 2 --microbatches 4 --steps 5 --seq 32
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.ckpt.store import CheckpointStore
from repro.configs import registry
from repro.core.costs import CostModel
from repro.core.hints import HintKind
from repro.core.taskgraph import PipelineSpec
from repro.data.synthetic import PrefetchIterator, synth_batch
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.build import build
from repro.models.common import ArchConfig
from repro.optim.adamw import AdamWConfig, make_optimizer
from repro.pipeline import schedules
from repro.pipeline.executor import ExecOptions, make_train_fn
from repro.pipeline.sharding import partition_for
from repro.runtime.straggler import StragglerMonitor


@dataclasses.dataclass
class RunLog:
    """What a language-workload run returns: per step, the loss and the host
    seconds the step took, ending once its results are on the host."""
    losses: list[float] = dataclasses.field(default_factory=list)
    seconds: list[float] = dataclasses.field(default_factory=list)


def model_config(args) -> ArchConfig:
    """The run's config (``--full-size``: published widths, cut in depth
    only), announced on one line."""
    cfg = registry.model_config(args.arch, args.layers,
                                full_size=args.full_size)
    print(f"arch={args.arch} "
          f"{'published' if args.full_size else 'reduced'} widths "
          f"d_model={cfg.d_model} heads={cfg.num_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size}  depth {cfg.num_layers} of "
          f"{registry.get_arch(args.arch).num_layers}", flush=True)
    return cfg


def init_params(model):
    """Initial (stage, io) parameters from one fixed key: every runtime and
    every stage split of a config starts from the same model."""
    key = jax.random.key(0)
    return (model.init_stage_params(key),
            model.init_io_params(jax.random.fold_in(key, 1)))


def build_trainer(cfg: ArchConfig, *, data: int, stages: int,
                  mb_rows: int, microbatches: int, seq: int,
                  schedule: str = "rrfp",
                  lr: float = 1e-3, total_steps: int = 1000):
    model = build(cfg, num_stages=stages)
    mesh = make_mesh(data, stages)
    stage_params, io_params = init_params(model)
    partition = partition_for(model, stage_params, io_params)
    # place the parameters where train_step returns them: step 1 then reuses
    # step 0's compiled program instead of compiling it again
    def on_mesh(tree, specs):
        return jax.device_put(tree, jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs,
            is_leaf=lambda x: isinstance(x, PartitionSpec)))

    stage_params = on_mesh(stage_params, partition.stage_specs)
    io_params = on_mesh(io_params, partition.io_specs)

    spec = PipelineSpec(stages, microbatches,
                        split_backward=(schedule == "zb"))
    table = schedules.BUILDERS[schedule](spec)
    global_tokens = data * microbatches * mb_rows * seq
    opts = ExecOptions(mb_rows=mb_rows, seq_len=seq,
                       loss_scale=1.0 / global_tokens)
    exec_fn, _ = make_train_fn(model, table, mesh, opts, partition)
    opt_cfg = AdamWConfig(lr=lr, warmup_steps=20, total_steps=total_steps)
    opt_init, opt_update = make_optimizer(model, mesh, partition, opt_cfg)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(stage_params, io_params, opt_state, batch, step):
        metrics, grad_shard, expert_grads = exec_fn(
            stage_params, io_params, batch)
        with jax.named_scope("optimizer"):
            stage_params, io_params, opt_state, stats = opt_update(
                stage_params, io_params, opt_state, grad_shard,
                expert_grads, step)
        return stage_params, io_params, opt_state, {**metrics, **stats}

    opt_state = jax.jit(opt_init)(stage_params, io_params)
    batch_size = data * microbatches * mb_rows
    return dict(
        cfg=cfg, model=model, mesh=mesh, table=table, spec=spec,
        stage_params=stage_params, io_params=io_params,
        opt_state=opt_state, train_step=train_step,
        batch_size=batch_size, seq=seq, partition=partition,
        exec_fn=exec_fn, opts=opts,
    )


# ---------------------------------------------------------------------------
# observability (--metrics-report / --export-perfetto; actor runtime only)
# ---------------------------------------------------------------------------
def _obs_registry(args):
    """A MetricsRegistry when ``--metrics-report`` asked for one, else None
    (None keeps the runtime's metrics hooks at their zero-cost path)."""
    if not getattr(args, "metrics_report", False):
        return None
    from repro.obs import MetricsRegistry
    return MetricsRegistry()


def _obs_record_step0(args, step: int, first: int = 0) -> bool:
    """Record the first step's trace when any end-of-run consumer needs
    it (Perfetto export, the --explain health report, or --record-trace)."""
    return step == first and (
        bool(args.record_trace)
        or bool(getattr(args, "export_perfetto", None))
        or bool(getattr(args, "explain", False)))


def _obs_finish(args, registry, trace) -> None:
    """End-of-run sync point: print the summary table, export Perfetto."""
    if registry is not None and getattr(args, "metrics_report", False):
        print("\nper-stage metrics (accumulated over all steps):")
        print(registry.report())
    if getattr(args, "export_perfetto", None):
        from repro.obs import export_perfetto
        if trace is None:
            raise SystemExit(
                "--export-perfetto: no trace was recorded to export")
        export_perfetto(trace, args.export_perfetto)
        print(f"perfetto export ({len(trace.events)} events) -> "
              f"{args.export_perfetto}  (open at ui.perfetto.dev)")
    if getattr(args, "explain", False):
        from repro.obs.report import explain
        if trace is None:
            raise SystemExit(
                "--explain: no trace was recorded to analyze")
        print("\n" + explain(trace).format())


# ---------------------------------------------------------------------------
# multimodal DAG workload (--workload multimodal)
# ---------------------------------------------------------------------------
def _multimodal_stage_split(stages: int) -> tuple[int, int]:
    """Split a total stage budget into (encoder, LM) branch depths.

    Total stages = encoder branch + 1 text frontend + LM chain; the LM
    chain (fusion + decoder) gets at least as many stages as the encoder.
    """
    if stages < 3:
        raise SystemExit(
            "--workload multimodal needs --stages >= 3 "
            "(encoder branch + text frontend + fusion/LM chain)")
    enc = max(1, (stages - 1) // 2)
    return enc, stages - 1 - enc


def train_multimodal(args) -> list[float]:
    """Train the branch+fusion multimodal DAG pipeline on the actor runtime.

    ``--substrate thread`` (default) drives the real jitted encoder /
    fusion / LM stage callables with thread-per-stage actors, including
    variable-length vision/audio microbatches via shape bucketing and
    (optionally) BFW split backward.  ``--substrate sim`` runs the same
    DAG task graph through the virtual-clock actor substrate on the DES
    cost model of the same topology (per-microbatch skew from the shared
    modality length sampler) — useful for schedule experiments without a
    device.  Returns the loss history (thread) or makespan history (sim).
    """
    from repro.multimodal import (
        MultimodalStageFns, multimodal_config, multimodal_dag_costs,
        multimodal_model)
    from repro.multimodal.model import MULTIMODAL_ARCHS
    from repro.pipeline.stagefn import ActorStageProgram, StageFnOptions
    from repro.optim.adamw import AdamWConfig, make_host_update
    from repro.runtime.rrfp import ActorConfig, ActorDriver, parse_chaos

    if args.arch is None:
        args.arch = "qwen2-vl-2b"
    if args.arch not in MULTIMODAL_ARCHS:
        raise SystemExit(
            f"--workload multimodal needs a multimodal arch, not "
            f"{args.arch!r}; registered: {sorted(MULTIMODAL_ARCHS)}")
    if args.replay_trace:
        raise SystemExit("--replay-trace is not supported for the "
                         "multimodal workload yet; record works")
    enc_stages, lm_stages = _multimodal_stage_split(args.stages)
    model = multimodal_model(
        args.arch, enc_stages=enc_stages, lm_stages=lm_stages,
        text_seq=args.seq, reduced=not args.full_size,
        num_layers=args.layers)
    cfg = model.cfg
    split = args.split_backward or args.schedule == "zb"
    hint = HintKind(args.hint)
    chaos = parse_chaos(args.chaos) if args.chaos else None
    spec = cfg.spec(args.microbatches, split_backward=split)
    if args.schedule == "rrfp":
        mode, fixed = "hint", "1f1b"
        if split != (hint == HintKind.BFW):
            raise SystemExit(
                "--hint bfw and --split-backward go together (the BFW hint "
                "needs W tasks, which only exist under split backward)")
    elif args.schedule in ("1f1b", "gpipe", "zb"):
        mode, fixed = "precommitted", args.schedule
        if (args.schedule == "zb") != split:
            raise SystemExit("--schedule zb is the split-backward baseline; "
                             "1f1b/gpipe are fused-only")
    else:
        raise SystemExit(
            f"--workload multimodal supports schedules rrfp/1f1b/gpipe/zb, "
            f"not {args.schedule!r}")
    registry = _obs_registry(args)
    acfg = ActorConfig(mode=mode, hint=hint, fixed_order=fixed,
                       w_defer_cap=args.w_defer_cap,
                       deadlock_timeout=args.deadlock_timeout,
                       chaos=chaos, seed=args.seed, metrics=registry)
    print(f"arch={args.arch} workload=multimodal modality={cfg.modality}  "
          f"substrate={args.substrate}  mode={mode}  hint={hint.value}  "
          f"split_backward={split}\n"
          f"  DAG: encoder x{enc_stages} | text | fusion + LM x"
          f"{lm_stages - 1}  edges={cfg.stage_graph().edges}  "
          f"buckets={cfg.buckets}")

    if args.substrate == "sim":
        # cost model from the FULL-SIZE arch (simulated timing should
        # reflect the real widths even when the jit path runs reduced)
        cost_cfg = multimodal_config(
            args.arch, enc_stages=enc_stages, lm_stages=lm_stages,
            text_seq=max(args.seq, 512), mean_enc_tokens=2048,
            buckets=(1024, 2048, 4096), reduced=False)
        costs = multimodal_dag_costs(cost_cfg, mb_rows=args.mb_rows,
                                     seed=args.seed)
        history = []
        obs_trace = None
        for step in range(args.steps):
            record_this = _obs_record_step0(args, step)
            cfg_i = dataclasses.replace(acfg, seed=args.seed + 1000 * step,
                                        record_trace=record_this)
            driver = ActorDriver(spec, costs, cfg_i)
            res = driver.run()
            if record_this:
                driver.trace.meta["step"] = step
                obs_trace = driver.trace
                if args.record_trace:
                    driver.trace.save(args.record_trace)
                    print(f"recorded step-0 trace "
                          f"({len(driver.trace.events)} events) "
                          f"-> {args.record_trace}")
            bd = res.breakdown()
            history.append(res.makespan)
            print(f"step {step:4d}  makespan {res.makespan*1e3:8.2f} ms  "
                  f"compute {bd['compute']*1e3:7.2f} ms  "
                  f"blocking {bd['blocking']*1e3:7.2f} ms")
        _obs_finish(args, registry, obs_trace)
        return history

    # ---- thread substrate: real jitted DAG training -------------------
    from repro.data.synthetic import multimodal_batch

    params = model.init_stage_params(jax.random.key(args.seed))
    tokens = args.microbatches * args.mb_rows * args.seq
    fns = MultimodalStageFns(model, StageFnOptions(
        mb_rows=args.mb_rows, seq_len=args.seq, loss_scale=1.0 / tokens))
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                          total_steps=max(args.steps, 1))
    mstate = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    vstate = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), params)
    apply_update = make_host_update(opt_cfg)

    losses: list[float] = []
    obs_trace = None
    for step in range(args.steps):
        batch = multimodal_batch(cfg, args.microbatches, args.mb_rows,
                                 seed=args.seed, step=step)
        programs = [
            ActorStageProgram(fns, s, params[s], {}, batch,
                              split_backward=split)
            for s in range(cfg.num_stages)
        ]
        t0 = time.time()
        record_this = _obs_record_step0(args, step)
        driver = ActorDriver(
            spec, None,
            dataclasses.replace(acfg, record_trace=True) if record_this
            else acfg)
        result = driver.run_threaded(list(programs))
        grads = [p.d_stage for p in programs]
        params, mstate, vstate, lr = apply_update(
            params, grads, mstate, vstate, jnp.asarray(step, jnp.int32))
        loss = float(sum(p.loss_acc for p in programs)) / tokens
        losses.append(loss)
        if record_this:
            trace = driver.trace
            trace.meta["step"] = step
            trace.meta["final_loss"] = loss
            obs_trace = trace
            if args.record_trace:
                trace.save(args.record_trace)
                print(f"recorded step-0 trace ({len(trace.events)} events) "
                      f"-> {args.record_trace}")
        bd = result.breakdown()
        dt = time.time() - t0
        print(f"step {step:4d}  loss {loss:8.4f}  lr {float(lr):.2e}  "
              f"{dt*1e3:7.1f} ms  makespan {result.makespan*1e3:7.1f} ms  "
              f"blocking {bd['blocking']*1e3:6.1f} ms")
    caches = fns.compile_cache_sizes()
    enc_caches = {k: v for k, v in caches.items()
                  if cfg.role_of(k[1]) == "encoder"}
    if enc_caches:
        print(f"jit retraces on encoder stages: "
              f"max {max(enc_caches.values())} per op "
              f"(bucket count {len(cfg.buckets)})")
    _obs_finish(args, registry, obs_trace)
    return losses


# ---------------------------------------------------------------------------
# actor-runtime backend (opt-in via --runtime actor)
# ---------------------------------------------------------------------------
def train_actor(args, cfg: ArchConfig) -> RunLog:
    """Train with thread-per-stage actors dispatching real stage callables.

    Single-process: stage s's parameters live with stage s's actor; AdamW
    runs host-side over the accumulated per-stage grads."""
    from repro.optim.adamw import make_host_update
    from repro.pipeline.stagefn import (
        ActorStageProgram, StageFnOptions, StageFns, warm_up)
    from repro.runtime.rrfp import ActorConfig, ActorDriver, Trace, parse_chaos

    model = build(cfg, num_stages=args.stages)
    stage_params, io_params = init_params(model)
    split = args.split_backward or args.schedule == "zb"
    hint = HintKind(args.hint)
    chaos = parse_chaos(args.chaos) if args.chaos else None
    replay = None
    if args.replay_trace:
        if args.chaos:
            raise SystemExit("--replay-trace replays the recorded arrival "
                             "order; combining it with --chaos is undefined")
        replay = Trace.load(args.replay_trace)
        meta = replay.meta
        for k, want in (("num_stages", args.stages),
                        ("num_microbatches", args.microbatches),
                        ("split_backward", split)):
            if meta.get(k) is not None and meta[k] != want:
                raise SystemExit(
                    f"--replay-trace {args.replay_trace}: recorded {k}="
                    f"{meta[k]} does not match this run's {want}")
    spec = PipelineSpec(args.stages, args.microbatches, split_backward=split)
    batch_size = args.microbatches * args.mb_rows
    tokens = batch_size * args.seq
    fns = StageFns(model, StageFnOptions(
        mb_rows=args.mb_rows, seq_len=args.seq, loss_scale=1.0 / tokens))
    if args.schedule == "rrfp":
        mode, fixed = "hint", "1f1b"
        if split != (hint == HintKind.BFW):
            raise SystemExit(
                "--hint bfw and --split-backward go together: the BFW hint "
                "needs W tasks, which only exist under split backward (and "
                "only the BFW hint dispatches them)")
    elif args.schedule == "zb":
        mode, fixed = "precommitted", "zb"
    elif args.schedule in ("1f1b", "gpipe"):
        if split:
            raise SystemExit(
                f"--split-backward is not defined for the fused-order "
                f"{args.schedule!r} baseline; use --schedule zb")
        mode, fixed = "precommitted", args.schedule
    else:
        raise SystemExit(
            f"--runtime actor supports schedules rrfp/1f1b/gpipe/zb, "
            f"not {args.schedule!r}")
    # NB: name must not shadow the module-level arch ``registry`` used above
    metrics_reg = _obs_registry(args)
    scheduler = None
    if args.adaptive:
        if mode != "hint":
            raise SystemExit("--adaptive re-synthesizes the hint table; it "
                             "requires --schedule rrfp")
        if args.replay_trace:
            raise SystemExit("--adaptive changes the hint table between "
                             "steps; combining it with --replay-trace is "
                             "undefined")
        from repro.obs import MetricsRegistry
        from repro.runtime.adaptive import AdaptiveConfig, AdaptiveScheduler

        if metrics_reg is None:
            metrics_reg = MetricsRegistry(args.stages)
        # synthesis prices tables on an expected cost model; the registry's
        # measured EWMAs (real stage timings) overwrite it cell by cell
        base_costs = CostModel.uniform(args.stages)
        if split:
            base_costs = base_costs.with_split_backward()
        scheduler = AdaptiveScheduler(
            spec, base_costs,
            AdaptiveConfig(resynth_every=args.resynth_every,
                           swap_threshold=args.swap_threshold,
                           hint=hint),
            registry=metrics_reg)
    acfg = ActorConfig(mode=mode, hint=hint, fixed_order=fixed,
                       w_defer_cap=args.w_defer_cap,
                       deadlock_timeout=args.deadlock_timeout,
                       chaos=chaos, recover=args.recover,
                       hb_deadline=args.hb_deadline,
                       replay=replay, metrics=metrics_reg)

    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=min(20, args.steps),
                          total_steps=max(args.steps, 1))
    params = {"sp": stage_params, "io": io_params}
    mstate = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)
    vstate = jax.tree.map(
        lambda x: jnp.zeros(x.shape, jnp.float32), params)

    apply_update = make_host_update(opt_cfg)

    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if store and args.resume and store.latest_step() is not None:
        start_step = store.latest_step()
        state, _ = store.restore(
            start_step, {"params": params, "m": mstate, "v": vstate})
        params, mstate, vstate = state["params"], state["m"], state["v"]
        print(f"resumed from step {start_step}")

    # The monitor re-synthesizes precommitted tables through the DES engine,
    # whose baseline orders model a fused backward — feed it the fused twin
    # of the spec (same stages/microbatches, W folded into B).
    monitor = StragglerMonitor(
        spec=PipelineSpec(args.stages, args.microbatches),
        costs=CostModel.uniform(args.stages))
    print(f"arch={args.arch} N={cfg.param_count():,} params  runtime=actor "
          f"mode={mode}  hint={hint.value}  split_backward={split}  "
          f"stages={args.stages}  microbatches={args.microbatches}")
    log = RunLog()
    obs_trace = None
    for step in range(start_step, args.steps):
        t0 = time.time()
        batch = synth_batch(cfg, batch_size, args.seq, seed=args.seed,
                            step=step)
        sp, io = params["sp"], params["io"]
        if step == start_step:
            # compile every stage callable before the threads start: a cold
            # full-width compile outlasts the starvation deadline otherwise
            warm_up(fns, [jax.tree.map(lambda x, s=s: x[s], sp)
                          for s in range(args.stages)], io, batch,
                    split_backward=split)
            print(f"warm-up (compile) {time.time() - t0:.1f} s", flush=True)
        programs = [
            ActorStageProgram(
                fns, s, jax.tree.map(lambda x, s=s: x[s], sp), io, batch,
                split_backward=split)
            for s in range(args.stages)
        ]

        def respawn(s, programs=programs, sp=sp, io=io, batch=batch):
            # the stage's in-memory state died with it: rebuild its program
            # from the latest checkpoint (under --ckpt-every 1 that is
            # exactly the params this step started from) or, before the
            # first checkpoint, from the live step-start params
            sp_r, io_r = sp, io
            if store is not None and store.latest_step() is not None:
                host, _ = store.restore_host(
                    store.latest_step(),
                    {"params": {"sp": sp, "io": io}})
                sp_r = jax.tree.map(jnp.asarray, host["params"]["sp"])
                io_r = jax.tree.map(jnp.asarray, host["params"]["io"])
                print(f"recover: stage {s} restored from checkpoint step "
                      f"{store.latest_step()}")
            programs[s] = ActorStageProgram(
                fns, s, jax.tree.map(lambda x: x[s], sp_r), io_r, batch,
                split_backward=split)
            return programs[s]

        # recording costs lock traffic on the dispatch path: enable it only
        # for the step whose trace is actually saved
        record_this = _obs_record_step0(args, step, first=start_step)
        acfg_step = dataclasses.replace(acfg, respawn=respawn) \
            if args.recover else acfg
        if scheduler is not None:
            # iteration-boundary quiesce point: adopt the scheduler's
            # current table (HINT_SWAP events mark mid-run adoptions only)
            acfg_step = dataclasses.replace(
                acfg_step, hint_table=scheduler.table,
                hint_table_version=scheduler.version)
        driver = ActorDriver(
            spec, None,
            dataclasses.replace(acfg_step, record_trace=True) if record_this
            else acfg_step)
        result = driver.run_threaded(programs)
        d_sp = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[p.d_stage for p in programs])
        d_io = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]),
                            *[p.d_io for p in programs])
        grads = jax.tree.map(lambda g: g.astype(jnp.float32),
                             {"sp": d_sp, "io": d_io})
        params, mstate, vstate, lr = apply_update(
            params, grads, mstate, vstate, jnp.asarray(step, jnp.int32))
        # single device sync per step: the programs accumulate the loss as a
        # device array (no float() in the F hot path)
        loss = float(sum(p.loss_acc for p in programs)) / tokens
        jax.block_until_ready(params)
        log.losses.append(loss)
        log.seconds.append(time.time() - t0)
        if record_this:
            trace = driver.trace
            trace.meta["step"] = step
            trace.meta["final_loss"] = loss
            obs_trace = trace
            if args.record_trace:
                trace.save(args.record_trace)
                print(f"recorded step-0 trace ({len(trace.events)} events) "
                      f"-> {args.record_trace}")
        bd = result.breakdown()
        new_table = monitor.observe_result(result)
        swap_note = ""
        if scheduler is not None:
            decision = scheduler.maybe_resynthesize(step)
            if decision.swapped:
                swap_note = (f"  [hint-swap v{scheduler.version} "
                             f"ratio={decision.ratio:.3f}]")
        print(f"step {step:4d}  loss {loss:8.4f}  lr {float(lr):.2e}  "
              f"{log.seconds[-1]*1e3:7.1f} ms  "
              f"makespan {result.makespan*1e3:7.1f} ms  "
              f"blocking {bd['blocking']*1e3:6.1f} ms"
              + ("  [replan]" if new_table is not None else "")
              + swap_note, flush=True)
        if store and (step + 1) % args.ckpt_every == 0:
            store.save(step + 1,
                       {"params": params, "m": mstate, "v": vstate},
                       meta={"arch": args.arch, "step": step + 1})
    if monitor.replans:
        print(f"straggler monitor triggered {monitor.replans} replan(s)")
    if scheduler is not None and scheduler.swaps:
        print(f"adaptive scheduler swapped the hint table "
              f"{len(scheduler.swaps)} time(s) at step(s) {scheduler.swaps} "
              f"(table v{scheduler.version})")
    _obs_finish(args, metrics_reg, obs_trace)
    return log


def main(argv: list[str] | None = None) -> RunLog | None:
    """Parse ``argv`` (default: the command line) and train.  Returns the
    :class:`RunLog` of a language-workload run."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="architecture id (default: deepseek-7b, or "
                         "qwen2-vl-2b for --workload multimodal)")
    ap.add_argument("--devices", type=int, default=None,
                    help="devices in the compiled executor's mesh "
                         "(default: every device JAX finds)")
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--layers", type=int, default=None,
                    help="depth (default: 8 reduced; the published depth "
                         "under --full-size, where a cut keeps whole periods "
                         "of the layer pattern)")
    ap.add_argument("--mb-rows", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--schedule", default="rrfp",
                    choices=list(schedules.BUILDERS))
    ap.add_argument("--runtime", default="table", choices=("table", "actor"),
                    help="table: compiled schedule-table executor (default); "
                         "actor: thread-per-stage readiness-driven runtime")
    ap.add_argument("--workload", default="language",
                    choices=("language", "multimodal"),
                    help="language: linear-chain LM pipeline (default); "
                         "multimodal: branch+fusion DAG pipeline (encoder "
                         "branch || text frontend -> fusion -> LM chain) on "
                         "the actor runtime — archs qwen2-vl-2b / "
                         "seamless-m4t-large-v2")
    ap.add_argument("--substrate", default="thread",
                    choices=("thread", "sim"),
                    help="multimodal workload: thread = real jitted stage "
                         "callables (default); sim = virtual-clock actor "
                         "substrate on the DAG cost model")
    ap.add_argument("--hint", default="bf",
                    choices=[h.value for h in HintKind],
                    help="actor runtime, --schedule rrfp: hint order for "
                         "ready-set arbitration (bfw needs --split-backward)")
    ap.add_argument("--split-backward", action="store_true",
                    help="actor runtime: BFW decomposition — B computes dX "
                         "only, deferrable W tasks accumulate weight grads")
    ap.add_argument("--w-defer-cap", type=int, default=4,
                    help="actor runtime, split backward: max outstanding "
                         "un-executed W tasks per stage (activation-memory "
                         "bound; 0 = unbounded)")
    ap.add_argument("--deadlock-timeout", type=float, default=120.0,
                    help="actor runtime: seconds of stage starvation before "
                         "aborting with DeadlockError")
    ap.add_argument("--chaos", default=None,
                    help="actor runtime: fault-injection spec — a level "
                         "(C0..C3) and/or key=value overrides, e.g. "
                         "'C2' or 'C1,reorder_prob=0.5,straggler=1:2.0'")
    ap.add_argument("--record-trace", default=None, metavar="PATH",
                    help="actor runtime: record the step-0 event trace "
                         "(mailbox/TP-gate/dispatch events with logical "
                         "clocks) to PATH for replay and conformance checks")
    ap.add_argument("--replay-trace", default=None, metavar="PATH",
                    help="actor runtime: re-execute the per-stage dispatch "
                         "order recorded in PATH (order-exact replay; "
                         "reproduces the recorded loss bit pattern)")
    ap.add_argument("--metrics-report", action="store_true",
                    help="actor runtime: collect runtime telemetry "
                         "(repro.obs metrics shards) and print the "
                         "end-of-run per-stage summary table")
    ap.add_argument("--export-perfetto", default=None, metavar="PATH",
                    help="actor runtime: export the step-0 trace as Chrome "
                         "trace-event JSON (open at ui.perfetto.dev); "
                         "implies step-0 recording")
    ap.add_argument("--explain", action="store_true",
                    help="actor runtime: print the one-shot critical-path "
                         "health report of the step-0 trace (binding "
                         "bottleneck, what-if ranking, stragglers, bubble "
                         "cross-check); implies step-0 recording")
    ap.add_argument("--adaptive", action="store_true",
                    help="actor runtime, --schedule rrfp: close the "
                         "schedule loop — accumulate measured per-stage "
                         "timings, re-synthesize the hint table every "
                         "--resynth-every steps, and hot-swap it at the "
                         "iteration boundary when the drift detector fires "
                         "(docs/adaptive.md)")
    ap.add_argument("--resynth-every", type=int, default=1,
                    help="--adaptive: drift-detector cadence in steps")
    ap.add_argument("--swap-threshold", type=float, default=1.03,
                    help="--adaptive: required predicted-makespan "
                         "improvement factor (active/candidate) before a "
                         "check counts toward the swap hysteresis")
    ap.add_argument("--recover", action="store_true",
                    help="actor runtime: treat a fail-stop fault (--chaos "
                         "fail_stage=S[,fail_kind=kill|permanent_stall,"
                         "fail_after=K]) as recoverable — detect the death, "
                         "fence the stale epoch, respawn the stage from the "
                         "latest checkpoint (--ckpt-dir) or live params, and "
                         "replay its in-flight microbatches")
    ap.add_argument("--hb-deadline", type=float, default=2.0,
                    help="actor runtime, --recover: seconds without stage "
                         "progress before a permanent stall is declared dead")
    ap.add_argument("--full-size", action="store_true",
                    help="published widths (default: a reduced toy width "
                         "for CPU runs)")
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=None,
                    help="checkpoint cadence in steps (default 10; under "
                         "--recover default 1, so the respawn path restores "
                         "exactly the params the failed step started from)")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.ckpt_every is None:
        args.ckpt_every = 1 if args.recover else 10
    if args.layers is None and not args.full_size:
        args.layers = 8
    available = jax.device_count()
    if args.devices is None:
        args.devices = available
    if args.devices > available:
        raise SystemExit(
            f"--devices {args.devices}: JAX finds only {available} "
            f"{jax.devices()[0].platform} device(s)")
    use_compile_cache()

    if args.recover and not (args.runtime == "actor"
                             and args.workload == "language"):
        raise SystemExit("--recover drives the thread-per-stage actor "
                         "runtime; add --runtime actor (language workload)")
    if args.adaptive and not (args.runtime == "actor"
                              and args.workload == "language"):
        raise SystemExit("--adaptive drives the thread-per-stage actor "
                         "runtime; add --runtime actor (language workload)")
    if args.workload == "multimodal":
        args.runtime = "actor"  # the DAG only runs on the actor runtime
        train_multimodal(args)
        return None
    if args.arch is None:
        args.arch = "deepseek-7b"
    if args.runtime == "table" and (args.metrics_report or args.export_perfetto
                                    or args.explain):
        raise SystemExit("--metrics-report / --export-perfetto / --explain "
                         "instrument the actor runtime; add --runtime actor "
                         "(or --workload multimodal)")
    data = args.devices // args.stages
    if args.runtime == "table" and data < 1:
        raise SystemExit(f"--stages {args.stages} needs at least as many "
                         f"devices; --devices is {args.devices}")
    cfg = model_config(args)
    if args.runtime == "actor":
        return train_actor(args, cfg)

    t = build_trainer(
        cfg, data=data, stages=args.stages,
        mb_rows=args.mb_rows, microbatches=args.microbatches, seq=args.seq,
        schedule=args.schedule, lr=args.lr, total_steps=args.steps)
    print(f"arch={args.arch} N={cfg.param_count():,} params  "
          f"mesh=({data}×{args.stages})  schedule={args.schedule}  "
          f"bubble={t['table'].bubble_fraction():.2f}", flush=True)

    store = CheckpointStore(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    state = {
        "stage_params": t["stage_params"], "io_params": t["io_params"],
        "opt_state": t["opt_state"],
    }
    if store and args.resume and store.latest_step() is not None:
        start_step = store.latest_step()
        state, meta = store.restore(start_step, state)
        print(f"resumed from step {start_step}")

    monitor = StragglerMonitor(
        spec=t["spec"],
        costs=CostModel.uniform(args.stages))

    def make(step):
        return synth_batch(t["cfg"], t["batch_size"], t["seq"],
                           seed=args.seed, step=step)

    it = PrefetchIterator(make, start_step=start_step)
    sp, io, opt = (state["stage_params"], state["io_params"],
                   state["opt_state"])
    log = RunLog()
    try:
        for _ in range(args.steps - start_step):
            step, batch = next(it)
            t0 = time.time()
            sp, io, opt, m = t["train_step"](
                sp, io, opt, batch, jnp.asarray(step, jnp.int32))
            jax.block_until_ready((sp, io, opt, m))
            log.losses.append(float(m["loss"]))
            log.seconds.append(time.time() - t0)
            print(f"step {step:4d}  loss {log.losses[-1]:8.4f}  gnorm "
                  f"{float(m['gnorm']):7.3f}  lr {float(m['lr']):.2e}  "
                  f"{log.seconds[-1]*1e3:7.1f} ms", flush=True)
            if store and (step + 1) % args.ckpt_every == 0:
                store.save(step + 1,
                           {"stage_params": sp, "io_params": io,
                            "opt_state": opt},
                           meta={"arch": args.arch, "step": step + 1},
                           asynchronous=True)
        if store:
            store.wait()
    finally:
        it.close()
    return log


if __name__ == "__main__":
    main()

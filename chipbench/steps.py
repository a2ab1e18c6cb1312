"""The training step that the window drives, one class per runtime.

Compiled executor: ``launch.train.build_trainer(...)["train_step"]``, called
exactly as ``train.main`` loops it.  Actor runtime: the program has no step
entry, so ``ActorStep`` calls the pieces ``train.train_actor`` calls, in its
order: ``StageFns``, ``warm_up``, one ``ActorStageProgram`` per stage,
``ActorDriver.run_threaded``, the gradient stack, ``make_host_update``.
A test holds both to ``train.main``'s losses bit for bit.

Each piece runs inside a ``jax.profiler.TraceAnnotation`` named in SPANS, so
that device idle time in a trace can be laid to the host work open during it.
Both classes share one interface: ``warm(batch)``, ``step(i, batch) -> loss``,
and for the check ``params_now()`` and ``first_moment()`` as (stage, io)
trees, then ``free()``.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.hints import HintKind
from repro.core.taskgraph import PipelineSpec
from repro.models.build import build
from repro.optim.adamw import AdamWConfig, make_host_update, make_optimizer
from repro.pipeline.stagefn import (ActorStageProgram, StageFnOptions,
                                    StageFns, warm_up)
from repro.runtime.rrfp import ActorConfig, ActorDriver

from chipbench import weights

SPANS = ("step", "batch", "programs", "run_threaded", "grad_stack",
         "host_update", "loss_read", "train_step")


def opt_config(conf: dict) -> AdamWConfig:
    """The optimizer ``build_trainer`` builds: 20 warm-up steps over 1000."""
    return AdamWConfig(lr=conf["learning_rate"], warmup_steps=20,
                       total_steps=1000)


class ActorStep:
    """One step of ``train.train_actor``'s loop, with its state kept here."""

    runtime = "actor"

    def __init__(self, cfg, cell: dict, conf: dict, key, params=None):
        S, M = cell["stages"], cell["microbatches"]
        self.cell = cell
        self.spans = Spans()
        self.model = model = build(cfg, num_stages=S)
        self.spec = PipelineSpec(S, M, split_backward=False)
        self.tokens = M * cell["mb_rows"] * cell["seq"]
        self.fns = StageFns(model, StageFnOptions(
            mb_rows=cell["mb_rows"], seq_len=cell["seq"],
            loss_scale=1.0 / self.tokens))
        if cell["schedule"] == "rrfp":
            mode, fixed = "hint", "1f1b"
        else:
            mode, fixed = "precommitted", cell["schedule"]
        self.acfg = ActorConfig(mode=mode, hint=HintKind(cell["hint"]),
                                fixed_order=fixed, w_defer_cap=4,
                                deadlock_timeout=120.0)
        self.init = make_param_init(model)
        if params is None:
            params = self.init(key)
        self.params = {"sp": params[0], "io": params[1]}
        zeros = jax.jit(lambda t: jax.tree.map(
            lambda x: jnp.zeros(x.shape, jnp.float32), t))
        self.m = zeros(self.params)
        self.v = zeros(self.params)
        self.apply_update = make_host_update(opt_config(conf))
        self.results = []  # the ActorDriver's RunResult of each step

    def _stage(self, tree, s):
        return jax.tree.map(lambda x: x[s], tree)

    def warm(self, batch: dict) -> None:
        sp, io = self.params["sp"], self.params["io"]
        warm_up(self.fns, [self._stage(sp, s)
                           for s in range(self.spec.num_stages)], io, batch,
                split_backward=False)

    def programs(self, batch: dict) -> list:
        sp, io = self.params["sp"], self.params["io"]
        return [ActorStageProgram(self.fns, s, self._stage(sp, s), io, batch,
                                  split_backward=False)
                for s in range(self.spec.num_stages)]

    def step(self, i: int, batch: dict) -> float:
        with self.spans("programs"):
            programs = self.programs(batch)
        with self.spans("run_threaded"):
            result = ActorDriver(self.spec, None, self.acfg).run_threaded(
                programs)
        with self.spans("grad_stack"):
            d_sp = jax.tree.map(lambda *xs: jnp.stack(xs),
                                *[p.d_stage for p in programs])
            d_io = jax.tree.map(lambda *xs: sum(xs[1:], xs[0]),
                                *[p.d_io for p in programs])
            grads = jax.tree.map(lambda g: g.astype(jnp.float32),
                                 {"sp": d_sp, "io": d_io})
        with self.spans("host_update"):
            self.params, self.m, self.v, _ = self.apply_update(
                self.params, grads, self.m, self.v,
                jnp.asarray(i, jnp.int32))
        with self.spans("loss_read"):
            loss = float(sum(p.loss_acc for p in programs)) / self.tokens
            jax.block_until_ready(self.params)
        self.results.append(result)
        return loss

    def params_now(self):
        """(stage, io) parameters as the next step starts from them."""
        return self.params["sp"], self.params["io"]

    def first_moment(self):
        """AdamW's m as (stage, io) trees shaped like the parameters."""
        return self.m["sp"], self.m["io"]

    def free(self) -> None:
        self.params = self.m = self.v = None
        self.results.clear()


class TableStep:
    """``build_trainer(...)["train_step"]``, driven as ``train.main`` does."""

    runtime = "table"

    def __init__(self, cfg, cell: dict, conf: dict, key, params=None):
        from repro.launch.train import build_trainer

        t = build_trainer(cfg, data=1, stages=cell["stages"],
                          mb_rows=cell["mb_rows"],
                          microbatches=cell["microbatches"], seq=cell["seq"],
                          schedule=cell["schedule"],
                          lr=conf["learning_rate"], total_steps=1000)
        self.cell = cell
        self.spans = Spans()
        self.model, self.mesh = t["model"], t["mesh"]
        self.partition = t["partition"]
        self.train_step = t["train_step"]
        self.tokens = t["batch_size"] * cell["seq"]
        # build_trainer starts from its own fixed key: put the seed's weights
        # in their places, and the optimizer state that follows from them
        sp_sh = jax.tree.map(lambda x: x.sharding, t["stage_params"])
        io_sh = jax.tree.map(lambda x: x.sharding, t["io_params"])
        del t
        if params is None:
            self.init = make_param_init(self.model, (sp_sh, io_sh))
            params = self.init(key)
        else:
            self.init = None
            params = (jax.device_put(params[0], sp_sh),
                      jax.device_put(params[1], io_sh))
        opt_init, _ = make_optimizer(self.model, self.mesh, self.partition,
                                     opt_config(conf))
        self.sp, self.io = params
        self.opt = jax.jit(opt_init)(self.sp, self.io)

    def warm(self, batch: dict) -> None:
        """``train_step`` compiles at its first call, a checked step."""

    def step(self, i: int, batch: dict) -> float:
        with self.spans("train_step"):
            self.sp, self.io, self.opt, m = self.train_step(
                self.sp, self.io, self.opt, batch, jnp.asarray(i, jnp.int32))
        with self.spans("loss_read"):
            jax.block_until_ready((self.sp, self.io, self.opt, m))
            loss = float(m["loss"])
        return loss

    def _from_shards(self, field: str):
        """An optimizer-state field as (stage, io) trees shaped like the
        parameters (ZeRO-1 keeps each leaf flattened and padded)."""
        shards = self.opt["shards"]
        S = self.model.num_stages

        def unflat(key, like_shape):
            flat = shards[key][field]  # [S, padded]
            n = math.prod(like_shape)
            return flat[:, :n].reshape((S,) + tuple(like_shape))

        sp = jax.tree_util.tree_map_with_path(
            lambda p, x: unflat(jax.tree_util.keystr(p), x.shape[1:]),
            self.sp)
        io = jax.tree_util.tree_map_with_path(
            lambda p, x: unflat("io:" + jax.tree_util.keystr(p),
                                x.shape)[0], self.io)
        return sp, io

    def params_now(self):
        """The float32 master weights, which the next step starts from."""
        return self._from_shards("master")

    def first_moment(self):
        return self._from_shards("m")

    def free(self) -> None:
        self.sp = self.io = self.opt = None


def make_param_init(model, shardings=None):
    """``init(key) -> (stage_params, io_params)`` in the program's layout."""
    key = jax.random.key(0)
    shapes = jax.eval_shape(lambda k: (model.init_stage_params(k),
                                       model.init_io_params(k)), key)
    return weights.make_init(shapes, shardings)


STEPS = {"actor": ActorStep, "table": TableStep}


@dataclasses.dataclass
class Timer:
    """Host seconds of named set-up phases, in order."""
    start: float = dataclasses.field(default_factory=time.perf_counter)
    parts: dict = dataclasses.field(default_factory=dict)

    def lap(self, name: str) -> None:
        now = time.perf_counter()
        self.parts[name] = self.parts.get(name, 0.0) + now - self.start
        self.start = now


class Spans:
    """Host spans: each both a ``TraceAnnotation`` in the profiler's trace
    and a running total of host seconds by name."""

    def __init__(self):
        self.seconds = collections.defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        t = time.perf_counter()
        with TraceAnnotation(name):
            yield
        self.seconds[name].append(time.perf_counter() - t)

    def reset(self) -> None:
        self.seconds.clear()

    def totals(self) -> dict:
        return {k: list(v) for k, v in self.seconds.items()}

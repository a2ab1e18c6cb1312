"""Standalone per-stage jitted callables (factored out of the executor).

``pipeline.executor`` compiles the *whole* schedule into one SPMD program —
every stage steps in lockstep through a tick grid.  The actor runtime needs
the opposite factoring: one independently-callable, jitted function per
(stage, op) that a host thread can dispatch the moment the stage's message
arrives.  This module provides that factoring for single-process meshes
(CPU or multi-device single-host), sharing the executor's loss
(:func:`chunked_ce_sum`) and its remat-based backward recipe: B re-runs the
stage forward under ``jax.grad`` of a scalarized objective (CE at the last
stage, <y, g_in> elsewhere).

``ActorStageProgram`` adapts the callables to the actor runtime's
``work_fn(task, payload)`` protocol: it holds the stage's residual store
(per-microbatch forward inputs) and gradient accumulators, consumes arrived
activations/gradients as message payloads, and emits the outgoing payload.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.taskgraph import Kind, Task
from repro.models.build import ArchModel
from repro.models.layers import rmsnorm
from repro.obs.spans import span


@dataclasses.dataclass(frozen=True)
class StageFnOptions:
    mb_rows: int             # microbatch rows
    seq_len: int             # tokens per row
    ce_chunk: int = 0        # 0 -> auto from vocab size
    loss_scale: float = 1.0  # applied to the backward seed


def default_ce_chunk(cfg, requested: int = 0) -> int:
    if requested:
        return requested
    v = cfg.padded_vocab()
    return max(64, min(2048, (1 << 24) // v * 4))


# ---------------------------------------------------------------------------
# loss (shared with the executor)
# ---------------------------------------------------------------------------
@jax.named_scope("ce_loss")
def chunked_ce_sum(model: ArchModel, io, y, labels, chunk: int):
    """Sum of token cross-entropies, scanned over token chunks (bounded
    logits working set; checkpointed so backward re-materializes per chunk)."""
    cfg = model.cfg
    h = rmsnorm(y, io["final_ln"], cfg.norm_eps)
    d = h.shape[-1]
    h2 = h.reshape(-1, d)
    l2 = labels.reshape(-1)
    n = h2.shape[0]
    pad = (-n) % chunk
    if pad:
        h2 = jnp.pad(h2, ((0, pad), (0, 0)))
        l2 = jnp.pad(l2, (0, pad), constant_values=-1)
    h3 = h2.reshape(-1, chunk, d)
    l3 = l2.reshape(-1, chunk)
    head = io["head"]

    @jax.checkpoint
    def body(carry, inp):
        h_c, l_c = inp
        logits = (h_c @ head.T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        pick = jnp.take_along_axis(
            logits, jnp.maximum(l_c, 0)[:, None], axis=1)[:, 0]
        w = (l_c >= 0).astype(jnp.float32)
        return carry + jnp.sum((lse - pick) * w), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (h3, l3))
    return total


# ---------------------------------------------------------------------------
# per-stage callables
# ---------------------------------------------------------------------------
def _jit(fn, name: str):
    """``jax.jit`` under ``name``: the lowered module, and so the device
    trace's module line, reads ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


class StageFns:
    """Jitted forward/backward per stage of a single-process pipeline.

    ``forward(s)(sp_s, io, x, bm) -> (y, loss_sum)`` — loss_sum nonzero only
    at the last stage.  ``backward(s)(sp_s, io, x, g_in, bm) ->
    (dx, d_stage, d_io)`` — g_in ignored at the last stage (the loss is the
    objective there).

    Under BFW decomposition the fused backward splits into two jitted
    callables over the *same* scalarized objective:

    * ``backward_dx(s)(sp_s, io, x, g_in, bm) -> dx`` — the dX-only B task
      (``argnums=(2,)``), on the critical inter-stage path;
    * ``weight_grad(s)(sp_s, io, x, g_in, bm) -> (d_stage, d_io)`` — the
      deferrable per-microbatch W task (``argnums=(0, 1)``), stage-local.

    Each callable is jitted under its stage and op, so its module is
    ``jit_stage{s}_F``, ``jit_stage{s}_B``, ``jit_stage{s}_dX`` or
    ``jit_stage{s}_W``; inside, ops carry the scopes ``embed``, ``layers``
    and ``ce_loss``, and a backward's re-run forward sits under
    ``recompute``.
    """

    def __init__(self, model: ArchModel, opts: StageFnOptions):
        self.model = model
        self.opts = opts
        cfg = model.cfg
        self.ce_chunk = default_ce_chunk(cfg, opts.ce_chunk)
        self._fwd: dict[int, Any] = {}
        self._bwd: dict[int, Any] = {}
        self._bwd_dx: dict[int, Any] = {}
        self._wgrad: dict[int, Any] = {}

    # ---- helpers -------------------------------------------------------
    def _aux(self, bm: dict) -> dict:
        seq = self.opts.seq_len
        a: dict[str, Any] = {
            "positions": jnp.broadcast_to(
                jnp.arange(seq, dtype=jnp.int32)[None],
                (self.opts.mb_rows, seq)),
            "data_size": 1,
            "moe_layout": "none",  # single process: experts computed locally
        }
        if "mrope" in bm:
            a["mrope"] = bm["mrope"]
        return a

    def _embed(self, io, bm: dict):
        cfg = self.model.cfg
        if cfg.embed_input:
            return bm["embeds"].astype(cfg.dtype)
        return io["embed"][bm["tokens"]]

    def _stage_out(self, stage: int, sp_s, io, x, bm):
        """The stage's layers on its input (the embedded tokens at stage 0)."""
        model, cfg = self.model, self.model.cfg
        if stage == 0:
            with jax.named_scope("embed"):
                x = self._embed(io, bm).astype(cfg.dtype)
        with jax.named_scope("layers"):
            return model.stage_forward(sp_s, io, x, self._aux(bm),
                                       model.rows(stage))

    def _objective(self, stage: int, sp_s, io, x, g_in, bm):
        model = self.model
        with jax.named_scope("recompute"):
            y = self._stage_out(stage, sp_s, io, x, bm)
            if stage == model.num_stages - 1:
                return chunked_ce_sum(model, io, y, bm["labels"],
                                      self.ce_chunk) * self.opts.loss_scale
            return jnp.sum(y.astype(jnp.float32) * g_in.astype(jnp.float32))

    # ---- public --------------------------------------------------------
    def forward(self, stage: int):
        if stage not in self._fwd:
            model = self.model
            last = stage == model.num_stages - 1

            def f(sp_s, io, x, bm):
                y = self._stage_out(stage, sp_s, io, x, bm)
                loss = (chunked_ce_sum(model, io, y, bm["labels"],
                                       self.ce_chunk)
                        if last else jnp.zeros((), jnp.float32))
                return y, loss

            self._fwd[stage] = _jit(f, f"stage{stage}_F")
        return self._fwd[stage]

    def backward(self, stage: int):
        if stage not in self._bwd:
            def b(sp_s, io, x, g_in, bm):
                dsp, dio, dx = jax.grad(
                    lambda sp_, io_, x_: self._objective(
                        stage, sp_, io_, x_, g_in, bm),
                    argnums=(0, 1, 2))(sp_s, io, x)
                return dx, dsp, dio

            self._bwd[stage] = _jit(b, f"stage{stage}_B")
        return self._bwd[stage]

    def backward_dx(self, stage: int):
        """dX-only backward (the B task of the BFW decomposition)."""
        if stage not in self._bwd_dx:
            def b_dx(sp_s, io, x, g_in, bm):
                (dx,) = jax.grad(
                    lambda x_: self._objective(
                        stage, sp_s, io, x_, g_in, bm),
                    argnums=(0,))(x)
                return dx

            self._bwd_dx[stage] = _jit(b_dx, f"stage{stage}_dX")
        return self._bwd_dx[stage]

    def weight_grad(self, stage: int):
        """Per-microbatch weight gradient (the deferrable W task)."""
        if stage not in self._wgrad:
            def w(sp_s, io, x, g_in, bm):
                dsp, dio = jax.grad(
                    lambda sp_, io_: self._objective(
                        stage, sp_, io_, x, g_in, bm),
                    argnums=(0, 1))(sp_s, io)
                return dsp, dio

            self._wgrad[stage] = _jit(w, f"stage{stage}_W")
        return self._wgrad[stage]


def microbatch(batch: dict, mb: int, mb_rows: int) -> dict:
    """Host-side microbatch slice of a [M*mb_rows, ...] batch dict."""
    lo, hi = mb * mb_rows, (mb + 1) * mb_rows
    out = {}
    for k, v in batch.items():
        if k == "mrope":
            out[k] = v[:, lo:hi]
        else:
            out[k] = v[lo:hi]
    return out


# ---------------------------------------------------------------------------
# actor-runtime adapter
# ---------------------------------------------------------------------------
class ActorStageProgram:
    """``work_fn(task, payload)`` for one stage actor driving real callables.

    F: consume the upstream activation payload (None at stage 0), run the
    jitted forward, stash the stage input for remat-backward, emit y.
    B (fused): consume the downstream gradient payload (None at the last
    stage), re-run forward under grad, accumulate parameter grads, emit dx.

    With ``split_backward=True`` (the BFW decomposition):

    B: run the dX-only backward, stash the (x, g_in) pair for the W task,
    emit dx.  Stage 0 skips the dX computation entirely — no stage consumes
    its input gradient.
    W: consume the stashed pair, run the weight-grad callable, accumulate
    ``d_stage``/``d_io``.  W emits no payload: its result is stage-local
    (``PipelineSpec.message_successor`` is None for W, so no envelope is
    ever sent and no TP admission gate applies).

    The running loss is accumulated as a device array — reading
    ``loss_sum`` materializes it (one sync), so the F hot path never blocks
    on the device.

    With ``deterministic_reduction=True`` the per-microbatch loss and grad
    contributions are *stashed* instead of folded in eagerly, and
    :meth:`finalize` sums them in microbatch order.  Floating-point addition
    is not associative, so the default eager accumulation is bit-sensitive
    to the runtime's dispatch order; the deterministic mode makes the final
    loss and gradients bitwise identical across any execution order of the
    same task set — the property the conformance suite checks between
    chaotic actor runs and the fixed-order reference executor.

    Profiler spans: ``stage.init`` around the zeroed accumulators, and
    ``stage.accumulate`` around each microbatch's grad and loss adds.
    """

    def __init__(self, fns: StageFns, stage: int, sp_s, io, batch: dict,
                 *, split_backward: bool = False,
                 deterministic_reduction: bool = False):
        self.fns = fns
        self.stage = stage
        self.sp_s = sp_s
        self.io = io
        self.batch = batch
        self.split_backward = split_backward
        self.deterministic_reduction = deterministic_reduction
        self.residual: dict[int, Any] = {}  # mb -> stage input
        #: BFW: mb -> (x, g_in) held from B-time until the W task fires
        self.w_pending: dict[int, tuple[Any, Any]] = {}
        self.w_high_water = 0  # max outstanding W stashes (memory bound)
        with span("stage.init", stage=stage):
            self.d_stage = jax.tree.map(jnp.zeros_like, sp_s)
            self.d_io = jax.tree.map(jnp.zeros_like, io)
            self.loss_acc = jnp.zeros((), jnp.float32)
        #: deterministic mode: mb -> stashed contributions, folded by finalize
        self._mb_loss: dict[int, Any] = {}
        self._mb_grads: dict[int, tuple[Any, Any]] = {}
        #: highest microbatch already folded — guards against mid-run folds
        self._loss_folded: int | None = None
        self._grads_folded: int | None = None
        self._g_dummy = None

    def _add_grads(self, mb: int, dsp, dio) -> None:
        if self.deterministic_reduction:
            self._mb_grads[mb] = (dsp, dio)
            return
        with span("stage.accumulate", stage=self.stage):
            self.d_stage = jax.tree.map(jnp.add, self.d_stage, dsp)
            self.d_io = jax.tree.map(jnp.add, self.d_io, dio)

    def finalize(self) -> "ActorStageProgram":
        """Fold stashed per-microbatch contributions in microbatch order.

        Idempotent; a no-op under eager accumulation.  Must run only after
        all of the stage's work has executed: a *partial* fold would fix the
        already-seen microbatches' position in the reduction order, making
        the final bits depend on when the read happened — so folding a
        microbatch below an already-folded one raises instead of silently
        breaking the bitwise order-independence guarantee.
        """
        def fold_guard(kind: str, folded: int | None, keys) -> int | None:
            if folded is not None and keys and min(keys) < folded:
                raise RuntimeError(
                    f"stage {self.stage}: deterministic {kind} fold of "
                    f"microbatch {min(keys)} after microbatch {folded} was "
                    f"already folded — finalize()/loss_sum was read mid-run")
            return max(keys, default=folded) if keys else folded

        self._loss_folded = fold_guard(
            "loss", self._loss_folded, list(self._mb_loss))
        for mb in sorted(self._mb_loss):
            self.loss_acc = self.loss_acc + self._mb_loss[mb]
        self._mb_loss.clear()
        self._grads_folded = fold_guard(
            "grad", self._grads_folded, list(self._mb_grads))
        for mb in sorted(self._mb_grads):
            dsp, dio = self._mb_grads[mb]
            self.d_stage = jax.tree.map(jnp.add, self.d_stage, dsp)
            self.d_io = jax.tree.map(jnp.add, self.d_io, dio)
        self._mb_grads.clear()
        return self

    @property
    def loss_sum(self) -> float:
        """Materialized loss total (forces one device sync per read)."""
        self.finalize()
        return float(self.loss_acc)

    def w_outstanding(self) -> int:
        """Un-executed W tasks currently holding activation memory."""
        return len(self.w_pending)

    def __call__(self, task: Task, payload: Any) -> Any:
        bm = microbatch(self.batch, task.mb, self.fns.opts.mb_rows)
        if task.kind == Kind.F:
            x = payload  # None at stage 0 (embedded inside the callable)
            y, loss = self.fns.forward(self.stage)(
                self.sp_s, self.io, x, bm)
            self.residual[task.mb] = x
            if self.deterministic_reduction:
                self._mb_loss[task.mb] = loss
            else:
                with span("stage.accumulate", stage=self.stage):
                    self.loss_acc = self.loss_acc + loss
            self._g_dummy = jnp.zeros_like(y)
            return y
        if task.kind == Kind.B:
            x = self.residual.pop(task.mb)
            g_in = payload if payload is not None else self._g_dummy
            if self.split_backward:
                self.w_pending[task.mb] = (x, g_in)
                self.w_high_water = max(self.w_high_water,
                                        len(self.w_pending))
                if self.stage == 0:
                    return None  # nobody consumes stage 0's input gradient
                return self.fns.backward_dx(self.stage)(
                    self.sp_s, self.io, x, g_in, bm)
            dx, dsp, dio = self.fns.backward(self.stage)(
                self.sp_s, self.io, x, g_in, bm)
            self._add_grads(task.mb, dsp, dio)
            return dx
        if task.kind == Kind.W:
            if not self.split_backward:
                raise ValueError(
                    f"{task!r} dispatched to a fused-backward stage program "
                    f"(construct ActorStageProgram with split_backward=True)")
            x, g_in = self.w_pending.pop(task.mb)
            dsp, dio = self.fns.weight_grad(self.stage)(
                self.sp_s, self.io, x, g_in, bm)
            self._add_grads(task.mb, dsp, dio)
            return None  # stage-local: no outgoing envelope
        raise ValueError(f"actor stage program cannot run {task!r}")


def warm_up(fns: StageFns, stage_params: list, io, batch: dict, *,
            split_backward: bool = False) -> None:
    """Compile every stage's callables before threads dispatch them.

    Runs microbatch 0 through throwaway programs in pipeline order: F down
    the chain, then B (and W under split backward) back up — the same calls,
    with the same shapes, that the first threaded step makes.  A cold compile
    at full width can outlast the actor runtime's starvation deadline, which
    would then report a deadlock that is only a compilation.
    """
    progs = [ActorStageProgram(fns, s, sp_s, io, batch,
                               split_backward=split_backward)
             for s, sp_s in enumerate(stage_params)]
    payload = None
    for s, prog in enumerate(progs):
        payload = prog(Task(Kind.F, s, 0), payload)
    payload = None
    for s in reversed(range(len(progs))):
        payload = progs[s](Task(Kind.B, s, 0), payload)
        if split_backward:
            progs[s](Task(Kind.W, s, 0), None)
    jax.block_until_ready([(p.d_stage, p.d_io) for p in progs])

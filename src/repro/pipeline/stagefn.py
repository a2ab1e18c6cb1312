"""Standalone per-stage jitted callables (factored out of the executor).

``pipeline.executor`` compiles the *whole* schedule into one SPMD program —
every stage steps in lockstep through a tick grid.  The actor runtime needs
the opposite factoring: one independently-callable, jitted function per
(stage, op) that a host thread can dispatch the moment the stage's message
arrives.  This module provides that factoring for single-process meshes
(CPU or multi-device single-host), sharing the executor's loss
(:func:`chunked_ce_sum`) and its remat-based backward recipe: B re-runs the
stage forward under ``jax.grad`` of a scalarized objective (the loss at the
last stage, <y, g_in> elsewhere).

One builder serves chains and DAGs.  ``StageFns`` implements the model hooks
for an ``ArchModel`` chain; ``multimodal.stagefn`` overrides them for the
branch+fusion DAG, whose stages share no ``io`` tree (``io = {}``).

``ActorStageProgram`` adapts the callables to the actor runtime's
``work_fn(task, payload)`` protocol: it holds the stage's residual store
(per-microbatch forward inputs) and gradient accumulators, consumes arrived
activations/gradients as message payloads, and emits the outgoing payload.
Its backward callables take the accumulators as a donated argument and fold
each microbatch's gradients into them inside the same jitted program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.taskgraph import Kind, StageGraph, Task
from repro.models.build import ArchModel
from repro.models.layers import rmsnorm
from repro.obs.spans import span
from repro.runtime.rrfp.messages import EdgePayloads, payload_for_edge


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
def _jit(fn, name: str, **jit_kwargs):
    """``jax.jit`` under ``name``: the lowered module, and so the device
    trace's module line, reads ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kwargs)


def _fold(acc, grads):
    """``acc + grads`` leaf by leaf, each in its accumulator's dtype.  Inside
    a jitted backward XLA can fuse the add into the gradient's producer (the
    embedding's scatter-add lands in the accumulator itself), and a gradient
    that is identically zero (an ``io`` leaf the stage never reads) leaves
    its donated accumulator in place, unwritten."""
    return jax.tree.map(jnp.add, acc, grads)


@jax.custom_vjp
def embed_rows(table, tokens):
    """``table[tokens]``, whose backward sums each distinct token's rows in
    float32 and adds them to the table's gradient once.

    The plain gather's backward scatter-adds every position's row in the
    table's dtype, one rounding per position; folded into an accumulator
    (``StageFns.backward_into``) the scatter lands in the accumulator
    itself, where a frequent token's rows would round at the magnitude of
    the whole step's sum and the small ones would be lost in bf16."""
    return table[tokens]


def _embed_rows_fwd(table, tokens):
    return table[tokens], (table, tokens)


def _embed_rows_bwd(res, ct):
    table, tokens = res
    flat = tokens.reshape(-1)
    n, vocab = flat.shape[0], table.shape[0]
    rows, slot = jnp.unique(flat, size=n, fill_value=vocab,
                            return_inverse=True)
    sums = jax.ops.segment_sum(
        ct.reshape(n, -1).astype(jnp.float32), slot.reshape(-1),
        num_segments=n)
    # the slots past the distinct tokens hold row ``vocab``: dropped
    return (jnp.zeros_like(table).at[rows].add(
        sums.astype(table.dtype), mode="drop", indices_are_sorted=True),
        None)


embed_rows.defvjp(_embed_rows_fwd, _embed_rows_bwd)


def _zero_accumulators(sp_s, io):
    """``((d_stage, d_io), loss_acc)``: zeroed gradient accumulators in each
    parameter's dtype and a float32 loss, built in one program."""
    return ((jax.tree.map(jnp.zeros_like, sp_s),
             jax.tree.map(jnp.zeros_like, io)), jnp.zeros((), jnp.float32))


zero_accumulators = _jit(_zero_accumulators, "stage_init")


class StageFns:
    """Jitted forward/backward per stage of a single-process pipeline.

    Every stage has one calling convention.  ``x`` is the stage's
    differentiable input, the arrived payload: an array on a single-edge
    stage, a ``{src_stage: array}`` dict at a fan-in stage, None at a source
    stage (chain stage 0, which embeds ``bm``'s tokens).  Everything else a
    task reads is in ``bm``, the microbatch's slice of the batch.

    ``forward(s)(sp_s, io, x, bm) -> (y, loss_sum)`` — loss_sum nonzero only
    at the last stage.  ``backward(s)(sp_s, io, x, g_in, bm) ->
    (dx, d_stage, d_io)`` — g_in ignored at the last stage (the loss is the
    objective there); dx has ``x``'s structure.

    Under BFW decomposition the fused backward splits into two jitted
    callables over the *same* scalarized objective:

    * ``backward_dx(s)(sp_s, io, x, g_in, bm) -> dx`` — the dX-only B task
      (``argnums=(2,)``), on the critical inter-stage path;
    * ``weight_grad(s)(sp_s, io, x, g_in, bm) -> (d_stage, d_io)`` — the
      deferrable per-microbatch W task (``argnums=(0, 1)``), stage-local.

    The accumulating forms fold the gradients into ``acc``, the
    ``(d_stage, d_io)`` pair, which they take donated, in the same program:

    * ``backward_into(s)(acc, sp_s, io, x, g_in, bm) -> (dx, acc + grads)``;
    * ``weight_grad_into(s)(acc, sp_s, io, x, g_in, bm) -> acc + grads``.

    Each callable is jitted under its stage and op, so its module is
    ``jit_stage{s}_F``, ``jit_stage{s}_B``, ``jit_stage{s}_dX`` or
    ``jit_stage{s}_W`` (an accumulating form under its plain form's name);
    inside, ops carry the scopes ``embed``, ``layers`` and ``ce_loss``, and a
    backward's re-run forward sits under ``recompute``.

    Model hooks, overridden for another model: ``_stage_out``,
    ``_stage_loss`` (unscaled, last stage), ``microbatch`` (a stage's
    ``bm``), and for ``warm_up`` ``stage_graph`` and ``warm_microbatches``.
    """

    def __init__(self, model, opts: StageFnOptions):
        self.model = model
        self.opts = opts
        self._jitted: dict[tuple[str, int], Any] = {}  # by (op, stage)

    @functools.cached_property
    def ce_chunk(self) -> int:
        return default_ce_chunk(self.model.cfg, self.opts.ce_chunk)

    # ---- model hooks: an ArchModel chain -------------------------------
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
        return embed_rows(io["embed"], bm["tokens"])

    def _stage_out(self, stage: int, sp_s, io, x, bm):
        """The stage's layers on its input (the embedded tokens at stage 0)."""
        model, cfg = self.model, self.model.cfg
        if stage == 0:
            with jax.named_scope("embed"):
                x = self._embed(io, bm).astype(cfg.dtype)
        with jax.named_scope("layers"):
            return model.stage_forward(sp_s, io, x, self._aux(bm),
                                       model.rows(stage))

    def _stage_loss(self, stage: int, sp_s, io, y, bm):
        """Unscaled token cross-entropy sum of the last stage's output."""
        return chunked_ce_sum(self.model, io, y, bm["labels"], self.ce_chunk)

    def microbatch(self, batch: dict, mb: int, stage: int) -> dict:
        """Stage ``stage``'s ``bm`` for microbatch ``mb``."""
        return microbatch(batch, mb, self.opts.mb_rows)

    def stage_graph(self) -> StageGraph:
        """The stages' forward edges (``warm_up`` routes payloads on them)."""
        return StageGraph.linear(self.model.num_stages)

    def warm_microbatches(self, batch: dict) -> list[int]:
        """One microbatch of each set of shapes in ``batch``: all alike."""
        return [0]

    # ---- objective -----------------------------------------------------
    def _objective(self, stage: int, sp_s, io, x, g_in, bm):
        with jax.named_scope("recompute"):
            y = self._stage_out(stage, sp_s, io, x, bm)
            if stage == self.model.num_stages - 1:
                return self._stage_loss(stage, sp_s, io, y,
                                        bm) * self.opts.loss_scale
            return jnp.sum(y.astype(jnp.float32) * g_in.astype(jnp.float32))

    def _fused_grads(self, stage: int, sp_s, io, x, g_in, bm):
        """``(dx, d_stage, d_io)`` of the stage's objective."""
        dsp, dio, dx = jax.grad(
            lambda sp_, io_, x_: self._objective(
                stage, sp_, io_, x_, g_in, bm),
            argnums=(0, 1, 2))(sp_s, io, x)
        return dx, dsp, dio

    def _weight_grads(self, stage: int, sp_s, io, x, g_in, bm):
        """``(d_stage, d_io)`` of the stage's objective."""
        return jax.grad(
            lambda sp_, io_: self._objective(stage, sp_, io_, x, g_in, bm),
            argnums=(0, 1))(sp_s, io)

    def _once(self, op: str, stage: int, suffix: str, fn, **jit_kwargs):
        """``fn`` jitted as ``jit_stage{stage}_{suffix}``, once per op."""
        key = (op, stage)
        if key not in self._jitted:
            self._jitted[key] = _jit(fn, f"stage{stage}_{suffix}",
                                     **jit_kwargs)
        return self._jitted[key]

    # ---- public --------------------------------------------------------
    def forward(self, stage: int):
        last = stage == self.model.num_stages - 1

        def f(sp_s, io, x, bm):
            y = self._stage_out(stage, sp_s, io, x, bm)
            loss = (self._stage_loss(stage, sp_s, io, y, bm)
                    if last else jnp.zeros((), jnp.float32))
            return y, loss

        return self._once("forward", stage, "F", f)

    def backward(self, stage: int):
        def b(sp_s, io, x, g_in, bm):
            return self._fused_grads(stage, sp_s, io, x, g_in, bm)

        return self._once("backward", stage, "B", b)

    def backward_into(self, stage: int):
        """Fused backward that folds its grads into the donated ``acc``."""
        def b(acc, sp_s, io, x, g_in, bm):
            dx, dsp, dio = self._fused_grads(stage, sp_s, io, x, g_in, bm)
            return dx, _fold(acc, (dsp, dio))

        return self._once("backward_into", stage, "B", b, donate_argnums=0)

    def backward_dx(self, stage: int):
        """dX-only backward (the B task of the BFW decomposition)."""
        def b_dx(sp_s, io, x, g_in, bm):
            (dx,) = jax.grad(
                lambda x_: self._objective(stage, sp_s, io, x_, g_in, bm),
                argnums=(0,))(x)
            return dx

        return self._once("backward_dx", stage, "dX", b_dx)

    def weight_grad(self, stage: int):
        """Per-microbatch weight gradient (the deferrable W task)."""
        def w(sp_s, io, x, g_in, bm):
            return self._weight_grads(stage, sp_s, io, x, g_in, bm)

        return self._once("weight_grad", stage, "W", w)

    def weight_grad_into(self, stage: int):
        """Weight gradient folded into the donated ``acc`` (the W task)."""
        def w(acc, sp_s, io, x, g_in, bm):
            return _fold(acc, self._weight_grads(stage, sp_s, io, x, g_in,
                                                 bm))

        return self._once("weight_grad_into", stage, "W", w,
                          donate_argnums=0)


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
def _edge_payload(dx):
    """A fan-in stage's ``{src_stage: dx}`` goes out one entry per edge."""
    return EdgePayloads(dx) if isinstance(dx, dict) else dx


class ActorStageProgram:
    """``work_fn(task, payload)`` for one stage actor driving real callables.

    F: consume the upstream activation payload (None at a source stage, a
    ``{src_stage: activation}`` dict at a fan-in stage), run the jitted
    forward, stash the stage input for remat-backward, emit y.
    B (fused): consume the downstream gradient payload (None at the last
    stage), re-run forward under grad, fold the parameter grads into the
    accumulators inside the same jitted call, emit dx (``EdgePayloads`` at
    a fan-in stage, one per incoming edge; nothing at a source stage).

    With ``split_backward=True`` (the BFW decomposition):

    B: run the dX-only backward, stash the (x, g_in) pair for the W task,
    emit dx.  A source stage skips the dX computation entirely — no stage
    consumes its input gradient.
    W: consume the stashed pair, run the weight-grad callable, folding into
    ``d_stage``/``d_io`` as B does.  W emits no payload: its result is
    stage-local (``PipelineSpec.message_successor`` is None for W, so no
    envelope is ever sent and no TP admission gate applies).

    The running loss is accumulated as a device array — reading
    ``loss_sum`` materializes it (one sync), so the F hot path never blocks
    on the device.

    In the default eager mode the accumulators are donated to each B (W
    under split backward) call, which returns them with the microbatch's
    grads added: the previous ``d_stage``/``d_io`` arrays are deleted, so
    read them only after the run.  ``folded_in_callable`` counts the
    microbatches folded so, M a step in eager mode.

    With ``deterministic_reduction=True`` the per-microbatch loss and grad
    contributions are *stashed* instead of folded in eagerly, and
    :meth:`finalize` sums them in microbatch order (``folded_in_callable``
    stays 0).  Floating-point addition is not associative, so the default
    eager accumulation is bit-sensitive to the runtime's dispatch order; the
    deterministic mode makes the final loss and gradients bitwise identical
    across any execution order of the same task set — the property the
    conformance suite checks between chaotic actor runs and the fixed-order
    reference executor.

    Profiler spans: ``stage.init`` around the one jitted call that builds
    the zeroed accumulators, and ``stage.accumulate`` around each
    microbatch's loss add, or in deterministic mode its loss and grad stash.
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
            (self.d_stage, self.d_io), self.loss_acc = zero_accumulators(
                sp_s, io)
        #: microbatches whose grads the B/W callable folded in itself
        self.folded_in_callable = 0
        #: deterministic mode: mb -> stashed contributions, folded by finalize
        self._mb_loss: dict[int, Any] = {}
        self._mb_grads: dict[int, tuple[Any, Any]] = {}
        #: highest microbatch already folded — guards against mid-run folds
        self._loss_folded: int | None = None
        self._grads_folded: int | None = None
        self._g_dummy = None

    def _stash_grads(self, mb: int, dsp, dio) -> None:
        with span("stage.accumulate", stage=self.stage):
            self._mb_grads[mb] = (dsp, dio)

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
        bm = self.fns.microbatch(self.batch, task.mb, self.stage)
        if task.kind == Kind.F:
            x = payload  # None at a source stage (its input is in bm)
            y, loss = self.fns.forward(self.stage)(
                self.sp_s, self.io, x, bm)
            self.residual[task.mb] = x
            with span("stage.accumulate", stage=self.stage):
                if self.deterministic_reduction:
                    self._mb_loss[task.mb] = loss
                else:
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
                if x is None:
                    return None  # nobody consumes a source's input gradient
                return _edge_payload(self.fns.backward_dx(self.stage)(
                    self.sp_s, self.io, x, g_in, bm))
            args = (self.sp_s, self.io, x, g_in, bm)
            if self.deterministic_reduction:
                dx, dsp, dio = self.fns.backward(self.stage)(*args)
                self._stash_grads(task.mb, dsp, dio)
                return _edge_payload(dx)
            dx, (self.d_stage, self.d_io) = self.fns.backward_into(
                self.stage)((self.d_stage, self.d_io), *args)
            self.folded_in_callable += 1
            return _edge_payload(dx)
        if task.kind == Kind.W:
            if not self.split_backward:
                raise ValueError(
                    f"{task!r} dispatched to a fused-backward stage program "
                    f"(construct ActorStageProgram with split_backward=True)")
            x, g_in = self.w_pending.pop(task.mb)
            args = (self.sp_s, self.io, x, g_in, bm)
            if self.deterministic_reduction:
                self._stash_grads(
                    task.mb, *self.fns.weight_grad(self.stage)(*args))
            else:
                self.d_stage, self.d_io = self.fns.weight_grad_into(
                    self.stage)((self.d_stage, self.d_io), *args)
                self.folded_in_callable += 1
            return None  # stage-local: no outgoing envelope
        raise ValueError(f"actor stage program cannot run {task!r}")


def warm_up(fns: StageFns, stage_params: list, io, batch: dict, *,
            split_backward: bool = False) -> None:
    """Compile every stage's callables before threads dispatch them.

    Runs each of ``fns.warm_microbatches(batch)`` (microbatch 0 of a chain)
    through throwaway programs in pipeline order: F down the stage graph,
    then B (and W under split backward) back up, each stage handed its
    payload as the runtime's mailbox would — the same calls, with the same
    shapes, that the first threaded step makes: the accumulator build and
    the accumulating B/W of eager mode.  A cold compile at full width can
    outlast the actor runtime's starvation deadline, which would then
    report a deadlock that is only a compilation.
    """
    graph = fns.stage_graph()
    order = graph.topological_order()
    progs = [ActorStageProgram(fns, s, sp_s, io, batch,
                               split_backward=split_backward)
             for s, sp_s in enumerate(stage_params)]

    def arrived(outs: dict, srcs, dst: int):
        by_src = {src: payload_for_edge(outs[src], dst) for src in srcs}
        return by_src if len(by_src) > 1 else next(iter(by_src.values()),
                                                   None)

    for mb in fns.warm_microbatches(batch):
        ys, dxs = {}, {}
        for s in order:
            ys[s] = progs[s](Task(Kind.F, s, mb),
                             arrived(ys, graph.preds(s), s))
        for s in reversed(order):
            dxs[s] = progs[s](Task(Kind.B, s, mb),
                              arrived(dxs, graph.succs(s), s))
            if split_backward:
                progs[s](Task(Kind.W, s, mb), None)
    jax.block_until_ready([(p.d_stage, p.d_io) for p in progs])

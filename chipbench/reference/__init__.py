"""The plain references, and the training loop that drives them.

``train`` runs the first steps of a cell from the seed's weights on the
seed's batches: the loss of each step, the gradient of the first step as the
optimizer gets it, and each weight's change after the last step.  It runs in
float32 at ``highest`` (``precision="f32"``) or, as the control, with fp8
matrix products (``precision="fp8"``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.reference import common, gpt, zamba2

MODELS = {"gpt": gpt.loss_sum, "zamba2": zamba2.loss_sum}


def optimizer(conf: dict, cell: dict) -> dict:
    """The AdamW that the cell's runtime runs: the compiled executor clips
    the global gradient norm to 1 and keeps float32 master weights; the
    actor runtime's host update does neither, and keeps the weights in the
    model's dtype.  Both compute with the weights in the model's dtype."""
    table = cell["runtime"] == "table"
    dtype = jnp.dtype(conf["dtype"])
    return dict(lr=conf["learning_rate"], beta1=0.9, beta2=0.95, eps=1e-8,
                weight_decay=0.1, warmup_steps=20, total_steps=1000,
                min_frac=0.1, clip=1.0 if table else None,
                store=jnp.float32 if table else dtype, compute=dtype)


def grads_fn(conf: dict, cell: dict, precision: str):
    """``grads(params, tokens, labels) -> (mean loss, mean gradients)`` over
    one step's microbatches, [M, rows, seq] each, one microbatch at a time."""
    loss_fn = MODELS[conf["reference"]]
    n_tokens = cell["microbatches"] * cell["mb_rows"] * cell["seq"]

    @jax.jit
    def grads(p, tokens, labels):
        def body(acc, mb):
            loss, g = jax.value_and_grad(loss_fn)(p, mb[0], mb[1], conf,
                                                  precision)
            return (acc[0] + loss, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (loss, g), _ = jax.lax.scan(body, zero, (tokens, labels))
        return loss / n_tokens, jax.tree.map(lambda x: x / n_tokens, g)

    return grads


def train(conf: dict, cell: dict, params: dict, batches: list,
          precision: str = "f32") -> dict:
    """``params``: flat dict of float32 weights; ``batches``: one
    ``{"tokens", "labels"}`` per step.  Returns ``losses``, ``grad_norms``
    (first step) and ``change_norms`` (after the last step), the norms keyed
    like ``params``."""
    M, rows, seq = cell["microbatches"], cell["mb_rows"], cell["seq"]
    opt = optimizer(conf, cell)
    grads_of = grads_fn(conf, cell, precision)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, g, m, v, step, lr):
        return common.adamw(opt, p, g, m, v, step, lr)

    norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(x * x))
                               for k, x in t.items()})
    rounded = jax.jit(lambda t: {k: common.round_to(x, opt["compute"])
                                 for k, x in t.items()})
    start = dict(params)
    p = {k: jnp.array(x) for k, x in params.items()}
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    losses, grad_norms = [], None
    with jax.default_matmul_precision("highest"):
        for i, b in enumerate(batches):
            tok = jnp.asarray(b["tokens"]).reshape(M, rows, seq)
            lab = jnp.asarray(b["labels"]).reshape(M, rows, seq)
            loss, g = grads_of(rounded(p), tok, lab)
            p, m, v, g_used = update(p, g, m, v, jnp.asarray(i, jnp.float32),
                                     jnp.asarray(common.lr_at(opt, i),
                                                 jnp.float32))
            losses.append(float(loss))
            if i == 0:
                grad_norms = {k: float(x) for k, x in norms(g_used).items()}
            del g, g_used
        change = norms({k: p[k] - start[k] for k in p})
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": {k: float(x) for k, x in change.items()}}

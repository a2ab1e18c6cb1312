"""Faults planted under the timed path, to show that ``correct`` catches them.

Neither the benchmark's runs nor its cells use these: the calibration script
reads each fault's numbers on the chip, and a test drives a whole run with
each fault planted and sees ``correct`` come out false.

* ``state_unchanged``: every step returns the weights and optimizer state it
  was given;
* ``half_batch``: the step sees the first half of its microbatches and takes
  the mean over those;
* ``token``: the input tokens of the first microbatch are altered where the
  feed produces them;
* ``exchange``: the activation that stage 0 sends to stage 1 in the first
  microbatch is lost (zeros arrive); actor runtime only.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


class Fault:
    def cell(self, cell: dict) -> dict:
        return cell

    def batch(self, b: dict) -> dict:
        return b

    def wrap(self, step) -> None:
        pass


class StateUnchanged(Fault):
    FIELDS = ("params", "m", "v", "sp", "io", "opt")

    def wrap(self, step) -> None:
        inner = step.step

        def step_fn(i, b):
            keep = {k: jax.tree.map(jnp.copy, getattr(step, k))
                    for k in self.FIELDS if getattr(step, k, None) is not None}
            loss = inner(i, b)
            for k, v in keep.items():
                setattr(step, k, v)
            return loss

        step.step = step_fn


class HalfBatch(Fault):
    def cell(self, cell: dict) -> dict:
        return {**cell, "microbatches": cell["microbatches"] // 2}

    def batch(self, b: dict) -> dict:
        return {k: v[: v.shape[0] // 2] for k, v in b.items()}


class Token(Fault):
    def __init__(self, vocab: int):
        self.vocab = vocab

    def batch(self, b: dict) -> dict:
        tokens = np.array(b["tokens"])
        tokens[0] = (tokens[0] + 1) % self.vocab
        return {**b, "tokens": tokens}


class Exchange(Fault):
    def wrap(self, step) -> None:
        inner = step.programs
        step.programs = lambda batch: [_LoseFirst(p) if i == 0 else p
                                       for i, p in enumerate(inner(batch))]


class _LoseFirst:
    """A stage program whose first microbatch's activation never arrives."""

    def __init__(self, prog):
        self.prog = prog

    def __call__(self, task, payload):
        from repro.core.taskgraph import Kind

        out = self.prog(task, payload)
        if task.kind == Kind.F and task.mb == 0:
            return jnp.zeros_like(out)
        return out

    def __getattr__(self, name):
        return getattr(self.prog, name)


def make(name: str, conf: dict) -> Fault:
    return {"state_unchanged": StateUnchanged, "half_batch": HalfBatch,
            "exchange": Exchange,
            "token": lambda: Token(conf["vocab_size"])}[name]()

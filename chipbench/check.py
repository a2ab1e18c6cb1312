"""The numbers that decide ``correct``, each with its limit.

The program's first steps (from the seed, through the window's own call and
feed) are compared with the plain reference's:

* ``loss``: the largest relative gap of a step's loss, over the checked steps;
* ``grad``: the first step's gradient as the optimizer gets it (AdamW's m
  after one step, over 1 - beta1), by the worst weight: the gap between the
  program's norm and the reference's, over the larger of the reference's norm
  of that weight and the median weight's;
* ``update``: the same for each weight's change over the checked steps, as
  the next step starts from it.  Weights whose reference gradient is under a
  thousandth of the median weight's are left out: they move under AdamW by
  round-off alone.

Weights are counted one layer at a time (``L<g>/...``, ``io/...``): the
program's stacked stage arrays are cut into their layers first.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

BETA1 = 0.9
QUIET = 1e-3  # a weight whose reference gradient is under QUIET x median


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def _slots(model):
    """(stage, slot, layer) of every slot that holds a layer: stage s holds
    ``counts[s]`` consecutive layers in its first slots."""
    out, g = [], 0
    for s, n in enumerate(model.counts):
        for i in range(int(n)):
            out.append((s, i, g))
            g += 1
    return out


def _norms(sp, io):
    """Per-slot norms of stacked leaves [S, l_max, ...], and of io leaves."""
    def stacked(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x, axis=tuple(range(2, x.ndim))))

    def whole(x):
        x = x.astype(jnp.float32)
        return jnp.sqrt(jnp.sum(x * x))

    return jax.tree.map(stacked, sp), jax.tree.map(whole, io)


_norms_jit = jax.jit(_norms)


@jax.jit
def _change(now, start):
    """``_norms`` of now - start, with the difference fused into the sums:
    no float32 copy of the weights is held beside the program's state."""
    return _norms(*jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        now, start))


def _by_weight(model, nsp, nio, scale: float = 1.0) -> dict:
    out = {}
    slots = _slots(model)
    for path, a in jax.tree_util.tree_leaves_with_path(nsp):
        for s, i, g in slots:
            out[f"L{g}/{_name(path)}"] = float(a[s, i]) * scale
    for path, a in jax.tree_util.tree_leaves_with_path(nio):
        out[f"io/{_name(path)}"] = float(a) * scale
    return out


def leaf_norms(model, sp, io, scale: float = 1.0) -> dict:
    """Norms of (stage, io) trees by weight: ``L<g>/<path>``, ``io/<path>``."""
    return _by_weight(model, *jax.device_get(_norms_jit(sp, io)), scale)


def change_norms(model, now, init, key) -> dict:
    """Norms of (now - init(key)) by weight; ``init`` remakes the start.

    The start is made by a call of its own, so that its leaves come out
    rounded to their dtype as the program's did: inlined into the difference,
    XLA on a TPU drops the round trip through bfloat16, and every weight
    would read its rounding error as change."""
    start = init(key)
    norms = jax.device_get(_change(now, start))
    del start
    return _by_weight(model, *norms)


def flat_weights(model, sp, io) -> dict:
    """(stage, io) trees as the reference's flat dict of float32 arrays."""
    out = {}
    for path, a in jax.tree_util.tree_leaves_with_path(sp):
        for s, i, g in _slots(model):
            out[f"L{g}/{_name(path)}"] = a[s, i].astype(jnp.float32)
    for path, a in jax.tree_util.tree_leaves_with_path(io):
        out[f"io/{_name(path)}"] = a.astype(jnp.float32)
    return out


def _worst(prog: dict, ref: dict, keys) -> float:
    keys = list(keys)
    med = float(np.median([ref[k] for k in keys]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def numbers(prog: dict, ref: dict) -> dict:
    """The compared numbers from two records of ``losses``, ``grad_norms``
    and ``change_norms``."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"], strict=True))
    grads = ref["grad_norms"]
    med = float(np.median(list(grads.values())))
    moved = [k for k, g in grads.items() if g >= QUIET * med]
    return {"loss": loss,
            "grad": _worst(prog["grad_norms"], grads, grads),
            "update": _worst(prog["change_norms"], ref["change_norms"],
                             moved)}


def worst(prog: dict, ref: dict, field: str, n: int = 3) -> list:
    """The ``n`` weights with the largest gaps in ``field``, for the log."""
    r, p = ref[field], prog[field]
    med = float(np.median(list(r.values())))
    gaps = {k: abs(p[k] - r[k]) / max(r[k], med) for k in r}
    top = sorted(gaps, key=gaps.get, reverse=True)[:n]
    return [[k, gaps[k], p[k], r[k]] for k in top]


def verdict(values: dict, limits: dict) -> tuple[bool, list]:
    """``correct`` and [(name, value, limit)]: a number passes at or under
    its limit; a missing or non-finite number fails."""
    rows = [(k, values.get(k), limits[k]) for k in limits]
    ok = all(v is not None and np.isfinite(v) and v <= lim
             for _, v, lim in rows)
    return ok, rows

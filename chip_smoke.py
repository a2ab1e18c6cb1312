#!/usr/bin/env python3
"""Bring-up check: the training path on a TPU at published widths.

    python chip_smoke.py            # one chip: runs (a), (b) and (c) below
    python chip_smoke.py --chips 4  # four chips: the compiled executor on a
                                    # 1x4 mesh against a one-chip reference

Every run goes through ``repro.launch.train.main``, the entry point a user
calls, on ``paper-gpt3-large`` (d_model 1536, 16 heads of 96, d_ff 6144,
vocabulary 50304) with random weights from a fixed seed.

One chip, 8 of the 24 layers, 3 steps at seq 2048, 8 microbatches of 1 row:
  (a) the actor runtime, 4 stages, readiness-driven with the BF hint;
  (b) the actor runtime, 4 stages, the pre-committed 1F1B order;
  (c) the compiled executor, 1 stage, with ZeRO-1 AdamW.
Four chips: the compiled executor, 4 stages of 6 layers on a 1x4 mesh.

Checks (any failure exits non-zero): every loss is finite; the step-0 loss
is within 1.0 of ln(vocab), the loss of a random init; the step-0 losses of
(a), (b) and (c) agree within STEP0_RTOL; on four chips the step-0 loss
agrees with ``ArchModel.reference_forward`` on one chip within STEP0_RTOL.

Compile seconds, steady step seconds and peak device bytes are printed as
bring-up figures, not benchmark numbers.  The last line of standard output
is the JSON result.  There is no CPU fallback: without a TPU it exits 2.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import pathlib
import statistics
import sys

ARCH = "paper-gpt3-large"
SEQ = 2048
STEPS = 3
COMMON = ["--arch", ARCH, "--full-size", "--seq", str(SEQ),
          "--steps", str(STEPS), "--microbatches", "8", "--mb-rows", "1"]
ONE_CHIP_RUNS = {
    "a: actor rrfp/bf": ["--layers", "8", "--runtime", "actor", "--stages",
                         "4", "--schedule", "rrfp", "--hint", "bf"],
    "b: actor 1f1b": ["--layers", "8", "--runtime", "actor", "--stages", "4",
                      "--schedule", "1f1b"],
    "c: table 1 stage": ["--layers", "8", "--runtime", "table", "--stages",
                         "1"],
}
FOUR_CHIP_RUN = ["--runtime", "table", "--stages", "4"]
#: Relative agreement of step-0 losses between runs of the same model on the
#: same batch.  Activations are bf16 (about 3 significant digits), and the
#: runs compile to different programs (4 stage programs, 1 stage program, 1x4
#: SPMD program, the unpipelined reference), so fusion and summation order
#: differ.  Averaged over 16,384 tokens those roundings leave the mean loss
#: well inside 1e-3 relative; 2e-3 (about 0.02 nats at ln 50304) keeps a
#: margin, while a wrong layer, mask or scale moves the loss by far more.
STEP0_RTOL = 2e-3


def fail(msg: str, code: int = 1):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def run(train, name: str, argv: list[str], device) -> float:
    """One training run through ``train.main``; returns its step-0 loss."""
    print(f"--- run {name}: {' '.join(argv)}", flush=True)
    log = train.main(argv)
    if len(log.losses) != STEPS:
        fail(f"{name}: {len(log.losses)} steps, expected {STEPS}")
    if not all(math.isfinite(x) for x in log.losses):
        fail(f"{name}: non-finite loss in {log.losses}")
    stats = device.memory_stats() or {}
    print(f"bring-up figures {name}: first step (compile) "
          f"{log.seconds[0]:.1f} s, steady step "
          f"{statistics.median(log.seconds[1:]):.3f} s, process peak device "
          f"bytes so far {stats.get('peak_bytes_in_use', 'not reported')}",
          flush=True)
    print(f"losses {name}: {log.losses}", flush=True)
    return log.losses[0]


def check_step0(name: str, loss: float, vocab: int) -> None:
    if abs(loss - math.log(vocab)) > 1.0:
        fail(f"{name}: step-0 loss {loss} is not within 1.0 of "
             f"ln({vocab}) = {math.log(vocab):.4f}")


def agree(name: str, got: float, want: float) -> None:
    rel = abs(got - want) / abs(want)
    print(f"step-0 agreement {name}: {got} vs {want}, relative {rel:.2e} "
          f"(limit {STEP0_RTOL:.0e})", flush=True)
    if rel > STEP0_RTOL:
        fail(f"{name}: step-0 losses {got} and {want} differ by {rel:.2e}")


def reference_loss(train, cfg) -> float:
    """Step-0 loss of the four-chip run, recomputed on one chip by
    ``ArchModel.reference_forward``, one row at a time so that the
    [rows, seq, vocab] logits never exist at once."""
    import jax
    import jax.numpy as jnp

    from repro.data.synthetic import synth_batch
    from repro.models.build import build

    model = build(cfg, num_stages=4)
    with jax.default_device(jax.devices()[0]):
        sp, io = train.init_params(model)
        batch = synth_batch(cfg, 8, SEQ, seed=0, step=0)
        aux = {"positions": jnp.arange(SEQ, dtype=jnp.int32)[None],
               "data_size": 1, "moe_layout": "none"}

        @jax.jit
        def row_loss(sp, io, tokens, labels):
            logits = model.reference_forward(
                sp, io, {"tokens": tokens}, aux).astype(jnp.float32)
            pick = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
            return jnp.sum(jax.nn.logsumexp(logits, -1) - pick)

        total = sum(float(row_loss(sp, io, batch["tokens"][r:r + 1],
                                   batch["labels"][r:r + 1]))
                    for r in range(8))
    return total / (8 * SEQ)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip compiled-executor phase")
    args = ap.parse_args()

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"platform={dev.platform} device_kind={dev.device_kind} "
          f"count={len(devices)}", flush=True)
    if dev.platform != "tpu":
        fail(f"JAX found platform {dev.platform!r}, not a TPU; this check "
             f"runs on the chip only", code=2)
    if len(devices) < args.chips:
        fail(f"--chips {args.chips}: only {len(devices)} device(s)", code=2)

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))
    from repro.configs import registry
    from repro.launch import train
    from repro.launch.compile_cache import use_compile_cache

    print(f"compile cache: {use_compile_cache()}", flush=True)
    vocab = registry.get_arch(ARCH).vocab_size

    if args.chips == 4:
        loss0 = run(train, "d: table 4 stages x 4 chips",
                    COMMON + FOUR_CHIP_RUN + ["--devices", "4"], dev)
        check_step0("d", loss0, vocab)
        gc.collect()
        ref = reference_loss(train, registry.depth_cut(ARCH))
        agree("d vs one-chip reference_forward", loss0, ref)
    else:
        step0 = {}
        for name, extra in ONE_CHIP_RUNS.items():
            step0[name] = run(train, name, COMMON + extra + ["--devices", "1"],
                              dev)
            check_step0(name, step0[name], vocab)
            gc.collect()
        first, *rest = step0
        for name in rest:
            agree(f"{name} vs {first}", step0[name], step0[first])

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

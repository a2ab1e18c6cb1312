"""Multimodal DAG sweep: readiness-driven vs. pre-committed fixed order.

The paper's headline claim (up to 2.77× on multimodal workloads) lives in
the regime this benchmark reproduces: branch+fusion DAG pipelines whose
encoder stages are cheap, *variable-length* and misaligned with the
LM-decoder stages.  Two skewed workloads, derived from the registered
full-size multimodal archs via ``repro.multimodal``:

* ``qwen2-vl-2b/vision-variance`` — dynamic-resolution vision branch
  matching the LM chain on mean cost, but with large per-microbatch
  length variance (sigma 0.6) making it the intermittent bottleneck;
* ``seamless-m4t-large-v2/heavy-encoder`` — long audio-frame encoder
  branch dominating a light text decoder.

Methods per (workload, jitter level), all on the actor runtime's
virtual-clock substrate with CRN-keyed sampling (same realized
variability for every mode):

  - ``pre_1f1b``      precommitted depth-generalized 1F1B, fused backward
  - ``pre_modality``  precommitted ``modality_balanced_order`` (the
                      Cornstarch-like cost-aware planner), fused
  - ``pre_zb``        precommitted ZB-H1, split backward
  - ``hint_bf``       readiness-driven BF hint, fused
  - ``hint_bfw``      readiness-driven BFW hint, split backward, capped W

Plus a **real threaded smoke**: both archs reduced, real jitted DAG
stage callables through the thread-per-stage runtime, with conformance
invariants and hint-vs-fixed-order bitwise loss parity checked.

    PYTHONPATH=src python -m benchmarks.multimodal_compare
    REPRO_SMOKE=1 PYTHONPATH=src python -m benchmarks.multimodal_compare

Emits ``BENCH_multimodal.json``.
"""
from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

from repro.core import INJECTION_LEVELS, HintKind, PipelineSpec
from repro.core.hints import modality_balanced_order
from repro.multimodal import multimodal_config, multimodal_dag_costs
from repro.runtime.rrfp import ActorConfig, average_makespan_actor

S_ENC, S_LM = 3, 4
M = 24
ITERS = 4
W_DEFER_CAP = 4


def _smoke() -> bool:
    return bool(os.environ.get("REPRO_SMOKE"))


def workload_configs() -> dict:
    """The two skewed encoder/decoder workloads (full-size widths)."""
    return {
        # dynamic-resolution multi-image mix: the encoder branch matches the
        # decoder on MEAN cost but its per-microbatch lognormal spread
        # (sigma 0.6 -> 4-5x spikes) makes it the intermittent bottleneck —
        # the §2.1 regime where pre-committed orders serialize on spikes
        "qwen2-vl-2b/vision-variance": multimodal_config(
            "qwen2-vl-2b", enc_stages=S_ENC, lm_stages=S_LM,
            enc_layers_per_stage=4, lm_layers_per_stage=4,
            text_seq=2048, mean_enc_tokens=16384,
            buckets=(8192, 16384, 32768), reduced=False),
        "seamless-m4t-large-v2/heavy-encoder": multimodal_config(
            "seamless-m4t-large-v2", enc_stages=S_ENC, lm_stages=S_LM,
            enc_layers_per_stage=8, lm_layers_per_stage=3,
            text_seq=256, mean_enc_tokens=12288,
            buckets=(8192, 12288, 16384), reduced=False),
    }


def _mean(spec, cm, cfg, iters):
    m, _, _ = average_makespan_actor(spec, cm, cfg, iters)
    return m


def sweep_rows(iters: int = ITERS) -> list[dict]:
    levels = ["J0", "J2"] if _smoke() else list(INJECTION_LEVELS)
    iters = 1 if _smoke() else iters
    microbatches = 8 if _smoke() else M
    out = []
    for wname, mm in workload_configs().items():
        graph = mm.stage_graph()
        fused = PipelineSpec(mm.num_stages, microbatches, graph=graph)
        split = PipelineSpec(mm.num_stages, microbatches,
                             split_backward=True, graph=graph)
        base = multimodal_dag_costs(mm, seed=0)
        mod_orders = [
            modality_balanced_order(fused, s, list(base.f_cost))
            for s in range(mm.num_stages)]
        for level in levels:
            cm_f = dataclasses.replace(base,
                                       injection=INJECTION_LEVELS[level])
            cm_s = cm_f.with_split_backward()
            ms = {
                "pre_1f1b": _mean(fused, cm_f, ActorConfig(
                    mode="precommitted", fixed_order="1f1b"), iters),
                "pre_modality": _mean(fused, cm_f, ActorConfig(
                    mode="precommitted", custom_orders=mod_orders), iters),
                "pre_zb": _mean(split, cm_s, ActorConfig(
                    mode="precommitted", fixed_order="zb"), iters),
                "hint_bf": _mean(fused, cm_f, ActorConfig(
                    mode="hint", hint=HintKind.BF), iters),
                "hint_bfw": _mean(split, cm_s, ActorConfig(
                    mode="hint", hint=HintKind.BFW,
                    w_defer_cap=W_DEFER_CAP), iters),
            }
            best_pre = min(ms["pre_1f1b"], ms["pre_modality"], ms["pre_zb"])
            out.append({
                "workload": wname,
                "modality": mm.modality,
                "level": level,
                "stages": mm.num_stages,
                "graph": [list(e) for e in graph.edges],
                "makespan_s": ms,
                "speedups": {
                    "bfw_vs_1f1b": ms["pre_1f1b"] / ms["hint_bfw"],
                    "bfw_vs_modality": ms["pre_modality"] / ms["hint_bfw"],
                    "bfw_vs_zb": ms["pre_zb"] / ms["hint_bfw"],
                    "bf_vs_1f1b": ms["pre_1f1b"] / ms["hint_bf"],
                    "bfw_vs_best_precommitted": best_pre / ms["hint_bfw"],
                },
            })
    return out


def real_threaded_dag(steps: int = 2) -> dict:
    """Real jitted DAG stage callables through the threaded actor runtime:
    completion, conformance invariants, and bitwise hint-vs-fixed-order
    loss parity (deterministic reduction) on both registered archs."""
    import jax

    from repro.data.synthetic import multimodal_batch
    from repro.multimodal import MultimodalStageFns, multimodal_model
    from repro.pipeline.stagefn import ActorStageProgram, StageFnOptions
    from repro.runtime.rrfp import ActorDriver
    from repro.runtime.rrfp.conformance import check_all

    out = {}
    for arch in ("qwen2-vl-2b", "seamless-m4t-large-v2"):
        model = multimodal_model(
            arch, enc_stages=2, lm_stages=2, enc_layers_per_stage=1,
            lm_layers_per_stage=1, text_seq=16, fusion_slots=4,
            mean_enc_tokens=14, buckets=(8, 16, 24))
        cfg = model.cfg
        mm, rows = 4, 1
        params = model.init_stage_params(jax.random.key(0))
        fns = MultimodalStageFns(model, StageFnOptions(
            mb_rows=rows, seq_len=16, loss_scale=1.0 / (mm * rows * 16)))

        def run(mode: str, step: int):
            batch = multimodal_batch(cfg, mm, rows, seed=0, step=step)
            progs = [
                ActorStageProgram(fns, s, params[s], {}, batch,
                                  deterministic_reduction=True)
                for s in range(cfg.num_stages)
            ]
            spec = cfg.spec(mm)
            acfg = ActorConfig(mode=mode, hint=HintKind.BF,
                               fixed_order="1f1b", deadlock_timeout=300.0,
                               record_trace=True)
            res = ActorDriver(spec, None, acfg).run_threaded(list(progs))
            check_all(res.trace, spec, acfg)
            assert len(res.end) == spec.total_tasks()
            for p in progs:
                p.finalize()
            loss = float(sum(p.loss_acc for p in progs))
            return loss, res.makespan * 1e3

        losses_h, losses_p, step_ms = [], [], []
        for step in range(steps):
            lh, msh = run("hint", step)
            lp, _ = run("precommitted", step)
            assert np.float32(lh).tobytes() == np.float32(lp).tobytes(), (
                f"{arch}: hint loss bits diverged from fixed order")
            losses_h.append(lh)
            losses_p.append(lp)
            step_ms.append(msh)
        out[arch] = {
            "stages": cfg.num_stages,
            "graph": [list(e) for e in cfg.stage_graph().edges],
            "tasks": cfg.spec(mm).total_tasks(),
            "loss": losses_h,
            "step_ms": step_ms,
            "loss_parity_vs_fixed_order": True,
            "conformance": True,
        }
    return out


def run_multimodal_benchmark() -> dict:
    rows = sweep_rows()
    jittered = [r for r in rows if r["level"] != "J0"]
    wins = all(r["speedups"]["bfw_vs_best_precommitted"] > 1.0
               for r in jittered)
    per_workload = {}
    for r in jittered:
        per_workload.setdefault(r["workload"], []).append(
            r["speedups"]["bfw_vs_best_precommitted"])
    return {
        "spec": {"enc_stages": S_ENC, "lm_stages": S_LM,
                 "microbatches": 8 if _smoke() else M,
                 "iters": 1 if _smoke() else ITERS,
                 "w_defer_cap": W_DEFER_CAP, "smoke": _smoke()},
        "sweep": rows,
        "real_threaded": real_threaded_dag(),
        "summary": {
            "hint_beats_best_precommitted_on_all_jittered_cells": wins,
            "mean_speedup_vs_best_precommitted_per_workload": {
                w: float(np.mean(v)) for w, v in per_workload.items()},
        },
    }


def emit_json(path: str = "BENCH_multimodal.json") -> dict:
    report = run_multimodal_benchmark()
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
    return report


def multimodal_rows(
    json_path: str = "BENCH_multimodal.json",
) -> list[tuple[str, float, str]]:
    """CSV rows for ``benchmarks.run`` (name, us_per_call, derived)."""
    report = emit_json(json_path)
    out = []
    for r in report["sweep"]:
        tag = f"multimodal/{r['workload']}/{r['level']}"
        ms, sp = r["makespan_s"], r["speedups"]
        out.append((f"{tag}/hint-bfw", ms["hint_bfw"] * 1e6,
                    f"vs_best_pre={sp['bfw_vs_best_precommitted']:.2f}x"))
        out.append((f"{tag}/pre-modality", ms["pre_modality"] * 1e6,
                    f"vs_1f1b={sp['bfw_vs_1f1b']:.2f}x"))
    for arch, rt in report["real_threaded"].items():
        out.append((f"multimodal/real-threaded/{arch}",
                    float(np.mean(rt["step_ms"])) * 1e3,
                    f"loss_parity={rt['loss_parity_vs_fixed_order']}"))
    return out


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for name, us, derived in multimodal_rows():
        print(f"{name},{us:.1f},{derived}")

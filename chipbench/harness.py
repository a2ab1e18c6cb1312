"""One run of one cell: set-up, the measured window, the traced steps, the
check against the plain reference, and the result line.

Everything that belongs to one cell, configuration, metric or kernel is a file
of its own, found by the name in ``BENCHMARK.json``: ``cells/<cell>.json``,
``configs/<config>.json``, ``metrics/<metric>.py``, ``kernels/<kernel>.py``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import pathlib
import shutil
import sys
import time

import jax
import numpy as np

from chipbench import check, data, faults, model_flops, peaks, steps, weights
from chipbench import trace as trace_lib

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CHECKED_STEPS = 3
TRACED_STEPS = 3
COMPILE_EVENT = "/jax/compilation_cache/compile_requests_use_cache"


def load_json(kind: str, name: str) -> dict:
    return json.loads((HERE / kind / f"{name}.json").read_text())


def load_module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench.{kind}.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def program_config(conf: dict):
    """The program's config for ``conf``, checked against the file's sizes."""
    from repro.configs import registry

    cfg = registry.depth_cut(conf["arch"], conf["n_layers"])
    want = {"d_model": cfg.d_model, "n_heads": cfg.num_heads,
            "d_head": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
            "vocab_size": cfg.vocab_size, "dtype": str(np.dtype(cfg.dtype)),
            "activation": cfg.act, "n_layers": cfg.num_layers}
    if cfg.ssm is not None:
        want.update(d_state=cfg.ssm.d_state, d_conv=cfg.ssm.d_conv,
                    ssm_head_dim=cfg.ssm.head_dim, chunk=cfg.ssm.chunk,
                    ssm_heads=cfg.ssm.num_heads(cfg.d_model),
                    shared_period=cfg.shared_attn_period)
    bad = {k: (conf.get(k), v) for k, v in want.items() if conf.get(k) != v}
    if bad:
        raise SystemExit(f"configuration {conf['name']} is not what the "
                         f"program runs: (file, program) {bad}")
    return cfg


def roofline(rec: dict, kernel: str):
    """% of the roofline that ``kernel``'s traced calls reach, or None."""
    tr = rec.get("trace") or {}
    seconds, calls = tr.get("kernels", {}).get(kernel, (0.0, 0))
    if not calls or seconds <= 0:
        return None
    k = load_module("kernels", kernel)
    sh = k.shapes(rec["conf"], rec["cell"])
    least = max(k.flops(**sh) / rec["peak"]["bf16_flops"],
                k.bytes_moved(**sh) / rec["peak"]["hbm_bytes_per_s"])
    return 100.0 * least * calls / seconds


# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """The state of one run between its phases."""
    cell: dict
    conf: dict
    seed: int
    step: object
    batch: object  # i -> the batch the program is fed at step i
    clean: object  # i -> the seed's batch of step i, for the reference
    checked: dict  # the program's losses, grad_norms, change_norms
    setup_parts: dict


def feed(conf: dict, cell: dict, seed: int):
    rows = cell["microbatches"] * cell["mb_rows"]
    return lambda i: data.batch(conf["vocab_size"], rows, cell["seq"],
                                seed=seed, step=i)


def setup(cell: dict, conf: dict, seed: int, *, cfg=None, params=None,
          timer: steps.Timer | None = None, fault=None) -> Run:
    """Build the cell's step from the seed, warm it up and drive it through
    the checked steps, reading what the check needs on the way."""
    timer = timer or steps.Timer()
    cfg = cfg if cfg is not None else program_config(conf)
    fault = fault or faults.Fault()
    key = weights.seed_key(seed)
    step = steps.STEPS[cell["runtime"]](cfg, fault.cell(cell), conf, key,
                                        params=params)
    fault.wrap(step)
    clean = feed(conf, cell, seed)
    batch = lambda i: fault.batch(clean(i))  # noqa: E731
    timer.lap("init")
    step.warm(batch(0))
    timer.lap("warm")
    losses, grads = [], None
    for i in range(CHECKED_STEPS):
        losses.append(step.step(i, batch(i)))
        if i == 0:
            grads = check.leaf_norms(step.model, *step.first_moment(),
                                     scale=1.0 / (1.0 - check.BETA1))
    timer.lap("checked_steps")
    init = step.init or steps.make_param_init(step.model)
    change = check.change_norms(step.model, step.params_now(), init, key)
    timer.lap("check_reads")
    return Run(cell, conf, seed, step, batch, clean,
               {"losses": losses, "grad_norms": grads,
                "change_norms": change}, timer.parts)


def window(run: Run, seconds: float) -> dict:
    """Whole steps for ``seconds``; the record the metric readers read.
    Counts the programs compiled or loaded from the cache meanwhile."""
    step, spans = run.step, run.step.spans
    spans.reset()
    if hasattr(step, "results"):
        step.results.clear()
    step_s, losses, compiles, gc_full = [], [], [], []
    gc_start = [0.0]

    def on_event(name: str, **_):
        if name == COMPILE_EVENT:
            compiles.append(name)

    def on_gc(phase: str, info: dict):
        # Full collections only, as (window step, seconds): a diagnostic of
        # the window's slow steps, printed on standard error.
        if phase == "start":
            gc_start[0] = time.perf_counter()
        elif info["generation"] == 2:
            gc_full.append((i, time.perf_counter() - gc_start[0]))

    i = CHECKED_STEPS
    jax.monitoring.register_event_listener(on_event)
    gc.callbacks.append(on_gc)
    try:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            ts = time.perf_counter()
            with spans("step"):
                with spans("batch"):
                    b = run.batch(i)
                losses.append(step.step(i, b))
            step_s.append(time.perf_counter() - ts)
            i += 1
        window_s = time.perf_counter() - t0
    finally:
        gc.callbacks.remove(on_gc)
        jax.monitoring.unregister_event_listener(on_event)
    cell, conf = run.cell, run.conf
    return {"step_s": step_s, "steps": len(step_s), "window_s": window_s,
            "gc_full": gc_full,
            "losses": losses, "spans": spans.totals(),
            "actor": list(getattr(step, "results", [])),
            "tokens_per_step": step.tokens,
            "flops_per_step": model_flops.step_flops(
                conf, cell["microbatches"] * cell["mb_rows"], cell["seq"]),
            "cell": cell, "conf": conf, "chips": cell["chips"],
            "window_compiles": len(compiles), "next_step": i}


def traced(run: Run, rec: dict, kernels: list) -> dict:
    """``TRACED_STEPS`` more steps under the profiler, reduced."""
    out = ROOT / ".chipbench" / "trace" / run.cell["name"]
    shutil.rmtree(out, ignore_errors=True)
    spans, i = run.step.spans, rec["next_step"]
    jax.profiler.start_trace(str(out))
    try:
        for i in range(i, i + TRACED_STEPS):
            with spans("step"):
                with spans("batch"):
                    b = run.batch(i)
                run.step.step(i, b)
    finally:
        jax.profiler.stop_trace()
    match = {k: load_module("kernels", k).match for k in kernels}
    red = trace_lib.reduce(trace_lib.load(str(out)), steps.SPANS, match)
    shutil.rmtree(out, ignore_errors=True)
    return red


def memory_peak(chips: int) -> int:
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.devices()[:chips])


def reference_record(run: Run, precision: str = "f32") -> dict:
    """The plain reference's first steps from the seed's weights."""
    from chipbench import reference

    model = run.step.model
    sp, io = steps.make_param_init(model)(weights.seed_key(run.seed))
    flat = check.flat_weights(model, sp, io)
    del sp, io
    batches = [run.clean(i) for i in range(CHECKED_STEPS)]
    return reference.train(run.conf, run.cell, flat, batches, precision)


def free(run: Run) -> None:
    run.step.free()
    gc.collect()


def kernels_of(bench: dict, name: str) -> list:
    """Kernels whose roofline metric the cell reports."""
    return [m["name"][: -len("_roofline")] for m in bench["per_layer"]
            if m["name"].endswith("_roofline")
            and name in m.get("workloads", [name])]


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_process: float) -> tuple[dict, list]:
    """One run of a cell of ``BENCHMARK.json`` on this machine's chips;
    returns the result line and the rows of the check."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = load_json("cells", name)
    conf = load_json("configs", cell["config"])
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"JAX finds platform {devices[0].platform!r}, not a "
                         f"TPU: this benchmark measures the chip only")
    if len(devices) < cell["chips"]:
        raise SystemExit(f"{name} needs {cell['chips']} chips; JAX finds "
                         f"{len(devices)}")
    peak = peaks.peak(devices[0].device_kind)
    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    timer = steps.Timer(start=t_process)
    timer.lap("imports")
    return measure(bench, cell, conf, seed, seconds, trace, peak, timer,
                   t_process)


def measure(bench: dict, cell: dict, conf: dict, seed: int, seconds: float,
            trace: bool, peak: dict, timer: steps.Timer, t_process: float, *,
            cfg=None, fault=None) -> tuple[dict, list]:
    """Everything of a run after the look for the chips."""
    dev = jax.devices()[0]
    name = cell["name"]
    run = setup(cell, conf, seed, cfg=cfg, timer=timer, fault=fault)
    setup_s = time.perf_counter() - t_process
    rec = window(run, seconds)
    rec["peak"] = peak
    rec["trace"] = traced(run, rec, kernels_of(bench, name)) if trace else {}
    rec["memory_peak_bytes"] = memory_peak(cell["chips"])
    free(run)
    ref = reference_record(run)
    values = check.numbers(run.checked, ref)
    values["window_compiles"] = rec["window_compiles"]
    ok, rows = check.verdict(values, {**cell.get("limits", {}),
                                      "window_compiles": 0})
    failed = sum(1 for x in rec["losses"] if not math.isfinite(x))
    if trace:
        metrics = {}
        for m in bench["per_layer"]:
            if name in m.get("workloads", [name]):
                v = load_module("metrics", m["name"]).read(rec)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {"tokens_per_s": {
            "value": rec["steps"] * rec["tokens_per_step"] / rec["window_s"],
            "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": cell["chips"],
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    result = {"correct": ok and failed == 0, "attempted": rec["steps"],
              "failed": failed, "metrics": metrics, "device": device}
    if trace and rec["trace"]:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        result["breakdown"] = {"device_ops": rec["trace"]["device_ops"],
                               "idle_gaps": rec["trace"]["idle_gaps"]}
    result["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    info = {"setup_parts": run.setup_parts, "steps": rec["steps"],
            "window_s": rec["window_s"], "step_s": rec["step_s"],
            "gc_full": rec["gc_full"],
            "losses": run.checked["losses"],
            "reference_losses": ref["losses"], "values": values,
            "worst": {f: check.worst(run.checked, ref, f)
                      for f in ("grad_norms", "change_norms")}}
    print("chipbench: " + json.dumps(info), file=sys.stderr, flush=True)
    return result, rows


@contextlib.contextmanager
def quiet_stdout():
    """Keep the program's prints off standard output, whose last line is the
    result."""
    saved = sys.stdout
    sys.stdout = sys.stderr
    try:
        yield
    finally:
        sys.stdout = saved

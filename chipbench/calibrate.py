"""Readings that the limits of a cell are set from, on the chip.

    python chipbench/calibrate.py --workload <cell> [--seeds 12]
        [--control 3] [--faults half_batch,token] [--first-seed N]

For each seed: the program's first steps against the plain reference (the
lower readings).  On the first ``--control`` seeds also the control, the
reference with fp8 matrix products in the program's place, and each planted
fault (the upper readings).  The benchmark's own runs never run this.  One
JSON line per reading, then a summary line: the largest program reading and
the smallest control and fault readings of each number.
"""
import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", default="half_batch,token")
    ap.add_argument("--first-seed", type=int, default=3_000_000_011)
    args = ap.parse_args()

    import jax

    from chipbench import check, faults, harness
    from repro.launch.compile_cache import use_compile_cache

    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibration reads the chip only")
    use_compile_cache()
    cell = harness.load_json("cells", args.workload)
    conf = harness.load_json("configs", cell["config"])
    kinds = [f for f in args.faults.split(",") if f]
    rows = []
    out = sys.stdout  # the program's prints go to standard error meanwhile

    def emit(kind, seed, values, seconds):
        row = {"kind": kind, "seed": seed, "seconds": seconds, **values}
        rows.append(row)
        print(json.dumps(row), file=out, flush=True)

    with harness.quiet_stdout():
        for n in range(args.seeds):
            seed = args.first_seed + 7919 * n
            t = time.perf_counter()
            run = harness.setup(cell, conf, seed)
            harness.free(run)
            ref = harness.reference_record(run)
            emit("program", seed, check.numbers(run.checked, ref),
                 time.perf_counter() - t)
            if n >= args.control:
                continue
            t = time.perf_counter()
            ctl = harness.reference_record(run, "fp8")
            emit("control", seed, check.numbers(ctl, ref),
                 time.perf_counter() - t)
            for kind in kinds:
                t = time.perf_counter()
                bad = harness.setup(cell, conf, seed,
                                    fault=faults.make(kind, conf))
                harness.free(bad)
                emit(kind, seed, check.numbers(bad.checked, ref),
                     time.perf_counter() - t)
    summary = {}
    for key in ("loss", "grad", "update"):
        prog = [r[key] for r in rows if r["kind"] == "program"]
        summary[key] = {"program_max": max(prog)}
        for kind in ["control"] + kinds:
            vals = [r[key] for r in rows if r["kind"] == kind]
            if vals:
                summary[key][f"{kind}_min"] = min(vals)
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

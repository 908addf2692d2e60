"""Readings that the correctness limits are set from, for one cell, over
many seeds in one process.  Not part of a benchmark run.

    python3 perfbench/calibrate.py --workload <name> --seeds 1,2,3 --out r.jsonl
    python3 perfbench/calibrate.py --config gpt2s --traffic serve-zipf ...

For each seed it runs the cell as `run.py` does, with a short window, and
writes one JSON line: the program's numbers against the float32 reference
(the lower readings), the control's (the reference in fp8 put in the
program's place; the upper readings), and for training cells the planted
fault of half the cohort left out with the mean taken over the rest.  A
state left unchanged reads 1 on `grad_gap` and `change_gap` by their
definition and needs no run.  `--config/--traffic` run a pair that is not
a cell of BENCHMARK.json.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--config")
    ap.add_argument("--traffic")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    harness.setup_paths()
    from bench import spec
    bench = spec.load_benchmark(harness.ROOT)
    if args.workload:
        cell = spec.resolve(args.workload, bench, harness.ROOT)
    else:
        name = f"{args.config}.{args.traffic}"
        bench["workloads"].append({"name": name, "config": args.config,
                                   "traffic": args.traffic, "chips": 1})
        cell = spec.resolve(name, bench, harness.ROOT)

    import jax
    jax.config.update("jax_compilation_cache_dir", harness.CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import device
    from bench.compile_clock import CompileClock
    dev = device.check(cell.chips)
    peaks = device.peaks(dev["kind"])
    clock = CompileClock()
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    runner = cell.runner()
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.perf_counter()
        ns = argparse.Namespace(seed=seed, seconds=args.seconds, trace=0)
        ctx = harness.Context(ns, dict(dev), peaks, clock, cell.chips)
        ctx.t_start = t0
        run = runner.run(cell, ctx)
        line = {"cell": cell.name, "seed": seed,
                "program": {c.name: c.value for c in run.checks},
                "setup_s": run.setup_s, "failed": run.failed,
                "memory_peak_bytes": ctx.device.get("memory_peak_bytes")}
        line.update(readings(cell, seed, run))
        line["seconds"] = time.perf_counter() - t0
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        print(json.dumps(line), flush=True)
    return 0


def readings(cell, seed: int, run) -> dict:
    ref = cell.reference()
    d = run.dims
    if cell.kind == "federated":
        from bench.runners import federated as fd
        prog, ref32 = run.notes["program"], run.notes["reference"]
        n_check = int(cell.traffic["check_rounds"])
        clients = int(cell.traffic["cohort"]["clients"])
        ctrl = fd.reference_rounds(ref, d, cell, seed, n_check,
                                   precision="fp8")
        half = fd.reference_rounds(ref, d, cell, seed, n_check,
                                   use_clients=clients // 2)
        return {
            "control": fd.numbers(ref, d, cell, fd.as_program(ctrl, prog["vb"]),
                                  ref32),
            "half_cohort": fd.numbers(ref, d, cell,
                                      fd.as_program(half, prog["vb"]), ref32),
            "loss": {"program": [float(np.mean(x)) for x in prog["loss"]],
                     "reference": ref32["loss"], "control": ctrl["loss"]}}
    from bench.runners import serving as sv
    gaps = sv.reference_gaps(cell, d, seed, run.notes["sample"],
                             controls=("fp8",))
    return {"control": {"logit_gap": max(gaps["fp8"])},
            "per_request": {"program": gaps["served"],
                            "control": gaps["fp8"]},
            "checked_tokens": run.notes["checked_tokens"]}


if __name__ == "__main__":
    sys.exit(main())

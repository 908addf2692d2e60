"""Run one cell of the benchmark on the chip(s) of the machine it is on.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  The cell, its configuration, traffic,
metrics and correctness limits are found by name from `BENCHMARK.json`.
One process: it checks for the chips the cell needs (and exits non-zero,
printing no result, without them), sets up and warms up from the seed,
measures for `--seconds`, checks what the timed path produced against the
plain reference, and prints one JSON object as the last line of standard
output.  With `--trace 1` the window is traced and the metrics are the
cell's per-layer ones; with `--trace 0` its end-to-end ones.  The numbers
compared for `correct` come last, on standard error and in the result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# JAX's persistent compilation cache, at a fixed path inside the checkout
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# a traced run profiles the window's units up to the first that ends this
# long after the window opened, so the trace stays small and quick to read
TRACE_SECONDS = 4.0


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


class Context:
    """What a runner needs from the harness: the seed and window length,
    the device, the compile clock, and the hooks that open the measured
    window, mark each unit of work done in it, and close it.  In a traced
    run the profiler runs from the opening to the end of the first unit
    TRACE_SECONDS later, and `traced` sums the work of the traced units."""

    def __init__(self, args, device, peaks, clock, chips: int):
        self.seed = args.seed
        self.seconds = args.seconds
        self.device = device
        self.peaks = peaks
        self.clock = clock
        self.chips = chips
        self.t_start = T_START
        self.capture = None
        self.marks = {}
        self.traced = {"units": 0.0}
        self._trace_t0 = 0.0
        if args.trace:
            from bench import tracing
            self.capture = tracing.Capture()

    def window_started(self):
        if self.capture is not None:
            self.capture.start()
            self._trace_t0 = time.perf_counter()

    def unit_done(self, **work):
        if self.capture is None or not self.capture.active:
            return
        self.traced["units"] += 1
        for k, v in work.items():
            self.traced[k] = self.traced.get(k, 0.0) + float(v)
        if time.perf_counter() - self._trace_t0 >= TRACE_SECONDS:
            self.capture.stop()

    def window_ended(self):
        if self.capture is not None and self.capture.active:
            self.capture.stop()

    def mark(self, name: str):
        """Seconds from process start to a point of the set-up."""
        self.marks[name] = time.perf_counter() - self.t_start

    def read_memory(self):
        from bench import device
        self.device["memory_peak_bytes"] = device.memory_peak_bytes(self.chips)


def unit_stats(unit_s):
    """The slowest unit of the window against the median one, and when in
    the window it ended: what tells a stall from a slow run."""
    if not unit_s:
        return {}
    import statistics
    slow = max(range(len(unit_s)), key=unit_s.__getitem__)
    return {"unit_ms_median": 1e3 * statistics.median(unit_s),
            "unit_ms_max": 1e3 * unit_s[slow],
            "unit_max_ends_at_s": sum(unit_s[:slow + 1])}


def setup_paths():
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH_DIR)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR


def main(argv=None) -> int:
    args = parse(argv)
    setup_paths()
    from bench import spec
    cell = spec.resolve(args.workload, root=ROOT)
    runner = cell.runner()          # imports the system under test

    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import device
    dev = device.check(cell.chips)
    peaks = device.peaks(dev["kind"])
    from bench.compile_clock import CompileClock
    clock = CompileClock()
    ctx = Context(args, dev, peaks, clock, cell.chips)
    ctx.mark("device_s")

    run = runner.run(cell, ctx)
    if ctx.capture is not None:
        from bench import tracing
        run.trace = tracing.summarize(
            ctx.capture.path, cell.chips,
            kernels=tracing.kernel_names(run.hlo_texts))
        run.traced = ctx.traced
        ctx.capture.close()
        entries = cell.per_layer
    else:
        entries = cell.end_to_end
    metrics = spec.read_metrics(entries, run, cell.bench_dir)

    dv = dict(ctx.device)
    if run.trace is not None:
        dv["busy_s"] = run.trace.busy_s
        dv["window_s"] = run.trace.window_s
    checks = {c.name: {"value": c.value, "limit": c.limit} for c in run.checks}
    result = {"correct": run.correct, "attempted": run.attempted,
              "failed": run.failed, "metrics": metrics, "device": dv}
    if run.trace is not None:
        result["breakdown"] = run.trace.breakdown()
    result["work"] = dict(run.work, **ctx.marks, **unit_stats(run.unit_s))
    result["checks"] = checks
    for c in run.checks:
        print(f"check {c.name}: {c.value!r} (limit {c.limit!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

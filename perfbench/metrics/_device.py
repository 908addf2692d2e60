"""Shared arithmetic of the trace readers (not a metric: no entry in
BENCHMARK.json names it)."""


def idle_share(run, kind):
    if run.kind != kind or run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)


def units(run):
    """Units of work (rounds or chunks) inside the traced window."""
    n = run.traced.get("units", 0.0) if run.traced else 0.0
    return n if n > 0 else None

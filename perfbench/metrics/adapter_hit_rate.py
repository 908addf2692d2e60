"""Share of the window's adapter acquisitions that found the tenant's
adapter resident: the paged cache's own hit and miss counters."""


def read(run):
    if run.kind != "serving":
        return None
    hits, misses = run.work["adapter_hits"], run.work["adapter_misses"]
    if hits + misses == 0:
        return None
    return 100.0 * hits / (hits + misses)

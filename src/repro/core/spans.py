"""Host spans of the round and serving loops, on the profiler's clock.

The loops mark their phases with `jax.profiler.TraceAnnotation` (names
`engine.*`, `serve.*`, `cache.*`; docs/engines.md, docs/serving.md), so
under `jax.profiler.trace` they land in the same trace as the device's
ops and every idle stretch of the device lies inside a named host span.
Outside a trace an annotation costs about a microsecond.

`gc_spans()` adds the one span no loop can open itself: `python.gc`,
around each collection of Python's garbage collector.
"""
from __future__ import annotations

import contextlib
import gc
from typing import Iterator, List

from jax.profiler import TraceAnnotation


@contextlib.contextmanager
def gc_spans() -> Iterator[None]:
    """While inside, every garbage collection runs under a `python.gc`
    span whose `generation` arg is the generation collected."""
    opened: List[TraceAnnotation] = []

    def hook(phase, info):
        if phase == "start":
            span = TraceAnnotation("python.gc", generation=info["generation"])
            span.__enter__()
            opened.append(span)
        elif opened:
            opened.pop().__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)
        while opened:
            opened.pop().__exit__(None, None, None)

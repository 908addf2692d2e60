"""The device trace of a measured window, and its reduction to what the
per-layer metrics and the `breakdown` read.

`Capture` runs JAX's profiler over the window into a temporary directory.
`summarize` reads the `.xplane.pb` it wrote with `jax.profiler.ProfileData`
alone:

- the window is the benchmark's `bench.window` span, from the profiler's
  start to its stop, or else the extent of its other host spans
  (`bench.*`), whose clock the device planes share;
- a device's busy time is the union of its op intervals (the `XLA Ops`
  line of each `/device:TPU:<i>` plane) inside the window, averaged over
  the chips used;
- device time by op name (`XLA Ops`) and by program (`XLA Modules`);
- every idle stretch of the first chip inside the window, named by the
  innermost benchmark span open on the host across most of it, or
  `host` where none is.

An op is named `<program>:<instruction>`; a Pallas kernel (a
`tpu_custom_call`) by its kernel function's name instead of its
instruction's, which the compiled programs' HLO (`kernel_names`) gives.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench."
# the traced window itself: opened when the profiler starts, closed when it
# stops, so it holds exactly the units of work the harness counted
WINDOW_SPAN = "bench.window"


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                                    # mean over chips
    ops: Dict[str, Tuple[float, int]]                # name -> (self s, n)
    modules: Dict[str, Tuple[float, int]]
    idle: List[Tuple[str, float]]                    # (label, s) per gap
    spans: Dict[str, Tuple[float, int]]              # host span -> (s, n)

    def op_seconds(self, match) -> Tuple[float, int]:
        """Total device seconds and count of ops whose name `match` accepts."""
        return _total(self.ops, match)

    def module_seconds(self, match) -> Tuple[float, int]:
        """The same for programs (`XLA Modules`), by program name."""
        return _total(self.modules, match)

    def breakdown(self, top: int = 10) -> Dict[str, list]:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][0])[:top]
        idle: Dict[str, float] = collections.defaultdict(float)
        for label, s in self.idle:
            idle[label] += s
        gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v[0]] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def _total(table, match) -> Tuple[float, int]:
    s, n = 0.0, 0
    for name, (t, c) in table.items():
        if match(name):
            s, n = s + t, n + c
    return s, n


class Capture:
    """Profiler over a window; `path` is the trace file once stopped."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        self.path: Optional[str] = None
        self._on = False

    @property
    def active(self) -> bool:
        return self._on

    def start(self):
        import jax
        jax.profiler.start_trace(self.dir)
        self._on = True
        self._span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
        self._span.__enter__()

    def stop(self):
        import jax
        if not self._on:
            return
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self._on = False
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        self.path = found[0] if found else None

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')
_KERNEL = re.compile(rb"\b(_?[A-Za-z][A-Za-z0-9_]*_kernel)\b")


def kernel_names(hlo_texts: List[str]) -> Dict[Tuple[str, str], str]:
    """{(program, instruction): kernel function} for every Pallas call in
    the compiled programs' HLO texts; the kernel's name is read from the
    Mosaic module serialized in the call's backend config."""
    out = {}
    for text in hlo_texts:
        m = re.search(r"^HloModule ([^\s,]+)", text, re.M)
        module = m.group(1) if m else ""
        for line in text.splitlines():
            if "tpu_custom_call" not in line or " = " not in line:
                continue
            body = _BODY.search(line)
            if not body:
                continue
            found = _KERNEL.findall(base64.b64decode(body.group(1)))
            if found:
                instr = line.strip().split(" = ")[0].replace("ROOT ", "")
                out[(module, instr)] = found[0].decode()
    return out


def _module_base(name: str) -> str:
    """`jit_step(1234)` -> `jit_step`."""
    return name.split("(")[0]


def _instr(event_name: str) -> str:
    return event_name.split(" = ")[0]


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def _self_times(evs) -> List[int]:
    """Each op's duration less the ops nested inside it (a loop's body ops
    are events of their own inside the loop's event).  `evs` sorted by
    start, outer first."""
    out = [b - a for _, a, b in evs]
    stack: List[int] = []
    for i, (_, a, b) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack:
            out[stack[-1]] -= b - a
        stack.append(i)
    return out


def _events(line):
    return [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
            for e in line.events]


def summarize(path: str, chips: int = 1,
              kernels: Optional[Dict[Tuple[str, str], str]] = None
              ) -> Summary:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans: List[Tuple[str, int, int]] = []
    devices: Dict[int, Dict[str, list]] = {}
    for plane in data.planes:
        name = plane.name
        if name.startswith(DEVICE_PREFIX) and name[len(DEVICE_PREFIX):].isdigit():
            lines = {ln.name: _events(ln) for ln in plane.lines}
            devices[int(name[len(DEVICE_PREFIX):])] = lines
        elif name.startswith("/host:"):
            for ln in plane.lines:
                spans += [ev for ev in _events(ln)
                          if ev[0].startswith(SPAN_PREFIX)]
    if not spans:
        raise ValueError("the trace holds none of the benchmark's spans")
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    window = [(a, b) for n, a, b in spans if n == WINDOW_SPAN]
    spans = [s for s in spans if s[0] != WINDOW_SPAN]
    if window:
        lo, hi = window[0]
    else:
        lo = min(a for _, a, _ in spans)
        hi = max(b for _, _, b in spans)
    chip_ids = sorted(devices)[:chips]

    busy_total = 0.0
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    modules: Dict[str, List[float]] = collections.defaultdict(lambda: [0.0, 0])
    first_busy: List[Tuple[int, int]] = []
    kernels = kernels or {}
    for i, dev in enumerate(chip_ids):
        evs = sorted((e for e in devices[dev].get(OPS_LINE, [])
                      if lo <= e[1] < hi), key=lambda e: (e[1], -e[2]))
        busy = _union(_clip([(a, b) for _, a, b in evs], lo, hi))
        busy_total += sum(b - a for a, b in busy)
        if i == 0:
            first_busy = busy
        mods = sorted((a, b, _module_base(n))
                      for n, a, b in devices[dev].get(MODULES_LINE, [])
                      if lo <= a < hi)
        for a, b, n in mods:
            modules[n][0] += (b - a) / 1e9
            modules[n][1] += 1
        j = 0
        for (n, a, b), self_ns in zip(evs, _self_times(evs)):
            while j < len(mods) and mods[j][1] <= a:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= a else "?"
            instr = _instr(n)
            key = f"{mod}:{kernels.get((mod, instr), instr)}"
            ops[key][0] += self_ns / 1e9
            ops[key][1] += 1

    idle = []
    edges = [lo] + [x for iv in first_busy for x in iv] + [hi]
    by_start = sorted(spans, key=lambda s: s[1])
    starts = [s[1] for s in by_start]
    longest = max(e - s for _, s, e in spans)
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            idle.append((_label(by_start, starts, longest, a, b),
                         (b - a) / 1e9))
    span_tot: Dict[str, List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    for n, a, b in spans:
        span_tot[n][0] += (b - a) / 1e9
        span_tot[n][1] += 1
    return Summary(window_s=(hi - lo) / 1e9,
                   busy_s=busy_total / len(chip_ids) / 1e9,
                   ops={k: (v[0], int(v[1])) for k, v in ops.items()},
                   modules={k: (v[0], int(v[1])) for k, v in modules.items()},
                   idle=idle,
                   spans={k: (v[0], int(v[1])) for k, v in span_tot.items()})


def _label(by_start, starts, longest: int, a: int, b: int) -> str:
    """The innermost (shortest) benchmark span covering most of [a, b)."""
    import bisect
    best, best_len = "host", None
    i = bisect.bisect_left(starts, b)
    while i > 0 and starts[i - 1] > a - longest:
        i -= 1
        n, s, e = by_start[i]
        cover = min(e, b) - max(s, a)
        if cover * 2 > (b - a) and (best_len is None or e - s < best_len):
            best, best_len = n, e - s
    return best

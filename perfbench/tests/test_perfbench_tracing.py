"""The reduction from a device trace to busy and idle time, device time by
op and program, kernel names, and the breakdown: on hand-made intervals,
and on a small trace recorded on a TPU v5e (`data/small.xplane.pb`, a
tiny federated cell of the benchmark, with the kernel map the compiled
round's HLO gave, `data/kernels.json`)."""
import json
import os

import pytest

from bench import tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_clip():
    assert tracing._union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tracing._clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_self_times_take_out_nested_ops():
    evs = [("loop", 0, 100), ("a", 10, 30), ("b", 40, 50), ("c", 120, 130)]
    assert tracing._self_times(evs) == [70, 20, 10, 10]


def test_idle_gaps_are_named_by_the_innermost_host_span():
    spans = sorted([("bench.chunk", 0, 1000), ("bench.data", 100, 200)],
                   key=lambda s: s[1])
    starts = [s[1] for s in spans]
    assert tracing._label(spans, starts, 1000, 110, 190) == "bench.data"
    assert tracing._label(spans, starts, 1000, 300, 400) == "bench.chunk"
    assert tracing._label(spans, starts, 1000, 2000, 2100) == "host"


def test_kernel_names_from_hlo_text():
    import base64
    body = base64.b64encode(b"\x00func.func\x00_bin_kernel\x00root").decode()
    text = ("HloModule jit_pfn, entry_computation_layout={}\n"
            "  %closed_call.3 = f32[8]{0} custom-call(f32[8]{0} %p), "
            'custom_call_target="tpu_custom_call", backend_config='
            '{"custom_call_config":{"body":"' + body + '"}}\n'
            "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop\n")
    assert tracing.kernel_names([text]) == {
        ("jit_pfn", "%closed_call.3"): "_bin_kernel"}


@pytest.fixture(scope="module")
def recorded():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "kernels.json")) as f:
        kernels = {(m, i): k for m, i, k in json.load(f)}
    return tracing.summarize(path, 1, kernels=kernels), kernels


def test_recorded_trace_busy_and_window(recorded):
    s, _ = recorded
    assert 0 < s.busy_s <= s.window_s
    own = sum(t for t, _ in s.ops.values())
    # top-level ops do not overlap on a chip: their self times add up to
    # the busy time
    assert own == pytest.approx(s.busy_s, rel=0.02)
    idle = sum(t for _, t in s.idle)
    assert idle + s.busy_s == pytest.approx(s.window_s, rel=1e-6)


def test_recorded_trace_names_kernels_and_gaps(recorded):
    s, kernels = recorded
    named = {k.split(":", 1)[1] for k in s.ops}
    assert set(kernels.values()) & named
    assert all(label == "host" or label.startswith("bench.")
               for label, _ in s.idle)
    assert "bench.round" in s.spans
    b = s.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])

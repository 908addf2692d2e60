"""Device time by the round program's named scopes (`bench/scopes.py`): the
scope of each HLO instruction, the share of a kernel that runs in two
scopes, the per-round reading, and the round as the tiny federated cell
compiles it.  Also pins every field `tracing.summarize` reads from the
recorded trace, which holds no program spans, so that the readers added
beside it are seen to leave it as it was."""
import base64
import json
import os
import re
import types

import pytest

from bench import scopes, tracing

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
NAMES = ("fedround.clients", "fedround.upload", "fedround.aggregate",
         "fedround.server_update")


def _kernel(instr: str, name: str, shape: str, op_name: str) -> str:
    body = base64.b64encode(
        b"\x00func.func\x00" + name.encode() + b"\x00root").decode()
    return (f"  {instr} = {shape} custom-call({shape} %p), "
            'custom_call_target="tpu_custom_call", backend_config='
            '{"custom_call_config":{"body":"' + body + '"}}, '
            f'metadata={{op_name="{op_name}"}}\n')


HLO = (
    "HloModule jit_pfn, entry_computation_layout={}\n"
    "\n"
    "%fused_computation.1 (param_0: f32[8]) -> f32[8] {\n"
    "  %param_0 = f32[8]{0} parameter(0)\n"
    "  ROOT %scatter.1 = f32[8]{0} scatter(f32[8]{0} %param_0), "
    'metadata={op_name="jit(pfn)/fedround.aggregate/scatter-add"}\n'
    "}\n"
    "\n"
    "%body.2 (bp: (s32[], f32[8])) -> (s32[], f32[8]) {\n"
    "  %bp = (s32[], f32[8]{0}) parameter(0)\n"
    "  %gte.1 = f32[8]{0} get-tuple-element((s32[], f32[8]{0}) %bp), index=1\n"
    "  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%gte.1)\n"
    "}\n"
    "\n"
    "ENTRY %main.3 (p: f32[8]) -> f32[8] {\n"
    "  %p = f32[8]{0} parameter(0)\n"
    "  %while.1 = (s32[], f32[8]{0}) while(%p), body=%body.2, "
    'metadata={op_name="jit(pfn)/fedround.clients/while"}\n'
    "  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop, "
    "calls=%fused_computation.1, "
    'metadata={op_name="jit(pfn)/fedround.clients/add"}\n'
    "  %reduce-window.1 = f32[8]{0} reduce-window(f32[8]{0} %fusion.1)\n"
    + _kernel("%dl.1", "_bin_kernel", "f32[8]{0}",
              "jit(pfn)/fedround.clients/pallas_call")
    + _kernel("%ul.1", "_bin_kernel", "f32[3,8]{1,0}",
              "jit(pfn)/fedround.clients/vmap(fedround.upload)/pallas_call")
    + "  ROOT %copy.1 = f32[8]{0} copy(f32[8]{0} %p)\n"
    "}\n")


def test_scopes_of_instructions():
    got = scopes.scopes([HLO])
    # a fusion takes its root's scope, not its own
    assert got[("jit_pfn", "%fusion.1")] == "fedround.aggregate"
    # a loop body's op without a scope takes the loop's
    assert got[("jit_pfn", "%gte.1")] == "fedround.clients"
    # an op the compiler made takes its operand's
    assert got[("jit_pfn", "%reduce-window.1")] == "fedround.aggregate"
    # the innermost of nested scopes
    assert got[("jit_pfn", "%ul.1")] == "fedround.upload"
    assert got[("jit_pfn", "%dl.1")] == "fedround.clients"
    # an entry parameter, and a copy of one, lie in no scope
    assert ("jit_pfn", "%copy.1") not in got
    assert scopes.scopes([HLO.replace("fedround.", "other.")]) == {}


def _summary(ops):
    return tracing.Summary(window_s=1.0, busy_s=sum(t for t, _ in ops.values()),
                           ops=ops, modules={}, idle=[], spans={})


def test_scope_seconds_shares_a_kernel_by_size():
    s = _summary({"jit_pfn:%fusion.1": (0.5, 1),
                  "jit_pfn:_bin_kernel": (0.8, 2),
                  "jit_pfn:%copy.1": (0.25, 1),
                  "jit_data:%fusion.1": (2.0, 1)})
    # the kernel's two calls: 8 elements in the download, 24 in the upload
    assert scopes.scope_seconds(s, [HLO], "fedround.clients") == \
        pytest.approx(0.8 * 8 / 32)
    assert scopes.scope_seconds(s, [HLO], "fedround.upload") == \
        pytest.approx(0.8 * 24 / 32)
    assert scopes.scope_seconds(s, [HLO], "fedround.aggregate") == 0.5
    assert scopes.scope_seconds(s, [HLO], "fedround.server_update") == 0.0
    assert scopes.scope_seconds(s, [HLO.replace("fedround.", "x.")],
                                "fedround.clients") is None


def test_per_round_reading():
    s = _summary({"jit_pfn:%fusion.1": (0.5, 1)})
    run = types.SimpleNamespace(kind="federated", trace=s, hlo_texts=[HLO],
                                traced={"units": 4.0})
    assert scopes.per_round_ms(run, "fedround.aggregate") == 125.0
    assert scopes.per_round_ms(
        types.SimpleNamespace(**dict(vars(run), kind="serving")),
        "fedround.aggregate") is None
    assert scopes.per_round_ms(
        types.SimpleNamespace(**dict(vars(run), trace=None)),
        "fedround.aggregate") is None


@pytest.fixture(scope="module")
def tiny_round_hlo():
    """The compiled round of the tiny federated cell, as a traced run
    reads it (`TracedSimEngine.hlo_text`)."""
    import perfbench_tiny as tiny

    class Capture:                          # the profiler's hooks, unused
        active = False

        def start(self):
            pass

        def stop(self):
            pass

    ctx = tiny.Context(seconds=0.2)
    ctx.capture = Capture()
    c = tiny.cell("flasc-fused")
    return c.runner().run(c, ctx).hlo_texts


def test_every_matmul_and_scatter_of_the_round_has_a_scope(tiny_round_hlo):
    got = scopes.scopes(tiny_round_hlo)
    (text,) = tiny_round_hlo
    module = re.search(r"^HloModule ([^\s,]+)", text, re.M).group(1)
    op = re.compile(r" = .*?\b(dot|scatter|custom-call)\(")
    seen = set()
    for line in text.splitlines():
        if " = " in line and op.search(line):
            instr = line.strip().split(" = ")[0].replace("ROOT ", "")
            assert got.get((module, instr)) in NAMES, line
            seen.add(got[(module, instr)])
    assert {"fedround.clients", "fedround.aggregate"} <= seen
    assert set(got.values()) == set(NAMES)


def test_recorded_trace_summary_is_unchanged():
    with open(os.path.join(DATA, "kernels.json")) as f:
        kernels = {(m, i): k for m, i, k in json.load(f)}
    s = tracing.summarize(os.path.join(DATA, "small.xplane.pb"), 1,
                          kernels=kernels)
    assert s.window_s == pytest.approx(0.019527899, rel=1e-12)
    assert s.busy_s == pytest.approx(0.004665963, rel=1e-12)
    assert (len(s.ops), len(s.modules), len(s.idle)) == (322, 4, 669)
    assert sum(n for _, n in s.ops.values()) == 1969
    assert s.ops["jit_pfn:_pack_batch_kernel"] == \
        pytest.approx((0.002538156, 4), rel=1e-9)
    assert s.ops["jit_pfn:_bin_kernel"] == \
        pytest.approx((0.001341266, 8), rel=1e-9)
    assert s.modules["jit_pfn"] == pytest.approx((0.004676986, 4), rel=1e-9)
    assert s.spans == pytest.approx({"bench.data": (0.00178273, 4),
                                     "bench.round": (0.00431143, 4),
                                     "bench.callback": (0.00015969, 3)},
                                    rel=1e-9)
    b = s.breakdown()
    assert [k for k, _ in b["device_ops"][:4]] == [
        "jit_pfn:_pack_batch_kernel", "jit_pfn:_bin_kernel",
        "jit_pfn:%fusion.3", "jit_pfn:%while.47"]
    assert dict(b["idle_gaps"]) == pytest.approx(
        {"host": 0.014379085, "bench.data": 0.000415772,
         "bench.round": 0.000067079}, rel=1e-6)

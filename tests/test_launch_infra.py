"""Launch-layer infrastructure: sharding rules, hloprof, roofline math,
comm-time model — pure unit tests (no multi-device lowering here)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, hst

from repro.launch import hloprof
from repro.launch.shardings import (DEFAULT_RULES, fsdp_rules,
                                    logical_to_pspec)
from jax.sharding import PartitionSpec


class FakeMesh:
    """Duck-typed mesh for rule tests (shape mapping only)."""
    def __init__(self, shape):
        self.shape = shape


def test_logical_to_pspec_divisibility_guard():
    mesh = FakeMesh({"data": 16, "model": 16})
    # divisible head dim shards; whisper's 20-head dim stays replicated
    assert logical_to_pspec((4096, 4096), ("embed", "heads"), mesh) == \
        PartitionSpec(None, "model")
    assert logical_to_pspec((1280, 1280), ("embed", "heads"), mesh) == \
        PartitionSpec(None, "model")
    assert logical_to_pspec((1280, 1290), ("embed", "heads"), mesh) == \
        PartitionSpec(None, None)


def test_logical_to_pspec_multi_axis_batch():
    mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
    spec = logical_to_pspec((256, 4096), ("batch", None), mesh)
    assert spec == PartitionSpec(("pod", "data"), None)
    # 16 can't shard over pod*data=32 -> replicated
    spec = logical_to_pspec((16, 4096), ("batch", None), mesh)
    assert spec == PartitionSpec(None, None)


def test_fsdp_rules_overlay():
    rules = fsdp_rules()
    assert rules["embed"] == ("pod", "data")
    assert DEFAULT_RULES["embed"] == ()


def test_hloprof_counts_scan_trips():
    def g(x):
        def body(c, _):
            return c @ x, None
        y, _ = jax.lax.scan(body, jnp.eye(128), None, length=5)
        return y
    c = jax.jit(g).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    p = hloprof.profile(c.as_text(), 1)
    assert p["dot_flops"] == pytest.approx(5 * 2 * 128 ** 3, rel=0.01)


def test_hloprof_nested_loops():
    def g(x):
        def outer(c, _):
            def inner(c2, _):
                return c2 @ x, None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        y, _ = jax.lax.scan(outer, jnp.eye(64), None, length=4)
        return y
    c = jax.jit(g).lower(jax.ShapeDtypeStruct((64, 64), jnp.float32)).compile()
    p = hloprof.profile(c.as_text(), 1)
    assert p["dot_flops"] == pytest.approx(12 * 2 * 64 ** 3, rel=0.01)


def test_hloprof_dot_traffic_not_degenerate():
    """Traffic must be operands+result bytes, never a round multiple of
    flops — the 2x signature meant operand parsing silently failed."""
    def g(x):
        def body(c, _):
            return c @ x, None
        y, _ = jax.lax.scan(body, jnp.eye(128), None, length=5)
        return y
    c = jax.jit(g).lower(jax.ShapeDtypeStruct((128, 128), jnp.float32)).compile()
    p = hloprof.profile(c.as_text(), 1)
    # per trip: lhs + rhs + result, each 128*128*f32
    assert p["dot_traffic_bytes"] == pytest.approx(5 * 3 * 128 * 128 * 4, rel=0.01)
    for k in (1.0, 2.0, 4.0):
        assert p["dot_traffic_bytes"] != pytest.approx(k * p["dot_flops"], rel=1e-6)


def test_hloprof_unparseable_dot_raises():
    """A dot whose operands/contracting dims can't be parsed must raise, not
    silently fall back to contract=1 (that under-counted flops ~1000x)."""
    comp = hloprof.Computation("c", [], {})
    op = hloprof.Op("dot.1", "dot", "f32[8,8]{1,0}",
                    "dot(%mystery.1, %mystery.2), metadata={}")
    with pytest.raises(ValueError):
        hloprof._dot_flops(comp, op)


def test_hloprof_bf16_upcast_detection():
    """CPU materializes f32 copies of bf16 dot inputs; cpu_upcast_bytes must
    see them, whether or not the fusion call prints its operand types."""
    def g(x, w):
        return (x @ w).astype(jnp.bfloat16)
    args = (jax.ShapeDtypeStruct((64, 64), jnp.bfloat16),
            jax.ShapeDtypeStruct((64, 64), jnp.bfloat16))
    c = jax.jit(g).lower(*args).compile()
    up = hloprof.cpu_upcast_bytes(c.as_text())
    # at least the two 64x64 f32 operand upcasts
    assert up >= 2 * 64 * 64 * 4


INLINE_TYPED_UPCAST_HLO = """
%parallel_convert (p: bf16[8,16]) -> f32[8,16] {
  %p = bf16[8,16]{1,0} parameter(0)
  ROOT %c = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %p)
}
%softmax (q: bf16[8,16]) -> f32[8,16] {
  %q = bf16[8,16]{1,0} parameter(0)
  ROOT %e = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %q)
}
ENTRY %main (x: bf16[8,16], w: bf16[8,16]) -> f32[8,16] {
  %x = bf16[8,16]{1,0} parameter(0)
  %w = bf16[8,16]{1,0} parameter(1)
  %up = f32[8,16]{1,0} call(bf16[8,16]{1,0} %x), to_apply=%parallel_convert
  %cv = f32[8,16]{1,0} convert(bf16[8,16]{1,0} %w)
  %sm = f32[8,16]{1,0} fusion(bf16[8,16]{1,0} %w), kind=kLoop, calls=%softmax
  ROOT %add = f32[8,16]{1,0} add(f32[8,16]{1,0} %up, f32[8,16]{1,0} %cv)
}
"""


def test_hloprof_bf16_upcast_detection_inline_typed_operands():
    """Printers that type each call operand inline: the convert wrapper and
    the plain convert count, and a non-convert fusion that widens bf16 does
    not."""
    assert hloprof.cpu_upcast_bytes(INLINE_TYPED_UPCAST_HLO) == 2 * 8 * 16 * 4


def test_dryrun_sanity_check():
    from repro.launch.dryrun import sanity_check
    good = {"flops": 1e14, "xla_flops_raw": 7e12, "dot_traffic_bytes": 9.7e11,
            "dot_ops": 2112, "max_while_trips": 34.0, "while_ops": 6.0}
    assert sanity_check(good) == []
    undercount = dict(good, flops=1.6e11, dot_traffic_bytes=3.2e11)
    probs = sanity_check(undercount)
    assert any("under-counting" in p for p in probs)
    degenerate = dict(good, dot_traffic_bytes=2.0 * good["flops"])
    probs = sanity_check(degenerate)
    assert any("signature" in p for p in probs)
    # a regressed trip parser reports 1 trip everywhere — that must itself
    # trip the gate, not silently disarm the under-count check
    broken_trips = dict(good, max_while_trips=1.0)
    probs = sanity_check(broken_trips)
    assert any("trip parser" in p for p in probs)


def test_dryrun_sanity_ignores_loop_free_modules():
    """Loop-free graphs legitimately have dot flops below XLA's total (which
    counts elementwise work too) — the under-count gate must not fire.
    max_while_trips must be real while trips, not call-graph multiplicity."""
    from repro.launch.dryrun import sanity_check

    def g(x, w):
        y = x @ w
        return jnp.sum(y) + jnp.sum(x)

    args = (jax.ShapeDtypeStruct((64, 64), jnp.float32),) * 2
    c = jax.jit(g).lower(*args).compile()
    p = hloprof.profile(c.as_text(), 1)
    assert p["max_while_trips"] == 1.0
    assert p["while_ops"] == 0.0
    stats = {"flops": p["dot_flops"], "xla_flops_raw": p["dot_flops"] * 1.1,
             "dot_traffic_bytes": p["dot_traffic_bytes"],
             "dot_ops": p["dot_ops"], "max_while_trips": p["max_while_trips"],
             "while_ops": p["while_ops"]}
    assert sanity_check(stats) == []


def test_roofline_rejects_impossible_ratio():
    from repro.launch.roofline import analyse
    d = {"status": "OK", "arch": "minitron-8b", "shape": "train_4k",
         "chips": 256, "flops": 1.6e11, "dot_traffic_bytes": 3.2e11,
         "collective_bytes": 1.3e11, "cpu_upcast_bytes": 0}
    with pytest.raises(ValueError, match="useful_ratio"):
        analyse(d)


def test_hloprof_sort_accounting():
    c = jax.jit(jnp.sort).lower(jax.ShapeDtypeStruct((4096,), jnp.float32)).compile()
    p = hloprof.profile(c.as_text(), 1)
    assert p["sort_ops"] >= 1
    assert p["sort_bytes"] >= 4096 * 4


def test_roofline_model_flops_sanity():
    from repro.launch.roofline import model_flops
    # train: 6*N*D within 2x of the closed form for a dense arch
    mf = model_flops("minitron-8b", "train_4k")
    from repro.configs.registry import get_config
    from repro.models.model import count_params
    n = count_params(get_config("minitron-8b"))
    assert mf == pytest.approx(6 * n * 256 * 4096, rel=1e-6)
    # MoE uses active params only
    mf3 = model_flops("deepseek-v3-671b", "train_4k")
    assert mf3 < 6 * count_params(get_config("deepseek-v3-671b")) * 256 * 4096 * 0.2


@settings(deadline=None, max_examples=20)
@given(hst.integers(2, 512))
def test_collective_factors(n):
    assert 0 < hloprof._coll_factor("all-gather", n) < 1
    assert hloprof._coll_factor("all-reduce", n) == pytest.approx(
        2 * (n - 1) / n)
    assert hloprof._coll_factor("collective-permute", n) == 1.0
    assert hloprof._coll_factor("all-gather", 1) == 0.0


def test_fed_for_mesh():
    from repro.launch.steps import fed_for_mesh
    from repro.models.config import INPUT_SHAPES
    mesh1 = FakeMesh({"data": 16, "model": 16})
    fed = fed_for_mesh(mesh1, INPUT_SHAPES["train_4k"])
    assert fed.n_clients * fed.local_batch == 256
    assert fed.n_clients == 16
    mesh2 = FakeMesh({"pod": 2, "data": 16, "model": 16})
    fed2 = fed_for_mesh(mesh2, INPUT_SHAPES["train_4k"])
    assert fed2.n_clients == 32 and fed2.local_batch == 8


# ---------------------------------------------------------------------------
# dryrun failure channels
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_dryrun_hloprof_suspect_stats():
    """An hloprof parse failure is a SUSPECT artifact (the compile
    succeeded), carrying the compile-side facts plus the parse error."""
    from repro.launch import dryrun
    stats = dryrun._hloprof_suspect(
        {"arch": "a", "shape": "s", "mesh": {"data": 2}, "chips": 2,
         "compile_s": 1.5}, ValueError("cannot parse operand"))
    assert stats["status"] == "SUSPECT"
    assert stats["sanity"] == ["hloprof parse failed: cannot parse operand"]
    assert stats["chips"] == 2 and stats["compile_s"] == 1.5


@pytest.mark.fast
def test_dryrun_main_exception_narrowing(tmp_path, monkeypatch):
    """main() catches only the concrete lowering/compile failure modes
    (written as FAIL artifacts); anything outside that set — and the
    SUSPECT stats lower_combo returns for hloprof parse errors — takes
    its own channel instead of vanishing into a blanket except."""
    import json
    import sys

    from repro.configs.registry import ARCH_IDS
    from repro.launch import dryrun
    from repro.models.config import INPUT_SHAPES

    arch, shape = ARCH_IDS[0], next(iter(INPUT_SHAPES))
    out = tmp_path / "dryrun"
    monkeypatch.setattr(dryrun, "make_production_mesh",
                        lambda multi_pod=False: None)
    monkeypatch.setattr(sys, "argv", ["dryrun", "--arch", arch, "--shape",
                                      shape, "--out", str(out)])
    artifact = out / f"{arch}__{shape}__pod1.json"

    def raising(exc):
        def fn(*a, **k):
            raise exc
        return fn

    # a concrete failure type -> FAIL artifact + nonzero exit
    monkeypatch.setattr(dryrun, "lower_combo",
                        raising(ValueError("sharding mismatch")))
    with pytest.raises(SystemExit, match="1 combos failed"):
        dryrun.main()
    stats = json.loads(artifact.read_text())
    assert stats["status"] == "FAIL"
    assert "ValueError: sharding mismatch" in stats["error"]

    # hloprof parse failures surface through the SUSPECT/sanity channel
    monkeypatch.setattr(
        dryrun, "lower_combo",
        lambda *a, **k: dryrun._hloprof_suspect(
            {"arch": arch, "shape": shape, "mesh": {}, "chips": 1,
             "compile_s": 0.1}, ValueError("bad dot")))
    with pytest.raises(SystemExit, match="1 combos failed"):
        dryrun.main()
    stats = json.loads(artifact.read_text())
    assert stats["status"] == "SUSPECT"
    assert "hloprof parse failed: bad dot" in stats["sanity"][0]

    # anything outside the concrete set still crashes the sweep loudly
    monkeypatch.setattr(dryrun, "lower_combo", raising(KeyboardInterrupt()))
    with pytest.raises(KeyboardInterrupt):
        dryrun.main()

"""repro-lint suite tests: every rule has a bad fixture proving it
fires and a good fixture proving it stays silent, plus the suppression
mechanism, the registry contract (deleting a rule fails here), and the
baseline gate (a fresh run over src/ + tests/ must exactly match
tools/reprolint/baseline.json, with zero entries in core/ or
federated/)."""
import os
import subprocess
import sys
import textwrap

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.reprolint import core  # noqa: E402

pytestmark = pytest.mark.fast


def lint(src, rule, rel="src/repro/fake/mod.py", **kw):
    return core.lint_sources({rel: textwrap.dedent(src)}, [rule], **kw)


# ---------------------------------------------------------------------------
# tracer hygiene
# ---------------------------------------------------------------------------

def test_host_sync_in_traced_fires():
    bad = """
        import jax

        @jax.jit
        def step(x):
            y = float(x) + 1.0
            x.block_until_ready()
            return y
    """
    found = lint(bad, "host-sync-in-traced")
    assert [f.rule for f in found] == ["host-sync-in-traced"] * 2
    assert "float()" in found[0].message


def test_host_sync_in_traced_silent_on_static_and_host_code():
    good = """
        import jax

        @jax.jit
        def step(x):
            return x * float(x.shape[0])

        def host_side(x):
            return float(x)
    """
    assert lint(good, "host-sync-in-traced") == []


def test_host_pull_in_loop_fires():
    bad = """
        def drain(xs, ys):
            out = []
            for i in range(3):
                out.append(float(xs[i]))
            out += [float(v) for v in ys]
            return out
    """
    found = lint(bad, "host-pull-in-loop", rel="src/repro/federated/f.py")
    assert len(found) == 2
    assert all(f.rule == "host-pull-in-loop" for f in found)


def test_host_pull_in_loop_silent_on_host_arrays():
    good = """
        import numpy as np

        def drain(xs):
            host = np.asarray(xs)
            out = [float(v) for v in host]
            for i in range(3):
                out.append(float(host[i]))
            return out
    """
    assert lint(good, "host-pull-in-loop",
                rel="src/repro/federated/f.py") == []


# ---------------------------------------------------------------------------
# PRNG discipline
# ---------------------------------------------------------------------------

def test_prng_constant_key_fires():
    bad = """
        import jax

        def apply_round(params):
            key = jax.random.key(0)
            return jax.random.normal(key, (2,))
    """
    found = lint(bad, "prng-constant-key")
    assert len(found) == 1
    assert "apply_round" in found[0].message


def test_prng_constant_key_silent_when_folded():
    good = """
        import jax

        def apply_round(params, r):
            key = jax.random.fold_in(jax.random.key(0), r)
            return jax.random.normal(key, (2,))

        def apply_round_bound(params, r):
            base = jax.random.key(7)
            key = jax.random.fold_in(base, r)
            return jax.random.normal(key, (2,))
    """
    assert lint(good, "prng-constant-key") == []


def test_prng_key_reuse_fires():
    bad = """
        import jax

        def draw(key):
            a = jax.random.normal(key, (2,))
            b = jax.random.uniform(key, (2,))
            return a + b
    """
    found = lint(bad, "prng-key-reuse")
    assert len(found) == 1
    assert "key `key`" in found[0].message


def test_prng_key_reuse_silent_after_split():
    good = """
        import jax

        def draw(key):
            ka, kb = jax.random.split(key)
            a = jax.random.normal(ka, (2,))
            b = jax.random.uniform(kb, (2,))
            return a + b
    """
    assert lint(good, "prng-key-reuse") == []


# ---------------------------------------------------------------------------
# bit-exactness
# ---------------------------------------------------------------------------

def test_host_reduction_fires():
    bad = """
        def record(losses):
            return sum(losses) / len(losses)
    """
    found = lint(bad, "host-reduction", rel="src/repro/federated/f.py")
    assert len(found) == 1
    assert "_mean_f32" in found[0].message


def test_host_reduction_silent_on_int_accounting_and_other_paths():
    good = """
        def count(xs):
            return int(sum(len(x) for x in xs))
    """
    assert lint(good, "host-reduction", rel="src/repro/federated/f.py") == []
    # launch/ is outside the metric paths entirely
    bad_elsewhere = "def m(xs):\n    return sum(xs)\n"
    assert lint(bad_elsewhere, "host-reduction",
                rel="src/repro/launch/f.py") == []


def test_unordered_pytree_fires():
    bad = """
        import jax.numpy as jnp

        def build(xs):
            return jnp.stack([x for x in set(xs)])
    """
    found = lint(bad, "unordered-pytree")
    assert len(found) == 1
    assert "hash-seed" in found[0].message


def test_unordered_pytree_silent_when_sorted():
    good = """
        import jax.numpy as jnp

        def build(xs):
            return jnp.stack([x for x in sorted(set(xs))])
    """
    assert lint(good, "unordered-pytree") == []


# ---------------------------------------------------------------------------
# registry contracts (project scope)
# ---------------------------------------------------------------------------

STRAT = """
    def register_strategy(name):
        def deco(cls):
            return cls
        return deco

    @register_strategy("test-strat")
    class TestStrat:
        pass
"""


def test_registry_coverage_fires():
    found = lint(STRAT, "registry-coverage", docs_text="", tests_text="")
    assert len(found) == 2
    msgs = " ".join(f.message for f in found)
    assert "not mentioned" in msgs and "not exercised" in msgs


def test_registry_coverage_silent_when_documented_and_tested():
    assert lint(STRAT, "registry-coverage",
                docs_text="the test-strat strategy",
                tests_text="resolve('test-strat')") == []


def test_stage_wire_fires():
    bad = """
        @register_stage("noop")
        class Noop:
            pass
    """
    found = lint(bad, "stage-wire", docs_text="noop", tests_text="noop")
    assert len(found) == 1
    assert "wire" in found[0].message


def test_stage_wire_silent_with_explicit_wire():
    good = """
        @register_stage("noop")
        class Noop:
            def wire(self, n, value_bits, dense):
                return value_bits, dense
    """
    assert lint(good, "stage-wire", docs_text="x", tests_text="x") == []


def test_fused_stage_wire_fires_on_identity_wire():
    # fuses quantization (has `bits`) but bills the un-narrowed width
    bad = """
        @register_stage("fused_fake")
        class FusedFake:
            bits: int = 4

            def wire(self, n, value_bits, dense):
                return value_bits, dense
    """
    found = lint(bad, "fused-stage-wire",
                 docs_text="fused_fake", tests_text="fused_fake")
    assert len(found) == 1
    assert "never reads it" in found[0].message


def test_fused_stage_wire_fires_on_missing_wire():
    bad = """
        @register_stage("fused_fake")
        class FusedFake:
            bits: int = 4
    """
    found = lint(bad, "fused-stage-wire",
                 docs_text="fused_fake", tests_text="fused_fake")
    assert len(found) == 1
    assert "does not declare" in found[0].message


def test_fused_stage_wire_silent_when_wire_reads_bits():
    good = """
        @register_stage("fused_fake")
        class FusedFake:
            bits: int = 4

            def wire(self, n, value_bits, dense):
                if 0 < self.bits < 32:
                    return float(self.bits), dense
                return value_bits, dense
    """
    assert lint(good, "fused-stage-wire",
                docs_text="x", tests_text="x") == []


def test_fused_stage_wire_ignores_unquantized_stages():
    # no `bits` field -> not a fusing stage; stage-wire's jurisdiction
    plain = """
        @register_stage("plain")
        class Plain:
            density: float = 0.1

            def wire(self, n, value_bits, dense):
                return value_bits, dense
    """
    assert lint(plain, "fused-stage-wire",
                docs_text="x", tests_text="x") == []


def test_engine_config_fires():
    missing_config = """
        @register_engine("fake")
        class FakeEngine:
            def __init__(self, lr):
                self.lr = lr
    """
    found = lint(missing_config, "engine-config")
    assert len(found) == 1 and "does not define config()" in found[0].message

    missing_param = """
        @register_engine("fake")
        class FakeEngine:
            def __init__(self, lr):
                self.lr = lr

            def config(self):
                return {}
    """
    found = lint(missing_param, "engine-config")
    assert len(found) == 1 and "['lr']" in found[0].message


def test_engine_config_silent_when_round_trippable():
    good = """
        @register_engine("fake")
        class FakeEngine:
            def __init__(self, lr):
                self.lr = lr

            def config(self):
                return {"lr": self.lr}
    """
    assert lint(good, "engine-config") == []


# ---------------------------------------------------------------------------
# pallas kernel contracts
# ---------------------------------------------------------------------------

KREL = "src/repro/kernels/fake.py"


def test_pallas_raw_index_fires():
    bad = """
        from jax.experimental import pallas as pl

        def kernel(ref, out):
            i = 0
            x = pl.load(ref, (i, slice(None)))
            pl.store(out, (pl.ds(i, 1), slice(None)), x)
    """
    found = lint(bad, "pallas-raw-index", rel=KREL)
    assert len(found) == 1
    assert "pl.ds" in found[0].message


def test_pallas_raw_index_silent_with_ds():
    good = """
        from jax.experimental import pallas as pl

        def kernel(ref, out):
            i = 0
            x = pl.load(ref, (pl.ds(i, 1), slice(None)))
            pl.store(out, (pl.ds(i, 1), ...), x)
    """
    assert lint(good, "pallas-raw-index", rel=KREL) == []


def test_pallas_interpret_fires():
    bad = """
        from jax.experimental import pallas as pl

        def run(kernel, x):
            return pl.pallas_call(kernel, out_shape=x)(x)
    """
    found = lint(bad, "pallas-interpret", rel=KREL)
    assert len(found) == 1


def test_pallas_interpret_silent_with_kwarg():
    good = """
        from jax.experimental import pallas as pl

        def run(kernel, x, interpret):
            return pl.pallas_call(kernel, out_shape=x,
                                  interpret=interpret)(x)
    """
    assert lint(good, "pallas-interpret", rel=KREL) == []


def test_pallas_grid_guard_fires():
    bad = """
        from jax.experimental import pallas as pl

        def run(kernel, x, n, b):
            return pl.pallas_call(kernel, grid=(n // b,),
                                  interpret=True)(x)
    """
    found = lint(bad, "pallas-grid-guard", rel=KREL)
    assert len(found) == 1
    assert "tail block" in found[0].message


def test_pallas_grid_guard_silent_with_assert():
    good = """
        from jax.experimental import pallas as pl

        def run(kernel, x, n, b):
            assert n % b == 0, "pad upstream"
            grid = n // b
            return pl.pallas_call(kernel, grid=(grid,),
                                  interpret=True)(x)
    """
    assert lint(good, "pallas-grid-guard", rel=KREL) == []


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------

def test_jit_no_donate_fires():
    bad = """
        import jax

        def compile_step(fn, sharding, loss):
            sharded = jax.jit(fn, in_shardings=sharding)
            stepped = jax.jit(make_round_fn(loss))
            return sharded, stepped
    """
    found = lint(bad, "jit-no-donate")
    assert len(found) == 2
    assert "in_shardings" in found[0].message
    assert "make_round_fn" in found[1].message


def test_jit_no_donate_silent_when_donating():
    good = """
        import jax

        def compile_step(fn, sharding, loss):
            sharded = jax.jit(fn, in_shardings=sharding,
                              donate_argnums=(0,))
            stepped = jax.jit(make_round_fn(loss), donate_argnums=0)
            plain = jax.jit(fn)
            return sharded, stepped, plain
    """
    assert lint(good, "jit-no-donate") == []


def test_use_after_donate_fires():
    bad = """
        import jax

        def run(g, x):
            f = jax.jit(g, donate_argnums=0)
            y = f(x)
            return x + y
    """
    found = lint(bad, "use-after-donate")
    assert len(found) == 1
    assert "`x` was donated" in found[0].message


def test_use_after_donate_silent_when_rebound():
    good = """
        import jax

        def run(g, x):
            f = jax.jit(g, donate_argnums=0)
            x = f(x)
            return x
    """
    assert lint(good, "use-after-donate") == []


def test_params_closure_fires():
    bad = """
        def make_round_fn(params, loss):
            def round_fn(flatP, server, batch):
                return loss(params, flatP, batch)
            return round_fn
    """
    found = lint(bad, "params-closure", rel="src/repro/federated/fake.py")
    assert len(found) == 1
    assert "`round_fn` closes over `params`" in found[0].message
    assert "pfn(params, flatP, ...)" in found[0].message


def test_params_closure_silent_on_explicit_argument_and_scope():
    good = """
        def make_round_fn(loss):
            def round_fn(params, flatP, server, batch):
                return loss(params, flatP, batch)
            return round_fn

        def round_stats(history):
            params = {"n": len(history)}   # locally bound, not a closure
            return params

        def summarize(params):             # not a step/round/phase name
            def helper():
                return params
            return helper
    """
    assert lint(good, "params-closure",
                rel="src/repro/federated/fake.py") == []
    # scoped to the engine trees: models/ et al. are exempt
    bad_elsewhere = """
        def round_fn(x):
            return params
    """
    assert lint(bad_elsewhere, "params-closure",
                rel="src/repro/models/fake.py") == []


# ---------------------------------------------------------------------------
# framework: suppressions, registry, baseline
# ---------------------------------------------------------------------------

def test_suppression_comment_silences_named_rule():
    src = """
        def record(losses):
            return sum(losses) / len(losses)  # reprolint: disable=host-reduction -- fixture
    """
    assert lint(src, "host-reduction", rel="src/repro/federated/f.py") == []
    src_all = """
        def record(losses):
            return sum(losses) / len(losses)  # reprolint: disable=all -- fixture
    """
    assert lint(src_all, "host-reduction",
                rel="src/repro/federated/f.py") == []


def test_suppression_does_not_leak_to_other_rules():
    src = """
        def record(losses):
            return sum(losses) / len(losses)  # reprolint: disable=unordered-pytree -- wrong rule
    """
    assert len(lint(src, "host-reduction",
                    rel="src/repro/federated/f.py")) == 1


def test_rule_registry_is_complete():
    # deleting (or renaming) any rule must fail this test: the docs rule
    # table and this tuple are both checked against the live registry
    assert core.registered_rules() == (
        "engine-config",
        "fused-stage-wire",
        "host-pull-in-loop",
        "host-reduction",
        "host-sync-in-traced",
        "jit-no-donate",
        "pallas-grid-guard",
        "pallas-interpret",
        "pallas-raw-index",
        "params-closure",
        "prng-constant-key",
        "prng-key-reuse",
        "registry-coverage",
        "stage-wire",
        "unordered-pytree",
        "use-after-donate",
    )


def test_resolve_rule_unknown_name():
    with pytest.raises(KeyError, match="no lint rule registered"):
        core.resolve_rule("no-such-rule")


def test_baseline_exactly_matches_fresh_run():
    """The checked-in baseline is a snapshot, not an allowlist: a fresh
    lint over src/ + tests/ must produce exactly the baselined findings
    (no new, no stale), and none may live in core/ or federated/ — those
    trees are lint-clean by acceptance criteria."""
    _, findings = core.lint_paths(["src", "tests"])
    baseline = core.load_baseline(core.DEFAULT_BASELINE)
    new, stale = core.diff_baseline(findings, baseline)
    assert new == [] and stale == []
    dirty = [b for b in baseline
             if b.path.startswith(("src/repro/core/",
                                   "src/repro/federated/"))]
    assert dirty == []


def test_cli_gate_passes_on_repo():
    """`python -m tools.reprolint src tests` is the CI gate; it must
    exit 0 on the current tree."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.reprolint", "src", "tests"],
        cwd=core.ROOT, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "OK" in proc.stdout

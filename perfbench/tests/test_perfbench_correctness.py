"""What decides `correct`, at a size a CPU test run holds.

The control (the plain reference computed in fp8, put in the program's
place) reads well above the program on the numbers compared; and a run
driven through the harness, with the timed path broken underneath, comes
out not correct: a step that returns its state unchanged, half of the
cohort left out with the mean taken over the rest, a served token altered
where it is produced, and a decode step that keeps its cache unchanged."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
import perfbench_tiny as tiny
from bench.runners import serving as serving_runner
from repro.federated import engine as eng
from repro.serving import ServingEngine

TRAIN_LIMITS = {"loss_gap": 1e-3, "grad_gap": 0.05, "change_gap": 0.05,
                "ledger_gap": 0.05}
SERVE_LIMITS = {"logit_gap": 0.05}


@pytest.fixture(scope="module")
def flasc():
    cell = tiny.cell("flasc-fused", TRAIN_LIMITS)
    return cell, tiny.run(cell)


@pytest.fixture(scope="module")
def serve():
    cell = tiny.cell("serve-zipf", SERVE_LIMITS)
    return cell, tiny.run(cell)


def test_sound_runs_are_correct(flasc, serve):
    for _, run in (flasc, serve):
        assert run.correct, [(c.name, c.value, c.limit) for c in run.checks]
        assert run.attempted > 0 and run.failed == 0


def test_training_control_reads_above_the_program(flasc):
    cell, run = flasc
    r = calibrate.readings(cell, tiny.Context().seed, run)
    prog = {c.name: c.value for c in run.checks}
    ratios = [r["control"][k] / max(prog[k], 1e-12)
              for k in ("loss_gap", "change_gap")]
    assert max(ratios) >= 3.0, (r["control"], prog)
    # half of the cohort left out, planted in the reference
    assert r["half_cohort"]["ledger_gap"] == pytest.approx(0.5)
    assert r["half_cohort"]["grad_gap"] > 10 * prog["grad_gap"]


def test_serving_control_reads_above_the_program(serve):
    cell, run = serve
    r = calibrate.readings(cell, tiny.Context().seed, run)
    served = max(run.notes["gaps"])
    assert r["control"]["logit_gap"] > 3 * max(served, 1e-3)


def _broken_step(monkeypatch, fault):
    orig = eng.SimEngine.compile

    def compile(self, plan):
        step = orig(self, plan)

        def broken(params, flatP, server, sstate, batch, key):
            if fault == "unchanged":
                out = step(params, flatP, server, sstate, batch, key)
                return (flatP, server, sstate) + tuple(out[3:])
            half = jax.tree.map(lambda x: x[:x.shape[0] // 2], batch)
            return step(params, flatP, server, sstate, half, key)
        return broken

    monkeypatch.setattr(eng.SimEngine, "compile", compile)


@pytest.mark.parametrize("fault", ["unchanged", "half_cohort"])
def test_broken_training_step_is_not_correct(monkeypatch, fault):
    _broken_step(monkeypatch, fault)
    run = tiny.run(tiny.cell("flasc-fused", TRAIN_LIMITS))
    assert not run.correct, [(c.name, c.value) for c in run.checks]


@pytest.mark.parametrize("fault", ["token_altered", "cache_unchanged"])
def test_broken_decode_is_not_correct(monkeypatch, fault):
    orig_init = ServingEngine.__init__

    def init(self, *a, **kw):
        orig_init(self, *a, **kw)
        decode = self._decode
        calls = [0]

        def broken(params, pool, cache, tokens, pos, gidx):
            out, new_cache = decode(params, pool, cache, tokens, pos, gidx)
            calls[0] += 1
            if fault == "cache_unchanged":
                return out, cache
            if calls[0] % 5 == 2:          # now and then, every lane's token
                out = (out + 1) % self.cfg.vocab_size
            return out, new_cache
        self._decode = broken

    monkeypatch.setattr(ServingEngine, "__init__", init)
    run = tiny.run(tiny.cell("serve-zipf", SERVE_LIMITS))
    assert not run.correct, [(c.name, c.value) for c in run.checks]


def test_check_sample_takes_the_longest():
    served = [{"prompt_len": p, "gen_len": g, "tokens": [0] * g}
              for p, g in [(8, 4), (16, 12), (8, 12), (16, 4)]]
    pick = serving_runner.check_sample(served, 5, 2)
    assert pick[0]["prompt_len"] + pick[0]["gen_len"] == 28
    assert len(pick) == 2
    _ = (jnp, np)

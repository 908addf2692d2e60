"""AsyncEngine sync-equivalence anchors: with full concurrency, a full
buffer, and a uniform `ClientSystemProfile` (the defaults), the async
backend reproduces SimEngine bit for bit — history records, final weights,
strategy state, eval accuracy, and ledger totals — for the 8 paper strategy
kinds plus the `flocora` and `two_stage_ortho` baselines, for an odd
cohort, for phased strategies, and under a full-participation sampler.

The sparse-aggregation and weighted paths are in test_async_sparse.py; the
genuinely-async event queue (staleness, checkpoint/resume, partial
participation, fig3) is in test_async_events.py.
"""
import numpy as np
import pytest

from _fedexp import sim_run, task  # noqa: F401  (module fixtures)
from _fedexp import (KIND_KWARGS, LEDGER_ATTRS, N_CLIENTS, CaptureState,
                    experiment, strip_async)


# ---------------------------------------------------------------------------
# the sync-equivalence anchor
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", sorted(KIND_KWARGS))
def test_async_defaults_reduce_to_sim_engine_bit_for_bit(task, sim_run, kind):
    res_sim, cap_sim = sim_run(kind)
    cap_async = CaptureState()
    res_async = (experiment(task, kind, **KIND_KWARGS[kind])
                 .with_engine("async").with_callbacks(cap_async).run())

    assert len(res_async.history) == len(res_sim.history)
    for rec_a, rec_s in zip(res_async.history, res_sim.history):
        assert strip_async(rec_a) == rec_s, rec_s["round"]
        assert rec_a["staleness"] == 0.0    # full fresh cohorts only
        assert rec_a["applied"] == N_CLIENTS
    assert res_async.final_acc == res_sim.final_acc
    for attr in LEDGER_ATTRS:
        assert getattr(res_async.ledger, attr) == \
            getattr(res_sim.ledger, attr), attr
    np.testing.assert_array_equal(cap_async.flatP, cap_sim.flatP)
    assert len(cap_async.sstate_leaves) == len(cap_sim.sstate_leaves)
    for a, b in zip(cap_async.sstate_leaves, cap_sim.sstate_leaves):
        np.testing.assert_array_equal(a, b)


def test_async_equivalence_holds_for_odd_cohort(task):
    """Non-power-of-two cohorts exercise the canonical host reductions
    (XLA's fused means are association-dependent there)."""
    res_sim = experiment(task, "hetlora",
                         hetlora_ranks=(1, 2, 4)).with_federation(
                              n_clients=3, local_batch=4).run()
    res_async = (experiment(task, "hetlora", hetlora_ranks=(1, 2, 4))
                 .with_federation(n_clients=3, local_batch=4)
                 .with_engine("async").run())
    for rec_a, rec_s in zip(res_async.history, res_sim.history):
        assert strip_async(rec_a) == rec_s


# ---------------------------------------------------------------------------
# PR 9 anchors: phased strategies and cohort samplers under AsyncEngine
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("phase_len", [2, 3])
def test_two_stage_ortho_phases_match_sim_bit_for_bit(task, phase_len):
    """phase_len > 1 must produce the same phase schedule (and hence the
    same weights) on both engines; the phase index derives from the server
    round counter, which AsyncEngine advances once per aggregation."""
    cap_sim, cap_async = CaptureState(), CaptureState()
    res_sim = (experiment(task, "two_stage_ortho", rounds=6,
                          phase_len=phase_len)
               .with_callbacks(cap_sim).run())
    res_async = (experiment(task, "two_stage_ortho", rounds=6,
                            phase_len=phase_len)
                 .with_engine("async").with_callbacks(cap_async).run())
    for rec_a, rec_s in zip(res_async.history, res_sim.history):
        assert strip_async(rec_a) == rec_s, rec_s["round"]
    np.testing.assert_array_equal(cap_async.flatP, cap_sim.flatP)


def test_two_stage_ortho_phase_len_changes_trajectory(task):
    """Sanity: the schedule knob is live (L=3 differs from L=1)."""
    res_1 = experiment(task, "two_stage_ortho", rounds=6, phase_len=1).run()
    res_3 = experiment(task, "two_stage_ortho", rounds=6, phase_len=3).run()
    assert [r["loss"] for r in res_1.history] != \
        [r["loss"] for r in res_3.history]


def test_async_full_participation_sampler_reduces_to_sim(task, sim_run):
    """A fraction sampler at participation=1.0 gates nothing, so the async
    run must stay bit-identical to the sim engine."""
    res_sim, _ = sim_run("flasc")
    res_async = (experiment(task, "flasc")
                 .with_engine("async",
                              sampler={"kind": "fraction",
                                       "participation": 1.0}).run())
    for rec_a, rec_s in zip(res_async.history, res_sim.history):
        assert strip_async(rec_a) == rec_s, rec_s["round"]
    assert res_async.final_acc == res_sim.final_acc

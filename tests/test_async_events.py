"""AsyncEngine event queue:

  * staleness-weight, system-profile and engine-config unit math;
  * a genuinely-async run (small buffer, tiered speeds) advances virtual
    time and applies stale updates; the staleness drop policy terminates
    and bills dropped traffic;
  * event-queue checkpoint/resume: with jobs mid-flight at the snapshot,
    the resumed run reproduces the uninterrupted one bit-exactly;
  * participation < 1 throttles client starts, stays reproducible, and
    diverges from full participation;
  * fig3 regression: under a 1/16 upload-bandwidth ratio, FLASC with
    d_up=1/64 reaches the target accuracy in less simulated time than
    dense LoRA, and the fig3 row helper emits the -1.0 sentinel instead
    of a silent 1.0 when a baseline is missing.
"""
import os

import numpy as np
import pytest

from _fedexp import task  # noqa: F401  (module fixture)
from _fedexp import (LEDGER_ATTRS, N_CLIENTS, StopAfterCheckpoint,
                    experiment, tiered_engine)
from repro.federated import async_clock as ac
from repro.federated import engine as eng
from repro.federated.api import Experiment


@pytest.fixture(scope="module")
def tiered_run(task):
    """Eight rounds on a tiered profile with half-cohort buffers."""
    return experiment(task, rounds=8).with_engine(tiered_engine()).run()


# ---------------------------------------------------------------------------
# staleness / profile / config units
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_staleness_weight_math():
    for alpha in (0.0, 0.5, 1.0, 2.0):
        assert ac.staleness_weight(0, alpha) == 1.0     # exactly
    ws = [ac.staleness_weight(s, 0.5) for s in range(5)]
    assert all(a > b for a, b in zip(ws, ws[1:]))       # monotone decay
    assert ac.staleness_weight(3, 0.0) == 1.0           # alpha=0 disables
    assert ac.staleness_weight(3, 1.0) == pytest.approx(0.25)
    with pytest.raises(AssertionError):
        ac.staleness_weight(-1, 0.5)


@pytest.mark.fast
def test_client_system_profile():
    uniform = ac.ClientSystemProfile()
    assert uniform.is_uniform
    assert uniform.compute_time(3, 2) == 2.0
    assert uniform.down_time(0, 2e6) == 2.0

    tiered = ac.ClientSystemProfile.tiered(4, 4)
    assert not tiered.is_uniform
    assert tiered.speed_factors == (0.25, 0.5, 0.75, 1.0)
    # slowest tier takes 4x the base step time; factors cycle past n
    assert tiered.compute_time(0, 1) == 4.0
    assert tiered.compute_time(4, 1) == 4.0
    assert tiered.up_time(3, 1e6) == 1.0

    logn = ac.ClientSystemProfile.lognormal(8, sigma=0.5, seed=1)
    assert len(logn.speed_factors) == 8
    assert all(f > 0 for f in logn.speed_factors)
    # deterministic in the seed
    assert logn == ac.ClientSystemProfile.lognormal(8, sigma=0.5, seed=1)

    with pytest.raises(AssertionError):
        ac.ClientSystemProfile(up_bw=0.0)


@pytest.mark.fast
def test_async_engine_registry_and_config_roundtrip():
    assert "async" in eng.registered_engines()
    e = eng.resolve_engine("async", buffer_size=2, staleness_alpha=1.0,
                           max_staleness=3,
                           profile=ac.ClientSystemProfile.tiered(4, 2))
    assert isinstance(e, eng.AsyncEngine)
    rebuilt = eng.resolve_engine("async", **e.config())
    assert rebuilt.buffer_size == 2
    assert rebuilt.max_staleness == 3
    assert rebuilt.profile == e.profile     # dict round-trip -> tuples


@pytest.mark.fast
def test_async_rejects_zero_buffer_and_concurrency(task):
    """An explicit 0 is an error, not a silent fall-back to the
    full-cohort default (None)."""
    with pytest.raises(AssertionError):
        experiment(task).with_engine("async", buffer_size=0).run()
    with pytest.raises(AssertionError):
        experiment(task).with_engine("async", concurrency=0).run()


@pytest.mark.fast
def test_async_refuses_dp(task):
    """The refusal must *name the open ROADMAP item* ('DP noise
    calibration under buffered/partial aggregation') and point at the
    sync engines, whose per-round noise rotation is pinned by
    tests/test_engine.py::test_dp_fallback_key_rotates_per_round — an
    operator hitting this error should land on the actual state of DP
    support, not a bare 'not implemented'."""
    exp = (experiment(task)
           .with_federation(n_clients=N_CLIENTS, local_batch=4, dp_clip=1.0,
                            dp_noise=0.1)
           .with_engine("async"))
    with pytest.raises(NotImplementedError, match="dp_clip") as ei:
        exp.run()
    msg = str(ei.value)
    assert "DP noise calibration under buffered/partial aggregation" in msg
    assert "ROADMAP" in msg
    assert "fresh noise every round" in msg


# ---------------------------------------------------------------------------
# genuinely-async behavior
# ---------------------------------------------------------------------------

def test_async_staleness_and_virtual_time(tiered_run):
    res = tiered_run
    assert len(res.history) == 8
    times = [h["sim_time"] for h in res.history]
    assert all(a <= b for a, b in zip(times, times[1:]))
    assert times[0] > 0.0
    assert all(h["applied"] == 2 for h in res.history)
    assert any(h["staleness"] > 0 for h in res.history)     # really async


def test_async_max_staleness_drops_and_terminates(task):
    res = (experiment(task, rounds=5)
           .with_engine(tiered_engine(buffer_size=1, max_staleness=0))
           .run())
    assert len(res.history) == 5
    assert sum(h["dropped"] for h in res.history) > 0
    # dropped messages still billed: more upload messages than applied
    applied = sum(h["applied"] for h in res.history)
    assert res.ledger.up_values > 0
    assert res.ledger.rounds == 5
    assert applied == 5     # buffer of 1 applies one update per event


def test_async_checkpoint_resumes_event_queue_bit_exactly(
        task, tiered_run, tmp_path):
    full = tiered_run

    ckpt = str(tmp_path / "ckpt")
    interrupted = (experiment(task, rounds=8)
                   .with_engine(tiered_engine())
                   .with_checkpoint(ckpt, every=3)
                   .with_callbacks(StopAfterCheckpoint())
                   .run())
    assert len(interrupted.history) == 3
    assert os.path.exists(os.path.join(ckpt, "state-r3.npz"))

    resumed_exp = Experiment.resume(ckpt)
    assert isinstance(resumed_exp.engine, eng.AsyncEngine)
    assert resumed_exp.engine.buffer_size == 2
    assert resumed_exp.engine.profile == \
        ac.ClientSystemProfile.tiered(N_CLIENTS, 4)
    resumed = resumed_exp.run()
    # bit-for-bit: floats, virtual timestamps, staleness — everything
    assert resumed.history == full.history
    assert resumed.final_acc == full.final_acc
    for attr in LEDGER_ATTRS:
        assert getattr(resumed.ledger, attr) == \
            getattr(full.ledger, attr), attr


@pytest.mark.fast
def test_virtual_clock_array_roundtrip():
    clock = ac.VirtualClock(n_clients=3, p_len=5)
    clock.now, clock.seq = 2.5, 4
    clock.job_counts[:] = (2, 1, 1)
    clock.idle = [2]
    job = ac.Job(slot=0, version=1, seq=3, t_start=2.0, t_finish=3.5,
                 delta=np.arange(5, dtype=np.float32), loss=np.float32(0.25),
                 down_nnz=5.0, up_nnz=2.0)
    clock.submit(job)
    clock.buffer.append(ac.Job(slot=1, version=0, seq=2, t_start=0.0,
                               t_finish=2.5,
                               delta=np.ones(5, np.float32),
                               loss=np.float32(1.0), down_nnz=5.0,
                               up_nnz=3.0))
    clock.drop_down, clock.drop_up = [5.0], [1.0]

    restored = ac.VirtualClock.from_arrays(clock.to_arrays(), 3, 5)
    assert restored.now == 2.5 and restored.seq == 4
    assert restored.idle == [2]
    assert [e[2].seq for e in restored.inflight] == [3]
    np.testing.assert_array_equal(restored.inflight[0][2].delta, job.delta)
    assert restored.buffer[0].up_nnz == 3.0
    assert restored.drop_down == [5.0] and restored.drop_up == [1.0]
    np.testing.assert_array_equal(restored.job_counts, clock.job_counts)


def test_async_partial_participation_runs_and_differs(task):
    """participation < 1 throttles client starts: the run still completes
    (FedBuff timeout flushes partial buffers), stays reproducible, and
    diverges from the full-participation trajectory."""
    def run():
        return (experiment(task, "flasc", rounds=6)
                .with_engine("async",
                             sampler={"kind": "fraction",
                                      "participation": 0.5, "seed": 3})
                .run())
    res_a, res_b = run(), run()
    assert [r["loss"] for r in res_a.history] == \
        [r["loss"] for r in res_b.history]
    assert all(np.isfinite(r["loss"]) for r in res_a.history)
    res_full = experiment(task, "flasc", rounds=6).with_engine("async").run()
    assert [r["loss"] for r in res_a.history] != \
        [r["loss"] for r in res_full.history]


# ---------------------------------------------------------------------------
# fig3 regression + row-helper sentinel
# ---------------------------------------------------------------------------

def test_fig3_flasc_sparse_upload_beats_dense_lora_sim_time(task):
    """The paper's Fig. 3 claim on the virtual clock: under upload 16x
    slower than download, FLASC d_up=1/64 reaches the target accuracy in
    far less simulated time than dense LoRA."""
    from benchmarks.fig3_async_bandwidth import sim_time_to_target
    profile = ac.ClientSystemProfile(step_time=0.0, down_bw=1e6,
                                     up_bw=1e6 / 16)
    res_lora = (experiment(task, "lora", rounds=6)
                .with_engine(eng.AsyncEngine(profile=profile)).run())
    res_flasc = (experiment(task, "flasc", rounds=6, density_down=0.25,
                            density_up=1 / 64)
                 .with_engine(eng.AsyncEngine(profile=profile)).run())
    target = 0.9 * min(res_lora.best_acc(), res_flasc.best_acc())
    t_lora = sim_time_to_target(res_lora.history, target)
    t_flasc = sim_time_to_target(res_flasc.history, target)
    assert t_lora is not None and t_flasc is not None
    assert t_flasc < t_lora


@pytest.mark.fast
def test_fig3_rel_row_sentinel():
    """`base_t is None` (dense LoRA never reached target) must yield the
    -1.0 sentinel, not a silent 1.0 — the bug the old inline code had."""
    from benchmarks.fig3_async_bandwidth import rel_row, sim_time_to_target
    assert rel_row("fig3", "s", "m", 5.0, None)["value"] == -1.0
    assert rel_row("fig3", "s", "m", None, 3.0)["value"] == -1.0
    assert rel_row("fig3", "s", "m", None, None)["value"] == -1.0
    assert rel_row("fig3", "s", "m", 6.0, 3.0)["value"] == 2.0
    assert rel_row("fig3", "s", "m", 3.0, 3.0)["value"] == 1.0
    # the time readers skip non-eval records and unreached targets
    hist = [{"round": 0, "loss": 1.0},
            {"round": 1, "loss": 0.5, "acc": 0.4, "sim_time": 7.0}]
    assert sim_time_to_target(hist, 0.3) == 7.0
    assert sim_time_to_target(hist, 0.9) is None

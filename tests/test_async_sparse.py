"""AsyncEngine sparse and weighted aggregation: the packed bulk-transfer
path (`StrategySpec.sparse_aggregate`) keeps sim == async bit-equality at
the sync defaults, the weighted `hetlora` override runs partial buffers
with slot-aware coverage and falls back to the dense path under the
sparse opt-in, and packed job deltas survive event-queue checkpoint and
resume."""
import numpy as np
import pytest

from _fedexp import task  # noqa: F401  (module fixture)
from _fedexp import (LEDGER_ATTRS, CaptureState, StopAfterCheckpoint,
                    experiment, strip_async, tiered_engine)
from repro.core import strategies as st
from repro.federated import async_clock as ac
from repro.federated.api import Experiment

WEIGHTED = dict(hetlora_ranks=(1, 2, 3, 4), hetlora_weighted=True)


@pytest.fixture(scope="module")
def weighted_partial(task):
    """hetlora_weighted on the async engine with half-cohort buffers."""
    return (experiment(task, "hetlora", **WEIGHTED)
            .with_engine("async", buffer_size=2).run())


# ---------------------------------------------------------------------------
# weighted aggregation under partial buffers
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_async_weighted_aggregation_runs_with_partial_buffers(
        task, weighted_partial):
    """hetlora_weighted under partial buffers: the server phase is
    specialized to each buffer's slot tuple (`cohort_slots`), so the
    rank-coverage weighting counts exactly the rows present instead of
    refusing (the PR 9 fix for the old full-fresh-cohort guard).  With a
    uniform profile and full concurrency every aggregation event is a
    deterministic half-cohort, so the run is reproducible and each entry
    of the pseudo-gradient is scaled by the coverage of its own buffer —
    which must differ from the unweighted trajectory."""
    res = weighted_partial
    assert all(rec["applied"] == 2 for rec in res.history)
    assert all(np.isfinite(rec["loss"]) for rec in res.history)
    again = (experiment(task, "hetlora", **WEIGHTED)
             .with_engine("async", buffer_size=2).run())
    assert [r["loss"] for r in res.history] == \
        [r["loss"] for r in again.history]
    unweighted = (experiment(task, "hetlora",
                             hetlora_ranks=(1, 2, 3, 4))
                  .with_engine("async", buffer_size=2).run())
    assert [r["loss"] for r in res.history] != \
        [r["loss"] for r in unweighted.history]


@pytest.mark.fast
def test_hetlora_coverage_counts_buffer_slots():
    """Unit-level pin of the slot-aware coverage: a partial buffer counts
    only its own rank slices, and a repeated slot counts twice."""
    spec = st.StrategySpec(kind="hetlora", **WEIGHTED)
    strat = st.resolve(spec)
    rank_idx = np.asarray([0, 1, 2, 3])
    full = st.PlanContext(n_clients=4, p_len=4, round_idx=0,
                          rank_idx=rank_idx)
    np.testing.assert_array_equal(strat.coverage(full), [4, 3, 2, 1])
    part = st.PlanContext(n_clients=4, p_len=4, round_idx=0,
                          rank_idx=rank_idx, cohort_slots=(1, 3))
    # ranks present: 2 and 4 -> entry j covered by ranks > j
    np.testing.assert_array_equal(strat.coverage(part), [2, 2, 1, 1])
    rep = st.PlanContext(n_clients=4, p_len=4, round_idx=0,
                         rank_idx=rank_idx, cohort_slots=(3, 3))
    np.testing.assert_array_equal(strat.coverage(rep), [2, 2, 2, 2])


# ---------------------------------------------------------------------------
# sparse aggregation (StrategySpec.sparse_aggregate): the packed
# bulk-transfer path must preserve the sim == async anchor
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("kw", [
    dict(kind="flasc"),                                     # packed path
    dict(kind="flasc", selector="fused", quant_bits_up=4),  # + fused kernels
    dict(kind="hetlora", hetlora_ranks=(1, 2, 3, 4),        # weighted
         hetlora_weighted=True),                            # override: must
], ids=["flasc", "flasc-fused-quant", "hetlora-weighted"])  # fall back dense
def test_async_sparse_aggregation_reduces_to_sim_bit_for_bit(task, kw):
    """sim == async bit-equality at sync defaults still holds with the
    sparse aggregation kernel enabled — the flasc specs actually exercise
    the packed scatter-add server phase, and hetlora_weighted (whose
    `aggregate` override reads the dense stack) must be gated back onto
    the dense path rather than mis-aggregated."""
    kw = dict(kw, sparse_aggregate=True)
    kind = kw.pop("kind")
    if kind == "hetlora":
        assert not st.supports_sparse_aggregate(
            st.resolve(st.StrategySpec(kind=kind, **kw)))
    cap_sim, cap_async = CaptureState(), CaptureState()
    res_sim = experiment(task, kind, **kw).with_callbacks(cap_sim).run()
    res_async = (experiment(task, kind, **kw)
                 .with_engine("async").with_callbacks(cap_async).run())
    for rec_a, rec_s in zip(res_async.history, res_sim.history):
        assert strip_async(rec_a) == rec_s, rec_s["round"]
    assert res_async.final_acc == res_sim.final_acc
    for attr in LEDGER_ATTRS:
        assert getattr(res_async.ledger, attr) == \
            getattr(res_sim.ledger, attr), attr
    np.testing.assert_array_equal(cap_async.flatP, cap_sim.flatP)


@pytest.mark.fast
def test_async_weighted_aggregation_with_sparse_opt_in_partial_buffers(
        task, weighted_partial):
    """The sparse_aggregate opt-in never makes a weighted `aggregate`
    override eligible for the packed path (it falls back dense), and the
    slot-specialized dense phase runs partial buffers bit-identically to
    the same spec without the opt-in."""
    sparse = (experiment(task, "hetlora", sparse_aggregate=True, **WEIGHTED)
              .with_engine("async", buffer_size=2).run())
    dense = weighted_partial
    assert [r["loss"] for r in sparse.history] == \
        [r["loss"] for r in dense.history]


def test_async_sparse_checkpoint_resumes_packed_queue_bit_exactly(
        task, tmp_path):
    """Event-queue checkpoint/resume with packed job deltas in flight:
    the `delta_idx`/`delta_val` serialization must round-trip so a
    resumed genuinely-async sparse run reproduces the uninterrupted one
    bit for bit (and keeps aggregating through the sparse phase)."""
    kw = dict(sparse_aggregate=True)
    full = (experiment(task, rounds=8, **kw)
            .with_engine(tiered_engine()).run())

    ckpt = str(tmp_path / "ckpt")
    interrupted = (experiment(task, rounds=8, **kw)
                   .with_engine(tiered_engine())
                   .with_checkpoint(ckpt, every=3)
                   .with_callbacks(StopAfterCheckpoint())
                   .run())
    assert len(interrupted.history) == 3
    resumed = Experiment.resume(ckpt).run()
    assert resumed.history == full.history
    assert resumed.final_acc == full.final_acc
    for attr in LEDGER_ATTRS:
        assert getattr(resumed.ledger, attr) == \
            getattr(full.ledger, attr), attr


@pytest.mark.fast
def test_virtual_clock_packed_delta_roundtrip():
    """`_jobs_to_arrays` with mixed packed/dense jobs: the flag-walk
    re-zips rows correctly and `dense_delta` recovers the dense form."""
    clock = ac.VirtualClock(n_clients=2, p_len=6)
    packed = (np.asarray([1, 4, 6, 6], np.int32),
              np.asarray([2.0, -3.0, 0.0, 0.0], np.float32))
    dense = np.asarray([0, 1, 0, 0, 5, 0], np.float32)
    clock.buffer.append(ac.Job(slot=0, version=0, seq=0, t_start=0.0,
                               t_finish=1.0, delta=packed,
                               loss=np.float32(0.5), down_nnz=6.0,
                               up_nnz=2.0))
    clock.buffer.append(ac.Job(slot=1, version=0, seq=1, t_start=0.0,
                               t_finish=1.5, delta=dense,
                               loss=np.float32(0.25), down_nnz=6.0,
                               up_nnz=2.0))
    restored = ac.VirtualClock.from_arrays(clock.to_arrays(), 2, 6)
    r0, r1 = restored.buffer
    assert isinstance(r0.delta, tuple) and not isinstance(r1.delta, tuple)
    np.testing.assert_array_equal(r0.delta[0], packed[0])
    np.testing.assert_array_equal(r0.delta[1], packed[1])
    np.testing.assert_array_equal(r1.delta, dense)
    np.testing.assert_array_equal(
        ac.dense_delta(r0.delta, 6),
        np.asarray([0, 2, 0, 0, -3, 0], np.float32))
    np.testing.assert_array_equal(ac.dense_delta(r1.delta, 6), dense)

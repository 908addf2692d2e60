"""Engine-API equivalence and behavior:

  * SimEngine vs the frozen pre-refactor `Experiment.run()` loop —
    bit-identical round outputs, final weights, strategy state, and
    ledger totals for the 8 legacy strategy kinds;
  * ShardedEngine end-to-end on 1 CPU device (per-round and scan-chunked),
    agreeing with SimEngine on ledger totals and losses;
  * checkpoint round-trip: save mid-run via CheckpointCallback + StopRun,
    `Experiment.resume`, concatenated history bit-for-bit;
  * engine registry, callback cadences, and rank-weighted hetlora
    aggregation.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _fedexp import sim_run, task  # noqa: F401  (module fixtures)
from _fedexp import KIND_KWARGS, LEDGER_ATTRS, StopAfterCheckpoint, experiment
from repro.core import fedround
from repro.core import strategies as st
from repro.data.pipeline import sample_round
from repro.federated import engine as eng
from repro.federated.api import Experiment

# the kinds that predate the engine API (the PR 5 baselines came later)
LEGACY_KINDS = sorted(set(KIND_KWARGS) - {"flocora", "two_stage_ortho"})


@pytest.fixture(scope="module")
def sim6(task):
    """Six SimEngine rounds of FLASC."""
    return experiment(task, rounds=6).run()


def _legacy_run(exp):
    """The pre-engine `Experiment.run()` inline loop, frozen verbatim (the
    SimEngine extraction must stay bit-identical to this).

    One deliberate update rode along with the AsyncEngine PR: recorded
    loss and ledger inputs are now derived from the per-client metrics
    with the canonical host reductions (`engine._mean_f32`/`_sum_f32`)
    instead of the fused device scalars, because XLA's per-program
    reduction association made those scalars engine-dependent.  This loop
    applies the same derivation so the bit-identity contract stays exact.
    """
    from repro.federated import runtime as rt
    from repro.models import model as mdl
    task, fed, t = exp.task, exp.federation, exp.train
    params, cfg = exp.build_backbone()
    trainable, meta, scale = exp._build_trainable(params, cfg)

    def loss_of(bb, tree, mb):
        p = dict(bb)
        if "head" in tree:
            p.update(tree["head"])
        return mdl.loss_fn(p, cfg, rt._task_batch(cfg, mb),
                           lora=tree["lora"], lora_scale=scale)

    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    sstate = exp.strategy.init_state(meta.p_len)
    round_fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed,
                                              exp.strategy))
    ledger = exp.build_ledger(meta.p_len)
    history, acc = [], 0.0
    for r in range(t.rounds):
        batch_np = sample_round(task, fed, r, seed=t.seed)
        batch = {k: jnp.asarray(v) for k, v in batch_np.items()}
        key = jax.random.fold_in(jax.random.key(t.seed + 2), r)
        flatP, server, sstate, m = round_fn(params, flatP, server, sstate,
                                            batch, key)
        down_pm = [float(v) for v in m["down_nnz_clients"]]
        up_pm = [float(v) for v in m["up_nnz_clients"]]
        ledger.record_round(
            fed.n_clients, eng._mean_f32(down_pm), eng._sum_f32(up_pm),
            down_per_message=down_pm, up_per_message=up_pm)
        rec = {"round": r, "loss": eng._mean_f32(m["loss_clients"]),
               "down_bytes": ledger.down_bytes, "up_bytes": ledger.up_bytes,
               "total_bytes": ledger.total_bytes,
               "coded_bytes": ledger.total_coded_bytes}
        if (r + 1) % t.eval_every == 0 or r == t.rounds - 1:
            acc = rt.evaluate(params, cfg, trainable, meta, task, scale, flatP)
            rec["acc"] = acc
        history.append(rec)
    return history, ledger, acc, np.asarray(flatP), jax.tree.leaves(sstate)


@pytest.mark.parametrize("kind", LEGACY_KINDS)
def test_sim_engine_bit_identical_to_prerefactor_loop(task, sim_run, kind):
    res, cap = sim_run(kind)
    hist_old, led_old, acc_old, P_old, ss_old = _legacy_run(
        experiment(task, kind, **KIND_KWARGS[kind]))

    assert len(res.history) == len(hist_old)
    for rec_new, rec_old in zip(res.history, hist_old):
        for k, v in rec_old.items():        # new records add coded splits
            assert rec_new[k] == v, (rec_new["round"], k)
    assert res.final_acc == acc_old
    for attr in LEDGER_ATTRS:
        assert getattr(res.ledger, attr) == getattr(led_old, attr), attr
    np.testing.assert_array_equal(cap.flatP, P_old)
    assert len(cap.sstate_leaves) == len(ss_old)
    for a, b in zip(cap.sstate_leaves, ss_old):
        np.testing.assert_array_equal(a, np.asarray(b))


@pytest.mark.parametrize("rounds_per_call", [1, 4])
def test_sharded_engine_end_to_end_single_device(task, sim6, rounds_per_call):
    """The SPMD backend on a (1, 1) cpu mesh: same experiment, same ledger
    totals, matching losses, eval cadence preserved across scan chunks."""
    sh = (experiment(task, rounds=6)
          .with_engine("sharded", rounds_per_call=rounds_per_call)
          .run())
    assert [h["round"] for h in sh.history] == \
        [h["round"] for h in sim6.history]
    for a, b in zip(sh.history, sim6.history):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
    for attr in LEDGER_ATTRS:
        assert getattr(sh.ledger, attr) == getattr(sim6.ledger, attr), attr
    # eval rounds must land at the cadence even when chunked
    assert [h["round"] for h in sh.history if "acc" in h] == \
        [h["round"] for h in sim6.history if "acc" in h]
    assert sh.final_acc == pytest.approx(sim6.final_acc, abs=1e-6)


@pytest.mark.parametrize("kind,kw", [
    ("flocora", dict(lowrank_down=4, lowrank_up=4)),
    ("two_stage_ortho", {}),
])
def test_baseline_kinds_run_under_sharded_engine(task, kind, kw):
    """The two named baselines (low-rank message compression / two-stage
    sparsified-orthogonal updates) run under the SPMD backend with zero
    engine edits: same ledger totals and history as SimEngine."""
    sim = experiment(task, kind, **kw).run()
    sh = experiment(task, kind, **kw).with_engine("sharded").run()
    assert [h["round"] for h in sh.history] == \
        [h["round"] for h in sim.history]
    for a, b in zip(sh.history, sim.history):
        assert a["loss"] == pytest.approx(b["loss"], rel=1e-5)
    for attr in LEDGER_ATTRS:
        assert getattr(sh.ledger, attr) == getattr(sim.ledger, attr), attr
    assert sh.final_acc == pytest.approx(sim.final_acc, abs=1e-6)


def test_checkpoint_resume_reproduces_history(task, tmp_path):
    full = experiment(task, rounds=8).run()

    ckpt = str(tmp_path / "ckpt")
    interrupted = (experiment(task, rounds=8)
                   .with_checkpoint(ckpt, every=3)
                   .with_callbacks(StopAfterCheckpoint())
                   .run())
    assert len(interrupted.history) == 3        # stopped at the round-3 save
    assert os.path.exists(os.path.join(ckpt, "state-r3.npz"))
    assert os.path.exists(os.path.join(ckpt, "frozen.npz"))
    assert os.path.exists(os.path.join(ckpt, "meta.json"))

    resumed = Experiment.resume(ckpt).run()
    assert resumed.history == full.history      # bit-for-bit, floats included
    for attr in LEDGER_ATTRS:
        assert getattr(resumed.ledger, attr) == getattr(full.ledger, attr), attr
    assert resumed.final_acc == full.final_acc


def test_resume_without_remaining_rounds_is_stable(task, tmp_path):
    """A checkpoint taken on the final round resumes to a no-op run that
    still reports the saved history and accuracy — and comes back on the
    engine backend the run was saved under."""
    ckpt = str(tmp_path / "ckpt")
    full = (experiment(task, rounds=3)
            .with_engine("sharded", rounds_per_call=2)
            .with_checkpoint(ckpt, every=3).run())
    exp = Experiment.resume(ckpt)
    assert isinstance(exp.engine, eng.ShardedEngine)
    assert exp.engine.rounds_per_call == 2
    resumed = exp.run()
    assert resumed.history == full.history
    assert resumed.final_acc == full.final_acc


@pytest.mark.fast
def test_weighted_aggregation_refused_under_dp():
    """DP noise calibration assumes uniform averaging; a weighted
    aggregate must be rejected, not silently dropped."""
    from repro.models.config import FederatedConfig
    tree = {"w": {"a": jnp.zeros((2, 4)), "b": jnp.zeros((4, 3))}}
    meta = fedround.FlatMeta.of(tree)
    fed = FederatedConfig(n_clients=2, local_batch=2, local_steps=1,
                          dp_clip=1.0, dp_noise=0.1)
    spec = st.StrategySpec(kind="hetlora", hetlora_ranks=(2, 4),
                           hetlora_weighted=True)
    fn = fedround.make_round_fn(
        lambda bb, tree, mb: jnp.sum(tree["w"]["a"] ** 2), meta, fed, spec)
    flatP = meta.flatten(tree)
    with pytest.raises(NotImplementedError, match="non-uniform"):
        fn({}, flatP, fedround.init_server(flatP), {},
           {"x": jnp.zeros((2, 1, 2, 1))}, jax.random.key(0))
    # ...while plain hetlora (uniform averaging) still composes with DP
    spec_ok = st.StrategySpec(kind="hetlora", hetlora_ranks=(2, 4))
    fn_ok = jax.jit(fedround.make_round_fn(
        lambda bb, tree, mb: jnp.sum(tree["w"]["a"] ** 2), meta, fed,
        spec_ok))
    out = fn_ok({}, flatP, fedround.init_server(flatP), {},
                {"x": jnp.zeros((2, 1, 2, 1))}, jax.random.key(0))
    assert np.isfinite(float(out[3]["loss"]))


def test_stoprun_mid_round_keeps_state_consistent(task):
    """StopRun raised from on_round_end still finishes that round's
    bookkeeping: history length, ledger.rounds, and state.round agree."""

    class StopAfter(eng.Callback):
        def __init__(self, n):
            self.n = n

        def on_round_end(self, ev):
            if ev.round + 1 >= self.n:
                raise eng.StopRun

    res = experiment(task, rounds=8).with_callbacks(StopAfter(3)).run()
    assert len(res.history) == 3
    assert res.ledger.rounds == 3
    assert [h["round"] for h in res.history] == [0, 1, 2]


@pytest.mark.fast
def test_engine_registry_resolves():
    assert set(eng.registered_engines()) >= {"sim", "sharded"}
    assert isinstance(eng.resolve_engine("sim"), eng.SimEngine)
    sharded = eng.resolve_engine("sharded", rounds_per_call=4)
    assert isinstance(sharded, eng.ShardedEngine)
    assert sharded.rounds_per_call == 4
    inst = eng.SimEngine()
    assert eng.resolve_engine(inst) is inst
    with pytest.raises(KeyError, match="no_such_engine"):
        eng.resolve_engine("no_such_engine")


@pytest.mark.fast
def test_chunk_len_cuts_at_state_rounds():
    """Scan chunks end where a callback needs host state (eval cadence)."""

    class Want(eng.Callback):
        def wants_state(self, r, rounds):
            return (r + 1) % 3 == 0

    e = eng.ShardedEngine(rounds_per_call=8)
    plan = object()
    state = eng.RunState(plan, None, None, None, round=0, rounds=10)
    cuts, r = [], 0
    while r < state.rounds:
        n = e._chunk_len(r, state, [Want()])
        cuts.append(n)
        r += n
    assert cuts == [3, 3, 3, 1]                 # chunks end at rounds 2,5,8,9


@pytest.mark.fast
def test_hetlora_weighted_aggregation_math():
    """Rank-coverage weighting divides each entry by the number of clients
    whose rank slice covers it (plain averaging divides by n_clients)."""
    tree = {"w": {"a": jnp.zeros((2, 4)), "b": jnp.zeros((4, 3))}}
    meta = fedround.FlatMeta.of(tree)
    ranks = (1, 2, 4, 4)
    strat = st.resolve(st.StrategySpec(kind="hetlora", hetlora_ranks=ranks,
                                       hetlora_weighted=True))
    ctx = meta.plan_context(4)
    masks = jnp.stack([strat.client_plan(None, c, ctx).m_down
                       for c in range(4)])
    deltas = masks.astype(jnp.float32)          # each client uploads its mask
    agg = strat.aggregate(deltas, ctx)
    cov = np.sum(np.asarray(masks), axis=0)
    # covered entries aggregate to exactly 1 (sum/coverage); uncovered to 0
    np.testing.assert_allclose(np.asarray(agg),
                               (cov > 0).astype(np.float32), atol=0)
    # the unweighted default would have produced mean = cov / 4
    plain = st.resolve(st.StrategySpec(kind="hetlora", hetlora_ranks=ranks))
    np.testing.assert_allclose(np.asarray(plain.aggregate(deltas, ctx)),
                               cov / 4.0, atol=0)


def test_hetlora_weighted_changes_round_outputs(task, sim_run):
    res_plain, _ = sim_run("hetlora")
    res_w = experiment(task, "hetlora", hetlora_weighted=True,
                       **KIND_KWARGS["hetlora"]).run()
    # identical communication, different server trajectory
    assert res_w.ledger.total_bytes == res_plain.ledger.total_bytes
    assert any(a["loss"] != b["loss"]
               for a, b in zip(res_w.history[1:], res_plain.history[1:]))


@pytest.mark.fast
def test_logging_callback_formats(capsys):
    state = eng.RunState(None, None, None, None, rounds=10)
    rec = {"loss": 1.25, "acc": 0.5, "total_bytes": 2e6}
    ev = eng.RoundEvent(round=4, state=state, metrics={}, record=rec,
                        evaluated=True)
    eng.LoggingCallback(verbose=True).on_eval(ev)
    out = capsys.readouterr().out
    assert "round    5" in out and "acc=0.5000" in out and "2.00MB" in out


@pytest.mark.fast
def test_dp_fallback_key_rotates_per_round():
    """With rng=None and DP noise on, the fallback key must fold the
    round index: the draw at round r+1 has to differ from round r (the
    old fixed key(0) replayed the identical noise every round, turning
    "noise" into a constant bias the server optimizer learns around)."""
    from repro.models.config import FederatedConfig
    tree = {"w": {"a": jnp.zeros((2, 4)), "b": jnp.zeros((4, 3))}}
    meta = fedround.FlatMeta.of(tree)
    fed = FederatedConfig(n_clients=2, local_batch=2, local_steps=1,
                          dp_clip=1.0, dp_noise=0.5)
    flatP = meta.flatten(tree)
    batches = {"x": jnp.zeros((2, 1, 2, 1))}
    kw = dict(loss_of=lambda bb, t, mb: jnp.sum(t["w"]["a"] ** 2),
              meta=meta, fed=fed, strategy=st.StrategySpec(kind="lora"))
    server0 = fedround.init_server(flatP)
    out_a = fedround.federated_round({}, flatP, server0, {}, batches, None,
                                     **kw)
    out_b = fedround.federated_round({}, flatP, server0, {}, batches, None,
                                     **kw)
    # deterministic at a fixed round...
    assert jnp.array_equal(out_a[0], out_b[0])
    # ...but a different round index must draw different noise
    server1 = dict(server0, round=jnp.asarray(1, jnp.int32))
    out_c = fedround.federated_round({}, flatP, server1, {}, batches, None,
                                     **kw)
    assert not jnp.array_equal(out_a[0], out_c[0])

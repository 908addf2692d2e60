"""FLASC core invariants: sparsity selectors, strategy masks, the federated
round, DP, and communication accounting (unit + hypothesis properties)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, hst

from repro.core import comm as comm_mod
from repro.core import dp as dp_mod
from repro.core import fedround
from repro.core import sparsity as sp
from repro.core import strategies as st
from repro.models.config import FederatedConfig


# ---------------------------------------------------------------------------
# sparsity selectors
# ---------------------------------------------------------------------------

@pytest.mark.fast
@settings(deadline=None, max_examples=25)
@given(hst.integers(64, 4096), hst.sampled_from([0.01, 0.1, 0.25, 0.5, 0.9]),
       hst.integers(0, 2 ** 31 - 1))
def test_topk_mask_density(n, density, seed):
    x = jax.random.normal(jax.random.key(seed), (n,))
    m = sp.topk_mask(x, density)
    k = int(jnp.sum(m))
    target = max(int(round(n * density)), 1)
    # ties can keep a few extra entries, never fewer
    assert k >= target
    assert k <= target + int(0.01 * n) + 1
    # kept entries dominate dropped entries in magnitude
    kept_min = float(jnp.min(jnp.where(m, jnp.abs(x), jnp.inf)))
    dropped_max = float(jnp.max(jnp.where(m, -jnp.inf, jnp.abs(x))))
    assert kept_min >= dropped_max


@pytest.mark.fast
@settings(deadline=None, max_examples=15)
@given(hst.integers(256, 8192), hst.sampled_from([0.05, 0.25, 0.5]),
       hst.integers(0, 2 ** 31 - 1))
def test_histogram_matches_exact(n, density, seed):
    x = jnp.abs(jax.random.normal(jax.random.key(seed), (n,)))
    te = sp.threshold_exact(x, density)
    th = sp.threshold_histogram(x, density, iters=30)
    ke = int(jnp.sum(x >= te))
    kh = int(jnp.sum(x >= th))
    assert abs(ke - kh) <= max(2, int(0.02 * n))


@pytest.mark.fast
def test_sparsify_counts():
    x = jnp.arange(1, 101, dtype=jnp.float32)
    masked, nnz = sp.sparsify(x, 0.25)
    assert int(nnz) == 25
    assert float(jnp.min(jnp.where(masked > 0, masked, jnp.inf))) == 76.0


# ---------------------------------------------------------------------------
# strategies
# ---------------------------------------------------------------------------

def _tiny_setup(kind="flasc", **kw):
    trainable = {"w": {"a": jnp.ones((8, 4)), "b": jnp.zeros((4, 8))}}
    meta = fedround.FlatMeta.of(trainable)
    spec = st.StrategySpec(kind=kind, **kw)
    return trainable, meta, spec


@pytest.mark.fast
def test_rank_index_map():
    tree = {"x": {"a": jnp.zeros((6, 3)), "b": jnp.zeros((3, 5))}}
    rk, ib = st.rank_index_map(tree)
    assert rk.shape == (6 * 3 + 3 * 5,)
    assert (ib[:18] == 0).all() and (ib[18:] == 1).all()
    # a entries: rank idx cycles 0,1,2 per row
    assert list(rk[:6]) == [0, 1, 2, 0, 1, 2]
    # b entries: rank idx is the row
    assert list(rk[18:28]) == [0] * 5 + [1] * 5


@pytest.mark.fast
def test_registry_covers_all_kinds():
    for kind in st.KINDS:
        strat = st.resolve(kind)
        assert isinstance(strat, st.Strategy) and strat.kind == kind
    with pytest.raises(ValueError, match="no_such_strategy"):
        st.resolve("no_such_strategy")


@pytest.mark.fast
def test_ffa_mask_trains_only_b():
    _, meta, spec = _tiny_setup("ffa")
    m_down = jnp.ones((meta.p_len,), bool)
    plan = st.resolve(spec).client_plan(m_down, 0, meta.plan_context(1))
    assert plan.upload.mode == "fixed"
    assert int(jnp.sum(plan.m_train)) == 4 * 8      # only b entries


@pytest.mark.fast
def test_hetlora_rank_mask():
    _, meta, spec = _tiny_setup("hetlora", hetlora_ranks=(2, 4))
    strat = st.resolve(spec)
    ctx = meta.plan_context(2)
    m0 = strat.client_plan(None, 0, ctx).m_down
    m1 = strat.client_plan(None, 1, ctx).m_down
    assert int(jnp.sum(m0)) == 8 * 2 + 2 * 8   # rank-2 slice of a and b
    assert int(jnp.sum(m1)) == meta.p_len
    assert bool(jnp.all(m1 | ~m0))             # nested


@pytest.mark.fast
def test_adapter_lth_density_decays():
    p_len = 1000
    strat = st.resolve(st.StrategySpec(kind="adapter_lth", lth_prune_every=1,
                                       lth_keep=0.9))
    sstate = strat.init_state(p_len)
    flatP = jax.random.normal(jax.random.key(0), (p_len,))
    for r in range(1, 4):
        sstate, flatP = strat.post_round(sstate, flatP, P_base=None,
                                         m_down=None, round_idx=jnp.asarray(r))
        nnz = int(jnp.sum(sstate["mask"]))
        assert nnz == pytest.approx(p_len * 0.9 ** r, rel=0.05)
        # pruned weights are permanently zeroed
        assert int(jnp.sum(flatP != 0)) <= nnz


@pytest.mark.fast
def test_sparse_adapter_freezes_after_first_round():
    p_len = 200
    strat = st.resolve(st.StrategySpec(kind="sparse_adapter", density_down=0.25))
    sstate = strat.init_state(p_len)
    flatP = jax.random.normal(jax.random.key(0), (p_len,))
    assert int(jnp.sum(strat.download_mask(flatP, sstate, 0))) == p_len
    sstate, _ = strat.post_round(sstate, flatP, P_base=None, m_down=None,
                                 round_idx=jnp.asarray(0))
    m1 = strat.download_mask(flatP, sstate, 1)
    assert int(jnp.sum(m1)) == 50
    sstate2, _ = strat.post_round(sstate, flatP * 2, P_base=None, m_down=None,
                                  round_idx=jnp.asarray(1))
    assert bool(jnp.all(sstate2["mask"] == sstate["mask"]))  # frozen


@pytest.mark.fast
def test_adapter_lth_prune_selector_parity():
    """The dynamic-density prune routes through the selector layer: exact
    keeps exactly k entries, histogram and pallas stay bit-identical to
    each other, and pruned (zeroed) entries never resurrect."""
    p_len = 1000
    flatP = jax.random.normal(jax.random.key(0), (p_len,))
    masks = {}
    for selector in ("exact", "histogram", "pallas"):
        strat = st.resolve(st.StrategySpec(kind="adapter_lth",
                                           lth_prune_every=1, lth_keep=0.5,
                                           selector=selector))
        sstate = strat.init_state(p_len)
        sstate, flat2 = strat.post_round(sstate, flatP, P_base=None,
                                         m_down=None,
                                         round_idx=jnp.asarray(1))
        masks[selector] = np.asarray(sstate["mask"])
        # permanent pruning: the surviving vector is supported on the mask
        assert bool(jnp.all((flat2 != 0) <= sstate["mask"]))
    assert masks["exact"].sum() == 500          # exactly k
    np.testing.assert_array_equal(masks["histogram"], masks["pallas"])
    assert masks["histogram"].sum() >= 500      # threshold family: >= k


@pytest.mark.fast
def test_two_stage_ortho_phase_masks_alternate():
    trainable = {"lora": {"l": {"a": jnp.ones((8, 4)),
                                "b": jnp.zeros((4, 8))}}}
    meta = fedround.FlatMeta.of(trainable)
    strat = st.resolve(st.StrategySpec(kind="two_stage_ortho"))
    m_down = jnp.ones((meta.p_len,), bool)
    ctx0 = meta.plan_context(2, round_idx=jnp.asarray(0))
    plan0 = strat.client_plan(m_down, 0, ctx0)
    assert plan0.upload.mode == "topk"
    assert int(jnp.sum(plan0.m_train)) == 8 * 4          # A entries only
    assert bool(jnp.all(plan0.m_down))                   # download is dense
    # one shared mask per round: the second client reuses the same array
    assert strat.client_plan(m_down, 1, ctx0).m_train is plan0.m_train
    ctx1 = meta.plan_context(2, round_idx=jnp.asarray(1))
    plan1 = strat.client_plan(m_down, 0, ctx1)
    assert int(jnp.sum(plan1.m_train)) == 4 * 8          # B entries only
    assert not bool(jnp.any(plan0.m_train & plan1.m_train))


@pytest.mark.fast
def test_two_stage_ortho_qr_preserves_adapter_product():
    a0 = 0.3 * jax.random.normal(jax.random.key(4), (16, 4))
    b0 = 0.2 * jax.random.normal(jax.random.key(5), (4, 8))
    trainable = {"lora": {"l": {"a": a0, "b": b0}}}
    meta = fedround.FlatMeta.of(trainable)
    strat = st.resolve(st.StrategySpec(kind="two_stage_ortho"))
    flatP = meta.flatten(trainable)
    # even round (A phase just ended): A comes back orthonormal, A@B intact
    ctx = meta.plan_context(2, round_idx=jnp.asarray(0))
    _, flat2 = strat.post_round({}, flatP, P_base=None, m_down=None,
                                round_idx=jnp.asarray(0), ctx=ctx)
    pair = meta.unflatten(flat2)["lora"]["l"]
    np.testing.assert_allclose(np.asarray(pair["a"].T @ pair["a"]),
                               np.eye(4), atol=1e-5)
    np.testing.assert_allclose(np.asarray(pair["a"] @ pair["b"]),
                               np.asarray(a0 @ b0), atol=1e-5)
    # odd round (B phase): weights pass through untouched
    ctx1 = meta.plan_context(2, round_idx=jnp.asarray(1))
    _, flat3 = strat.post_round({}, flatP, P_base=None, m_down=None,
                                round_idx=jnp.asarray(1), ctx=ctx1)
    np.testing.assert_array_equal(np.asarray(flat3), np.asarray(flatP))


@pytest.mark.fast
def test_flocora_kind_defaults_lowrank_ranks():
    strat = st.resolve("flocora")
    assert strat.spec.lowrank_down == strat.spec.lowrank_up == 8
    # each direction defaults independently (the method compresses both:
    # tuning one rank must not silently disable the other direction), and
    # the defaulted spec round-trips through the checkpoint dict form
    # without re-defaulting surprises
    custom = st.resolve(st.StrategySpec(kind="flocora", lowrank_up=4))
    assert (custom.spec.lowrank_down, custom.spec.lowrank_up) == (8, 4)
    sj = dataclasses.asdict(strat.spec)
    for k in ("client_densities", "hetlora_ranks"):
        sj[k] = tuple(sj[k])
    back = st.resolve(st.StrategySpec(**sj))
    assert back.spec == strat.spec


@pytest.mark.fast
def test_post_round_ctx_is_optional_for_old_overrides():
    """Out-of-tree strategies written against the pre-ctx hook signature
    still run: the round loop's `call_post_round` passes ctx only to
    overrides that accept it."""
    class OldStyle(st.Strategy):
        kind = "lora"

        def post_round(self, sstate, flatP, *, P_base, m_down, round_idx):
            return sstate, flatP + 1.0

    flatP = jnp.zeros((4,))
    ctx = st.PlanContext(p_len=4, n_clients=1)
    _, out = st.call_post_round(OldStyle(st.StrategySpec(kind="lora")), {},
                                flatP, P_base=None, m_down=None,
                                round_idx=0, ctx=ctx)
    np.testing.assert_array_equal(np.asarray(out), np.ones(4))
    # ctx-aware overrides (the built-ins) receive the real context
    strat = st.resolve(st.StrategySpec(kind="two_stage_ortho"))
    trainable = {"lora": {"l": {"a": jnp.ones((4, 2)),
                                "b": jnp.ones((2, 4))}}}
    meta = fedround.FlatMeta.of(trainable)
    _, out2 = st.call_post_round(strat, {}, meta.flatten(trainable),
                                 P_base=None, m_down=None,
                                 round_idx=jnp.asarray(1),
                                 ctx=meta.plan_context(1, round_idx=1))
    assert out2.shape == (meta.p_len,)


@pytest.mark.fast
def test_spec_rejects_bad_lowrank_config():
    with pytest.raises(ValueError, match="lowrank_mode"):
        st.StrategySpec(kind="flasc", lowrank_mode="svdish")
    with pytest.raises(ValueError, match="lowrank ranks"):
        st.StrategySpec(kind="flasc", lowrank_up=-1)


# ---------------------------------------------------------------------------
# federated round end-to-end (quadratic toy problem)
# ---------------------------------------------------------------------------

def _quadratic_round(kind="flasc", rounds=30, **kw):
    """Trainable 'lora' fits a least-squares target through the round API."""
    target = jax.random.normal(jax.random.key(1), (16, 4))
    trainable = {"w": {"a": jnp.zeros((16, 4)), "b": jnp.zeros((4, 4))}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=4, local_batch=2, local_steps=1,
                          client_lr=0.2, server_lr=0.05, **kw)

    def loss_of(bb, tree, mb):
        return jnp.mean((tree["w"]["a"] - bb) ** 2) + jnp.mean(tree["w"]["b"] ** 2)

    spec = st.StrategySpec(kind=kind, density_down=0.5, density_up=0.5)
    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    sstate = st.resolve(spec).init_state(meta.p_len)
    fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, spec))
    batch = {"x": jnp.zeros((4, 1, 2, 1))}
    losses = []
    for r in range(rounds):
        flatP, server, sstate, m = fn(target, flatP, server, sstate, batch,
                                      jax.random.key(r))
        losses.append(float(m["loss"]))
    return losses, flatP, meta


def test_flasc_round_converges():
    losses, _, _ = _quadratic_round("flasc")
    assert losses[-1] < 0.5 * losses[0]


def test_dense_lora_round_converges_faster_or_equal():
    l_flasc, _, _ = _quadratic_round("flasc")
    l_dense, _, _ = _quadratic_round("lora")
    assert l_dense[-1] <= l_flasc[-1] * 1.5


def test_round_metrics_densities():
    target = jax.random.normal(jax.random.key(1), (16, 16))
    trainable = {"w": {"a": jnp.ones((16, 16)), "b": jnp.ones((16, 16))}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=4, local_batch=2, local_steps=1,
                          client_lr=0.1, server_lr=0.05)

    def loss_of(bb, tree, mb):
        return jnp.mean((tree["w"]["a"] - bb) ** 2)

    spec = st.StrategySpec(kind="flasc", density_down=0.25, density_up=0.125)
    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    sstate = st.resolve(spec).init_state(meta.p_len)
    fn = fedround.make_round_fn(loss_of, meta, fed, spec)
    _, _, _, m = jax.jit(fn)(target, flatP, server, sstate,
                             {"x": jnp.zeros((4, 1, 2, 1))}, jax.random.key(0))
    # download: ~25% of 512 entries; ties possible at equal magnitudes
    assert float(m["down_nnz"]) >= 0.25 * meta.p_len
    # upload: each client <= ceil(12.5%) of entries, only a-entries nonzero
    assert float(m["up_nnz"]) <= 4 * (0.125 * meta.p_len + 1)


# ---------------------------------------------------------------------------
# DP
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=25)
@given(hst.integers(2, 8), hst.integers(4, 64),
       hst.floats(0.01, 10.0), hst.integers(0, 2 ** 31 - 1))
def test_dp_clipping_bounds_sensitivity(n, p, clip, seed):
    deltas = 10.0 * jax.random.normal(jax.random.key(seed), (n, p))
    clipped, norms = dp_mod.clip_deltas(deltas, clip)
    post = jnp.linalg.norm(clipped, axis=-1)
    assert bool(jnp.all(post <= clip * (1 + 1e-5)))
    # clipping preserves direction
    cos = jnp.sum(clipped * deltas, -1) / (
        jnp.maximum(jnp.linalg.norm(deltas, axis=-1) * post, 1e-12))
    assert bool(jnp.all(cos > 0.999))


def test_dp_aggregate_noise_scale():
    n, p = 8, 4096
    deltas = jnp.zeros((n, p))
    agg, _ = dp_mod.dp_aggregate(deltas, clip_norm=1.0, noise_mult=2.0,
                                 key=jax.random.key(0))
    # zero signal => pure noise with std sigma/n
    assert float(jnp.std(agg)) == pytest.approx(2.0 / n, rel=0.1)


def test_simulated_noise_multiplier():
    assert dp_mod.simulated_noise_multiplier(0.58, 1000, 10) == pytest.approx(0.0058)


# ---------------------------------------------------------------------------
# communication accounting
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_comm_ledger_math():
    led = comm_mod.CommLedger(total_params=1000)
    for _ in range(10):
        led.record_round(n_clients=4, down_nnz=250, up_nnz_total=4 * 100)
    assert led.down_bytes == 10 * 4 * 250 * 4
    assert led.up_bytes == 10 * 400 * 4
    dense = led.dense_equivalent_bytes(4)
    assert dense == 10 * 4 * 1000 * 2 * 4
    assert led.total_bytes / dense == pytest.approx((250 + 100) / 2000)
    t_sym = led.comm_time(1e6, 1e6, 4)
    t_slow_up = led.comm_time(1e6, 1e6 / 16, 4)
    assert t_slow_up > t_sym * 4  # upload-dominated


@pytest.mark.fast
def test_flasc_ef_residual_invariant():
    """flasc_ef (beyond-paper): the EF residual is exactly the unsent part
    of the corrected weights, and uploads stay at the nominal density."""
    trainable = {"w": {"a": jnp.arange(1.0, 33.0).reshape(8, 4),
                       "b": jnp.ones((4, 8)) * 0.1}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=2, local_batch=2, local_steps=1,
                          client_lr=0.1, server_lr=0.01)
    spec = st.StrategySpec(kind="flasc_ef", density_down=0.25, density_up=0.5)

    def loss_of(bb, tree, mb):
        return jnp.mean(tree["w"]["a"] ** 2)

    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    sstate = st.resolve(spec).init_state(meta.p_len)
    fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, spec))
    batch = {"x": jnp.zeros((2, 1, 2, 1))}
    P1, server, sstate, m = fn({}, flatP, server, sstate, batch,
                               jax.random.key(0))
    # residual supported exactly on the (1 - d_down) unsent entries
    assert int(jnp.sum(sstate["e"] != 0)) == meta.p_len - meta.p_len // 4
    assert float(m["up_nnz"]) <= 2 * (0.5 * meta.p_len + 1)
    # next round consumes the residual without error
    P2, _, sstate2, m2 = fn({}, P1, server, sstate, batch, jax.random.key(1))
    assert jnp.isfinite(m2["loss"])


@pytest.mark.fast
def test_exact_topk_is_exactly_k_under_ties():
    x = jnp.concatenate([jnp.zeros(90), jnp.ones(10)])
    assert int(jnp.sum(sp.topk_mask(x, 0.25))) == 25


@pytest.mark.fast
def test_topk_by_count_matches_static_and_handles_batches():
    x = jax.random.normal(jax.random.key(0), (257,))
    for d in (0.1, 0.25, 0.5):
        k = max(int(round(257 * d)), 1)
        np.testing.assert_array_equal(
            np.asarray(sp.topk_mask_by_count(x, k)),
            np.asarray(sp.topk_mask(x, d)))
    # batched input selects per row along the last axis
    xb = jnp.asarray([[1., 9., 2., 8., 3., 7., 4., 6.],
                      [9., 1., 8., 2., 7., 3., 6., 4.]])
    mb = sp.topk_mask_by_count(xb, 4)
    np.testing.assert_array_equal(np.asarray(mb),
                                  np.asarray(sp.topk_mask(xb, 0.5)))
    # traced count under vmap (the heterogeneous-upload path)
    ks = jnp.asarray([2, 4])
    mv = jax.vmap(lambda row, k: sp.topk_mask_by_count(row, k))(xb, ks)
    assert [int(r.sum()) for r in mv] == [2, 4]


@pytest.mark.fast
def test_fedavg_server_rule():
    """server_opt='sgd' applies the plain FedAvg update W <- W - lr*mean(d)."""
    trainable = {"w": {"a": jnp.ones((4, 4)), "b": jnp.ones((4, 4))}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=2, local_batch=2, local_steps=1,
                          client_lr=0.5, client_momentum=0.0,
                          server_lr=1.0, server_opt="sgd")
    spec = st.StrategySpec(kind="lora")

    def loss_of(bb, tree, mb):
        return jnp.sum(tree["w"]["a"]) + jnp.sum(tree["w"]["b"])   # grad = 1

    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, spec))
    P1, _, _, _ = fn({}, flatP, server, {}, {"x": jnp.zeros((2, 1, 2, 1))},
                     jax.random.key(0))
    # delta = lr_client * grad = 0.5 everywhere; FedAvg: P - 1.0*0.5
    np.testing.assert_allclose(np.asarray(P1), 0.5, rtol=1e-6)

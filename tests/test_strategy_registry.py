"""Registry-migration equivalence: every legacy `StrategySpec.kind` must
produce bit-identical masks, round outputs, and ledger totals through the
new `Strategy` registry versus the seed if/elif implementation (frozen in
`legacy_seed.py`), plus the heterogeneous-upload-quantization regression
the redesign fixes."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import legacy_seed
from repro.core import fedround
from repro.core import strategies as st
from repro.core.comm import CommLedger
from repro.models.config import FederatedConfig

pytestmark = pytest.mark.fast

N_CLIENTS = 4
ROUNDS = 5

CASES = [
    ("lora", {}),
    ("flasc", {}),
    ("flasc_ef", {}),
    ("sparse_adapter", {}),
    ("fedselect", {}),
    ("adapter_lth", dict(lth_prune_every=2, lth_keep=0.9)),
    ("ffa", {}),
    ("hetlora", dict(hetlora_ranks=(1, 2, 3, 4))),
    # heterogeneous per-client upload densities (seed: python-loop branch)
    ("flasc", dict(client_densities=(0.25, 0.5, 0.75, 1.0))),
    # quantized messages (stochastic rounding keys must line up exactly)
    ("flasc", dict(quant_bits_up=8, quant_bits_down=8)),
    ("lora", dict(quant_bits_up=4)),
]


def _toy():
    """Quadratic toy task: elementwise loss keeps vmap-vs-loop bit-exact."""
    target = jax.random.normal(jax.random.key(1), (16, 4))
    trainable = {"w": {"a": 0.1 * jax.random.normal(jax.random.key(2), (16, 4)),
                       "b": 0.05 * jax.random.normal(jax.random.key(3), (4, 4))}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=N_CLIENTS, local_batch=2, local_steps=1,
                          client_lr=0.2, server_lr=0.05)

    def loss_of(bb, tree, mb):
        return (jnp.mean((tree["w"]["a"] - bb) ** 2)
                + jnp.mean(tree["w"]["b"] ** 2))

    batch = {"x": jnp.zeros((N_CLIENTS, 1, 2, 1))}
    return meta, fed, loss_of, target, batch, meta.flatten(trainable)


def _drive(round_fn, init_state_fn, meta, fed, batch, flatP):
    """Run ROUNDS rounds of `round_fn(flatP, server, sstate, batch, rng)`;
    returns (flatP, sstate, per-round metrics, ledger)."""
    server = fedround.init_server(flatP)
    sstate = init_state_fn(meta.p_len)
    ledger = CommLedger(total_params=meta.p_len)
    history = []
    for r in range(ROUNDS):
        flatP, server, sstate, m = round_fn(flatP, server, sstate, batch,
                                            jax.random.key(r))
        ledger.record_round(fed.n_clients, float(m["down_nnz"]),
                            float(m["up_nnz"]))
        history.append({k: np.asarray(v) for k, v in m.items()})
    return flatP, sstate, history, ledger


@pytest.mark.parametrize("kind,kw", CASES,
                         ids=[f"{k}-{i}" for i, (k, _) in enumerate(CASES)])
def test_registry_matches_seed_bit_identical(kind, kw):
    meta, fed, loss_of, backbone, batch, flatP0 = _toy()
    spec = st.StrategySpec(kind=kind, density_down=0.5, density_up=0.5, **kw)
    strat = st.resolve(spec)

    new_fn = functools.partial(
        jax.jit(fedround.make_round_fn(loss_of, meta, fed, strat)), backbone)

    # the frozen seed takes the two-argument loss that closes over the
    # backbone
    def legacy_fn(flatP, server, sstate, cb, rng):
        return legacy_seed.federated_round(
            flatP, server, sstate, cb, rng,
            loss_of=functools.partial(loss_of, backbone), meta=meta, fed=fed,
            spec=spec)
    legacy_fn = jax.jit(legacy_fn)

    P_new, ss_new, hist_new, led_new = _drive(
        new_fn, strat.init_state, meta, fed, batch, flatP0)
    P_old, ss_old, hist_old, led_old = _drive(
        legacy_fn, lambda p: legacy_seed.init_strategy_state(spec, p),
        meta, fed, batch, flatP0)

    # round outputs: final weights bit for bit; nnz counts (the ledger
    # inputs) bit for bit.  The loss/grad_norm *diagnostics* are reductions
    # whose association XLA picks per-program, so two differently-fused jits
    # of the same math can differ by 1 ulp — compare those at ulp tolerance.
    np.testing.assert_array_equal(np.asarray(P_new), np.asarray(P_old))
    for r, (mn, mo) in enumerate(zip(hist_new, hist_old)):
        assert set(mo).issubset(mn)     # new path adds per-client nnz arrays
        for key in ("down_nnz", "up_nnz"):
            np.testing.assert_array_equal(mn[key], mo[key],
                                          err_msg=f"round {r} metric {key}")
        for key in ("loss", "grad_norm"):
            np.testing.assert_allclose(mn[key], mo[key], rtol=1e-6, atol=0,
                                       err_msg=f"round {r} metric {key}")
    # persistent strategy state
    ln, lo = jax.tree.leaves(ss_new), jax.tree.leaves(ss_old)
    assert len(ln) == len(lo)
    for a, b in zip(ln, lo):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # ledger totals
    for attr in ("down_values", "up_values", "down_bytes", "up_bytes",
                 "total_bytes", "rounds"):
        assert getattr(led_new, attr) == getattr(led_old, attr), attr


@pytest.mark.parametrize("kind,kw", CASES,
                         ids=[f"{k}-{i}" for i, (k, _) in enumerate(CASES)])
def test_client_plans_match_seed_masks(kind, kw):
    """Hook-level equivalence: download mask + per-client (m_down, m_train,
    upload rule) match the seed dispatch for every client slot."""
    meta, _, _, _, _, flatP = _toy()
    spec = st.StrategySpec(kind=kind, density_down=0.5, density_up=0.5, **kw)
    strat = st.resolve(spec)
    sstate = strat.init_state(meta.p_len)

    m_new = strat.download_mask(flatP, sstate, 0)
    m_old = legacy_seed.download_mask(spec, flatP, sstate, 0)
    np.testing.assert_array_equal(np.asarray(m_new), np.asarray(m_old))

    ctx = meta.plan_context(N_CLIENTS)
    for c in range(N_CLIENTS):
        plan = strat.client_plan(m_new, c, ctx)
        dn_o, tr_o, (mode_o, arg_o) = legacy_seed.client_masks(
            spec, m_old, c, meta.p_len, meta.rank_idx, meta.is_b)
        np.testing.assert_array_equal(np.asarray(plan.m_down), np.asarray(dn_o))
        assert (plan.m_train is None) == (tr_o is None)
        if tr_o is not None:
            np.testing.assert_array_equal(np.asarray(plan.m_train),
                                          np.asarray(tr_o))
        assert plan.upload.mode == mode_o
        if mode_o == "topk":
            assert plan.upload.density == arg_o
        else:
            np.testing.assert_array_equal(np.asarray(plan.upload.mask),
                                          np.asarray(arg_o))


def test_het_upload_quantization_applied():
    """Seed regression: the heterogeneous branch never forwarded
    quant_bits_up/quant_key to `_client_update`, so per-client-density runs
    silently skipped upload quantization.  The single vmapped path must
    quantize het uploads; the frozen seed demonstrably does not."""
    meta, fed, loss_of, backbone, batch, flatP0 = _toy()
    het = dict(client_densities=(0.25, 0.5, 0.75, 1.0))

    def new_round(bb, flatP, server, sstate, cb, rng, spec):
        return fedround.federated_round(bb, flatP, server, sstate, cb, rng,
                                        loss_of=loss_of, meta=meta, fed=fed,
                                        strategy=spec)

    def legacy_round(bb, flatP, server, sstate, cb, rng, spec):
        return legacy_seed.federated_round(
            flatP, server, sstate, cb, rng,
            loss_of=functools.partial(loss_of, bb), meta=meta, fed=fed,
            spec=spec)

    def final_weights(round_impl, spec):
        def fn(bb, flatP, server, sstate, cb, rng):
            return round_impl(bb, flatP, server, sstate, cb, rng, spec)
        P, _, _, _ = _drive(functools.partial(jax.jit(fn), backbone),
                            lambda p: legacy_seed.init_strategy_state(spec, p),
                            meta, fed, batch, flatP0)
        return np.asarray(P)

    spec_q = st.StrategySpec(kind="flasc", density_down=0.5, quant_bits_up=2,
                             **het)
    spec_nq = st.StrategySpec(kind="flasc", density_down=0.5, **het)

    new_q = final_weights(new_round, spec_q)
    new_nq = final_weights(new_round, spec_nq)
    assert not np.array_equal(new_q, new_nq), \
        "2-bit upload quantization must change heterogeneous round outputs"

    legacy_q = final_weights(legacy_round, spec_q)
    legacy_nq = final_weights(legacy_round, spec_nq)
    assert np.array_equal(legacy_q, legacy_nq), \
        "seed het branch ignored quant_bits_up (the bug this PR fixes)"


def test_het_download_quantization_applied():
    """Same regression for the download direction: het runs must quantize
    the per-client download message when quant_bits_down is set."""
    meta, fed, loss_of, backbone, batch, flatP0 = _toy()
    spec_q = st.StrategySpec(kind="hetlora", hetlora_ranks=(1, 2, 3, 4),
                             quant_bits_down=2)
    spec_nq = st.StrategySpec(kind="hetlora", hetlora_ranks=(1, 2, 3, 4))

    def final_weights(spec):
        fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, spec))
        P, _, _, _ = _drive(functools.partial(fn, backbone),
                            st.resolve(spec).init_state, meta, fed, batch,
                            flatP0)
        return np.asarray(P)

    assert not np.array_equal(final_weights(spec_q), final_weights(spec_nq))


def test_ledger_coded_accounting_live():
    """`coded_bytes` is no longer dead code: record_round accumulates the
    practical min(index, bitmap) coding per direction."""
    led = CommLedger(total_params=1000)
    led.record_round(n_clients=4, down_nnz=250, up_nnz_total=400)
    # down: 1000 values over 4 messages -> bitmap coding wins
    assert led.down_coded_bytes == min(1000 * 8, 1000 * 4 + 125 * 4)
    assert led.up_coded_bytes == min(400 * 8, 400 * 4 + 125 * 4)
    assert led.total_coded_bytes == led.down_coded_bytes + led.up_coded_bytes
    # paper-faithful accounting unchanged alongside
    assert led.total_bytes == (1000 + 400) * 4

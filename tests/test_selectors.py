"""Unified Top-K selector layer (`core/selectors.py`).

Covers, per ISSUE 4:
  * the keep-count contract (k=0 / k=n / all-zero deltas) unified across
    `exact`, `histogram`, and `pallas`;
  * bit-for-bit parity of the `pallas` selector (interpret mode) with
    `histogram`, including non-BLOCK-multiple lengths, multi-block grids,
    and vmapped *traced* per-client keep-counts;
  * tie-tolerance agreement of `pallas`/`histogram` with `exact`;
  * the `StrategySpec.selector` field: deprecation of `exact_topk=`,
    checkpoint-shaped round-trip, and every registered strategy kind running one
    federated round under every selector.
"""
import dataclasses
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import fedround
from repro.core import selectors as sel
from repro.core import sparsity as sp
from repro.core import strategies as st
from repro.core import transport as tp
from repro.models.config import FederatedConfig

SELECTORS = ("exact", "histogram", "pallas")
# small explicit block: exercises the multi-block grid + padding path in
# interpret mode without 64K-element test vectors
SMALL_BLOCK = 512


def _vec(n, seed=0):
    return jax.random.normal(jax.random.key(seed), (n,))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_registry_names_and_resolution():
    assert set(SELECTORS) <= set(sel.registered_selectors())
    for name in SELECTORS:
        s = sel.resolve_selector(name)
        assert s.name == name
        assert sel.resolve_selector(s) is s          # instances pass through
    # default instances are cached per name
    assert sel.resolve_selector("pallas") is sel.resolve_selector("pallas")
    with pytest.raises(KeyError):
        sel.resolve_selector("nope")
    with pytest.raises(TypeError):
        sel.resolve_selector(42)


# ---------------------------------------------------------------------------
# keep-count contract: k=0 / k=n / all-zero, identical clamping everywhere
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("name", SELECTORS)
def test_count_contract_k0_kn_allzero(name):
    s = sel.resolve_selector(name)
    n = 257                                          # non-BLOCK-multiple
    x = _vec(n, seed=1)

    # k = 0 keeps nothing on every selector (the unified contract)
    m0 = s.mask_by_count(x, 0)
    v0, c0 = s.sparsify_by_count(x, 0)
    assert int(jnp.sum(m0)) == 0
    assert int(c0) == 0 and int(jnp.sum(v0 != 0)) == 0

    # k = n keeps everything (x has no exact zeros)
    assert int(jnp.sum(s.mask_by_count(x, n))) == n

    # k > n clamps to n; negative k clamps to 0
    assert int(jnp.sum(s.mask_by_count(x, n + 100))) == n
    assert int(jnp.sum(s.mask_by_count(x, -3))) == 0

    # all-zero delta: exact keeps exactly k by positional tie-break; the
    # histogram family thresholds at |x| >= max(thr, TINY) and so never
    # keeps exact zeros
    z = jnp.zeros((n,))
    nz = int(jnp.sum(s.mask_by_count(z, 5)))
    assert nz == (5 if name == "exact" else 0)
    vz, cz = s.sparsify_by_count(z, 5)
    assert int(jnp.sum(vz != 0)) == 0                # values are zero anyway


@pytest.mark.fast
def test_clamp_count_is_the_single_contract_site():
    assert int(sp.clamp_count(-1, 10)) == 0
    assert int(sp.clamp_count(99, 10)) == 10
    assert sp.clamp_count(jnp.asarray([3, -2, 40]), 10).tolist() == [3, 0, 10]


# ---------------------------------------------------------------------------
# pallas == histogram, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("n", [511, 512, 3 * SMALL_BLOCK + 17])
def test_pallas_matches_histogram_bitwise(n):
    # n spans sub-block with padding, one exact block, and a multi-block
    # grid with a ragged tail
    hist = sel.resolve_selector("histogram")
    pal = sel.PallasSelector(block=SMALL_BLOCK)
    x = _vec(n, seed=2)
    for k in (1, n // 7, n):
        vh, ch = hist.sparsify_by_count(x, k)
        vp, cp = pal.sparsify_by_count(x, k)
        np.testing.assert_array_equal(np.asarray(vh), np.asarray(vp))
        assert int(ch) == int(cp)
        np.testing.assert_array_equal(np.asarray(hist.mask_by_count(x, k)),
                                      np.asarray(pal.mask_by_count(x, k)))
    for d in (0.25, 1.0):
        np.testing.assert_array_equal(np.asarray(hist.mask(x, d)),
                                      np.asarray(pal.mask(x, d)))
        vh, ch = hist.sparsify(x, d)
        vp, cp = pal.sparsify(x, d)
        np.testing.assert_array_equal(np.asarray(vh), np.asarray(vp))
        assert int(ch) == int(cp)


@pytest.mark.fast
def test_pallas_matches_histogram_vmapped_traced_counts():
    """The heterogeneous upload path: per-client traced keep-counts under
    jit(vmap(...)) — the argsort-inside-vmap replacement."""
    hist = sel.resolve_selector("histogram")
    pal = sel.PallasSelector(block=SMALL_BLOCK)
    xb = jax.random.normal(jax.random.key(3), (5, 1000))
    ks = jnp.asarray([0, 1, 137, 999, 1000], jnp.int32)
    fp = jax.jit(jax.vmap(pal.sparsify_by_count))
    fh = jax.jit(jax.vmap(hist.sparsify_by_count))
    vp, cp = fp(xb, ks)
    vh, ch = fh(xb, ks)
    np.testing.assert_array_equal(np.asarray(vp), np.asarray(vh))
    np.testing.assert_array_equal(np.asarray(cp), np.asarray(ch))
    # batched arrays without an explicit vmap take the same path
    vb, cb = pal.sparsify_by_count(xb, ks)
    np.testing.assert_array_equal(np.asarray(vb), np.asarray(vh))
    np.testing.assert_array_equal(np.asarray(cb), np.asarray(ch))


@pytest.mark.fast
def test_pallas_default_block_padding():
    """Default (auto-tuned) block: one interpret-mode block covering the
    whole padded vector, still bit-identical to histogram."""
    n = 70000                                        # > BLOCK, not a multiple
    x = _vec(n, seed=4)
    vh, ch = sel.sparsify_by_count(x, n // 3, selector="histogram")
    vp, cp = sel.sparsify_by_count(x, n // 3, selector="pallas")
    np.testing.assert_array_equal(np.asarray(vh), np.asarray(vp))
    assert int(ch) == int(cp)


@pytest.mark.fast
@pytest.mark.parametrize("name", SELECTORS)
def test_selectors_preserve_value_dtype(name):
    """Drop-in interchangeability: sparsified values come back in the
    caller's dtype (selection itself always runs in f32)."""
    s = sel.resolve_selector(name) if name != "pallas" \
        else sel.PallasSelector(block=SMALL_BLOCK)
    x = _vec(300, seed=7).astype(jnp.bfloat16)
    v, _ = s.sparsify_by_count(x, 30)
    assert v.dtype == jnp.bfloat16
    v, _ = s.sparsify(x, 0.25)
    assert v.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# agreement with exact (up to ties / probe resolution)
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("name", ["histogram", "pallas"])
def test_threshold_selectors_agree_with_exact_on_continuous_data(name):
    s = sel.resolve_selector(name)
    n, k = 4096, 1024
    x = _vec(n, seed=5)                              # continuous: no ties
    m_exact = sel.topk_mask_by_count(x, k, selector="exact")
    m = s.mask_by_count(x, k)
    nnz = int(jnp.sum(m))
    # bisection keeps the smallest magnitude-superset it can resolve:
    # >= k entries, and every exact top-k entry is in it
    assert k <= nnz <= k + 2
    assert bool(jnp.all(jnp.logical_or(~m_exact, m)))


# ---------------------------------------------------------------------------
# transport / spec plumbing
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_transport_topk_stage_takes_selector():
    x = _vec(2000, seed=6)
    for selector in ("histogram", sel.PallasSelector(block=SMALL_BLOCK)):
        msg = tp.TopKSparsify(density=0.25, selector=selector)(tp.Message.dense(x))
        ref = sel.sparsify(x, 0.25, selector=selector)
        np.testing.assert_array_equal(np.asarray(msg.values), np.asarray(ref[0]))
        assert int(msg.nnz) == int(ref[1])
    pipe = tp.upload_pipeline(st.UploadRule.topk(0.25), selector="histogram")
    msg = pipe(x)
    assert int(msg.nnz) == int(sel.sparsify(x, 0.25, selector="histogram")[1])


@pytest.mark.fast
def test_exact_topk_deprecated_alias_works_and_warns():
    with pytest.warns(DeprecationWarning, match="exact_topk"):
        spec = st.StrategySpec(kind="flasc", exact_topk=True)
    assert spec.selector == "exact"
    # the alias is consumed by the mapping, so a legacy spec migrates
    # cleanly through the documented override path and never persists
    # the deprecated field (e.g. into checkpoints)
    assert spec.exact_topk is None
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        moved = dataclasses.replace(spec, selector="pallas")
    assert moved.selector == "pallas"
    with pytest.warns(DeprecationWarning):
        spec = st.StrategySpec(kind="flasc", exact_topk=False)
    assert spec.selector == "histogram"
    # the default spec neither warns nor sets the legacy field
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        spec = st.StrategySpec(kind="flasc")
    assert spec.selector == "exact" and spec.exact_topk is None
    # conflicts are symmetric: an explicit selector never silently loses
    # to the deprecated boolean, in either direction
    with pytest.raises(ValueError, match="conflicting"):
        st.StrategySpec(kind="flasc", selector="histogram", exact_topk=True)
    with pytest.raises(ValueError, match="conflicting"):
        st.StrategySpec(kind="flasc", selector="exact", exact_topk=False)
    with pytest.raises(ValueError, match="unknown selector"):
        st.StrategySpec(kind="flasc", selector="sorting-hat")


@pytest.mark.fast
def test_selector_spec_checkpoint_roundtrip():
    """The exact shape `Experiment` checkpoints use: dataclasses.asdict ->
    json -> StrategySpec(**fields) must preserve the selector and must not
    re-trigger the deprecation warning."""
    spec = st.StrategySpec(kind="flasc", selector="pallas",
                           client_densities=(0.1, 0.5))
    sj = json.loads(json.dumps(dataclasses.asdict(spec)))
    for key in ("client_densities", "hetlora_ranks"):
        sj[key] = tuple(sj.get(key, ()))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        back = st.StrategySpec(**sj)
    assert back == spec and back.selector == "pallas"
    # legacy checkpoint payload (pre-selector): exact_topk only
    legacy = dict(sj, exact_topk=False)
    legacy.pop("selector")
    with pytest.warns(DeprecationWarning):
        old = st.StrategySpec(**legacy)
    assert old.selector == "histogram"


# ---------------------------------------------------------------------------
# strategy level: every registered kind x all selectors through one round
# ---------------------------------------------------------------------------

def _tiny_problem():
    tree0 = {"lora": {"l": {"a": jnp.zeros((10, 5), jnp.float32),
                            "b": jnp.zeros((5, 50), jnp.float32)}}}
    meta = fedround.FlatMeta.of(tree0)
    fed = FederatedConfig(n_clients=4, local_batch=2, local_steps=2,
                          client_lr=0.1, client_momentum=0.0, server_lr=0.1)

    def loss_of(bb, tree, mb):
        flat = jnp.concatenate([tree["lora"]["l"]["a"].reshape(-1),
                                tree["lora"]["l"]["b"].reshape(-1)])
        return jnp.sum((flat - jnp.mean(mb["t"])) ** 2)

    batches = {"t": jax.random.normal(jax.random.key(0), (4, 2, 2, 3))}
    flat0 = meta.flatten(tree0) + 0.01 * jax.random.normal(
        jax.random.key(9), (meta.p_len,))
    return meta, fed, loss_of, batches, flat0


def _one_round(spec, meta, fed, loss_of, batches, flat0):
    strat = st.resolve(spec)
    return fedround.federated_round(
        {}, flat0, fedround.init_server(flat0), strat.init_state(meta.p_len),
        batches, None, loss_of=loss_of, meta=meta, fed=fed, strategy=strat)


@pytest.mark.fast
@pytest.mark.parametrize("selector", SELECTORS)
def test_all_kinds_run_under_every_selector(selector):
    meta, fed, loss_of, batches, flat0 = _tiny_problem()
    kind_kw = {kind: {} for kind in st.registered_kinds()}
    kind_kw["hetlora"] = dict(hetlora_ranks=(1, 2, 3, 5))
    kind_kw["flocora"] = dict(lowrank_down=2, lowrank_up=2)
    for kind, kw in kind_kw.items():
        spec = st.StrategySpec(kind=kind, selector=selector, **kw)
        flatP, server, sstate, m = _one_round(spec, meta, fed, loss_of,
                                              batches, flat0)
        assert np.isfinite(float(m["loss"])), (kind, selector)
        assert np.all(np.isfinite(np.asarray(flatP))), (kind, selector)


@pytest.mark.fast
def test_het_densities_round_pallas_matches_histogram():
    """flasc with per-client densities: the traced-count upload path
    produces bit-identical rounds under histogram and pallas."""
    meta, fed, loss_of, batches, flat0 = _tiny_problem()
    outs = {}
    for selector in ("histogram", "pallas"):
        spec = st.StrategySpec(kind="flasc", selector=selector,
                               client_densities=(0.1, 0.25, 0.5, 1.0))
        flatP, server, sstate, m = _one_round(spec, meta, fed, loss_of,
                                              batches, flat0)
        outs[selector] = (np.asarray(flatP), np.asarray(m["up_nnz"]),
                          np.asarray(m["down_nnz"]))
    np.testing.assert_array_equal(outs["histogram"][0], outs["pallas"][0])
    np.testing.assert_array_equal(outs["histogram"][1], outs["pallas"][1])
    np.testing.assert_array_equal(outs["histogram"][2], outs["pallas"][2])


@pytest.mark.fast
def test_deprecated_exact_topk_round_is_bitwise_default_round():
    """exact_topk=True must still select the seed-exact path: same round
    output bit-for-bit as the selector="exact" default."""
    meta, fed, loss_of, batches, flat0 = _tiny_problem()
    with pytest.warns(DeprecationWarning):
        legacy_spec = st.StrategySpec(kind="flasc", exact_topk=True)
    a = _one_round(legacy_spec, meta, fed, loss_of, batches, flat0)
    b = _one_round(st.StrategySpec(kind="flasc"), meta, fed, loss_of,
                   batches, flat0)
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(np.asarray(a[3]["up_nnz"]),
                                  np.asarray(b[3]["up_nnz"]))

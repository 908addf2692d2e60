"""Differential + property tests for the packed upload path: the fused
selector's in-kernel pack == the `fused_transport.pack_values` reference
codec, pack -> unpack is exact, `sparse_accumulate` equals the row-ordered
dense sum, the hierarchical edge -> server reduction equals the flat
scatter-add bit for bit, and the synchronous round's row fold gives the
packed scatter-add's bits (or the dense mean's on overflow).  The
selection and quantization kernels are in test_fused_transport.py.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm
from repro.core import fedround
from repro.core import selectors as sel
from repro.core import sparsity as sp
from repro.core import strategies as st
from repro.core import transport as tp
from repro.kernels import fused_transport as ft
from tests._hypcompat import given, settings, hst

pytestmark = pytest.mark.fast


def _fused():
    return sel.FusedSelector(levels=12)


def _vec(n, seed=0):
    return jax.random.normal(jax.random.key(seed), (n,))


# ---------------------------------------------------------------------------
# the in-kernel pack vs the reference codec
# ---------------------------------------------------------------------------

def test_fused_pack_matches_reference_codec():
    x = _vec(1000, 6)
    fused = _fused()
    for k, bits in ((0, 0), (1, 0), (100, 4), (333, 8), (1000, 0)):
        key = jax.random.key(k) if bits else None
        cap = comm.pack_capacity(1000, k)
        vals, nnz, idx, val = fused.sparsify_quantized_packed(
            x, count=k, bits=bits, key=key, cap=cap)
        ridx, rval, rnnz = ft.pack_values(vals, cap)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(val), np.asarray(rval))
        # kernel nnz counts threshold survivors; the reference counts
        # nonzero *values* (quantization may round a survivor to zero),
        # so kernel nnz >= reference nnz and unpacking is still exact
        assert int(nnz) >= int(rnnz)
        np.testing.assert_array_equal(
            np.asarray(ft.unpack_values(idx, val, 1000)), np.asarray(vals))


def test_fused_pack_overflow_flags_without_corrupting():
    x = jnp.ones((512,))                        # fully tied: keeps all 512
    cap = 64
    vals, nnz, idx, val = _fused().sparsify_quantized_packed(
        x, count=32, bits=0, key=None, cap=cap)
    assert int(nnz) > cap                       # overflow is flagged...
    ridx, rval, rnnz = ft.pack_values(vals, cap)
    np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(val), np.asarray(rval))
    assert int(rnnz) == int(nnz)                # ...and counted in full


@settings(deadline=None, max_examples=6)
@given(hst.integers(1, 2048), hst.floats(0.0, 1.0), hst.integers(0, 2 ** 31))
def test_pack_unpack_roundtrip_property(n, density, seed):
    """pack -> unpack is bit-exact at capacity >= nnz, for random shapes
    and densities (satellite: the wire-format round-trip property)."""
    key = jax.random.key(seed)
    x = jax.random.normal(key, (n,))
    x = x * (jax.random.uniform(jax.random.fold_in(key, 1), (n,)) < density)
    idx, val, nnz = ft.pack_values(x, n)
    assert int(nnz) == int(jnp.sum(x != 0))
    np.testing.assert_array_equal(np.asarray(ft.unpack_values(idx, val, n)),
                                  np.asarray(x, np.float32))
    # ascending indices, sentinel n in the empty tail
    host = np.asarray(idx)
    assert (host[: int(nnz)] == np.flatnonzero(np.asarray(x))).all()
    assert (host[int(nnz):] == n).all()


def test_sparse_accumulate_matches_row_ordered_sum():
    X = jax.random.normal(jax.random.key(12), (6, 800))
    X = X * (jnp.abs(X) > 1.0)                  # sparse rows
    cap = int(jnp.max(jnp.sum(X != 0, axis=1)))
    idx, val, nnz = jax.vmap(lambda v: ft.pack_values(v, cap))(X)
    got = ft.sparse_accumulate(idx, val, 800)
    want = functools.reduce(lambda a, b: a + b, list(X))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# property: hierarchical edge -> server reduction == flat scatter-add,
# bitwise, for edge_shards in 1..8 x overflow x all-zero/tied inputs
# (the docs/scale.md bit-equality claim; deterministic spot checks live in
# tests/test_population.py)
# ---------------------------------------------------------------------------

def _packed_rows(n, cap, clients, mode, seed):
    rng = np.random.default_rng(seed)
    if mode == "all_zero":
        val = np.zeros((clients, cap), np.float32)
    elif mode == "tied":
        # every kept magnitude identical (only signs differ): per-coordinate
        # sums cancel or tie, the worst case for association-order claims
        val = (0.5 * rng.choice([-1.0, 1.0], (clients, cap))).astype(np.float32)
    else:
        val = rng.normal(0, 1, (clients, cap)).astype(np.float32)
    if mode == "overflow":
        # every slot occupied, duplicate coordinates allowed: nnz == cap
        # exceeds the k the capacity was sized for (engines call this
        # overflow and fall back to dense) — the kernels must still agree
        idx = rng.integers(0, n, (clients, cap))
    else:
        # pack_values layout: a sorted prefix of kept coordinates, the tail
        # parked at the sentinel n (dropped by both reductions; values left
        # nonzero on purpose to stress the drop path)
        idx = np.full((clients, cap), n, np.int64)
        for c in range(clients):
            nnz = int(rng.integers(0, min(cap, n) + 1))
            idx[c, :nnz] = np.sort(rng.choice(n, size=nnz, replace=False))
    return jnp.asarray(idx), jnp.asarray(val)


@settings(deadline=None, max_examples=10)
@given(hst.integers(1, 8), hst.integers(16, 3000), hst.integers(1, 6),
       hst.sampled_from(("random", "all_zero", "tied", "overflow")),
       hst.integers(0, 10_000))
def test_hierarchical_accumulate_matches_flat_property(edges, n, clients,
                                                       mode, seed):
    cap = comm.pack_capacity(n, max(n // 8, 1))
    idx, val = _packed_rows(n, cap, clients, mode, seed)
    flat = ft.sparse_accumulate(idx, val, n)
    hier = ft.hierarchical_accumulate(idx, val, n, edges)
    assert hier.shape == flat.shape and hier.dtype == flat.dtype
    # bitwise, not allclose: compare the raw f32 words
    assert np.array_equal(np.asarray(flat).view(np.uint32),
                          np.asarray(hier).view(np.uint32))


# ---------------------------------------------------------------------------
# sparse-aggregation gating + the packed server reduction
# ---------------------------------------------------------------------------

def test_supports_sparse_aggregate_gating():
    on = st.resolve(st.StrategySpec(kind="flasc", sparse_aggregate=True))
    assert st.supports_sparse_aggregate(on)
    assert st.sparse_aggregate_capacity(on, 10_000) == \
        comm.pack_capacity(10_000, sp.density_count(10_000, on.spec.density_up))
    # off by default
    assert not st.supports_sparse_aggregate(
        st.resolve(st.StrategySpec(kind="flasc")))
    # weighted-aggregate override keeps the dense stack
    assert not st.supports_sparse_aggregate(st.resolve(st.StrategySpec(
        kind="hetlora", hetlora_ranks=(1, 2), hetlora_weighted=True,
        sparse_aggregate=True)))
    # per-client densities / low-rank uploads stay dense
    assert not st.supports_sparse_aggregate(st.resolve(st.StrategySpec(
        kind="flasc", client_densities=(0.1, 0.5), sparse_aggregate=True)))
    assert not st.supports_sparse_aggregate(st.resolve(st.StrategySpec(
        kind="flasc", lowrank_up=4, sparse_aggregate=True)))


def test_aggregate_sparse_matches_dense_mean():
    strat = st.resolve(st.StrategySpec(kind="flasc", sparse_aggregate=True))
    X = jax.random.normal(jax.random.key(13), (4, 600))
    X = X * (jnp.abs(X) > 1.2)
    cap = int(jnp.max(jnp.sum(X != 0, axis=1)))
    idx, val, _ = jax.vmap(lambda v: ft.pack_values(v, cap))(X)
    ctx = st.PlanContext(p_len=600, n_clients=4, round_idx=0,
                         rank_idx=None, is_b=None)
    got = strat.aggregate_sparse(idx, val, ctx)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(strat.aggregate(X, ctx)),
                               rtol=1e-6, atol=1e-7)


def _packed_aggregate(strat, deltas, ctx):
    """The packed form of the sparse aggregation, which the AsyncEngine
    runs on its jobs: the batched pack, the (flat or edge-tree)
    scatter-add, and the dense mean when a row overflows the capacity."""
    cap = st.sparse_aggregate_capacity(strat, ctx.p_len)
    idx, val, pnnz = ft.pack_values_batch(deltas, cap)
    return jnp.where(jnp.any(pnnz > cap), strat.aggregate(deltas, ctx),
                     strat.aggregate_sparse(idx, val, ctx))


@pytest.mark.parametrize("clients,edges,overflow", [
    (1, 0, False), (1, 2, False), (3, 0, False), (3, 2, False),
    (32, 0, False), (32, 2, False), (3, 0, True), (32, 2, True)])
def test_round_fold_matches_packed_aggregation_bitwise(clients, edges,
                                                       overflow):
    """The synchronous round's row fold over the dense upload stack gives
    the packed scatter-add's bits on fused 4-bit Top-K uploads, and the
    dense mean's when a row holds more nonzeros than the capacity."""
    p_len = 1000                        # not a lane multiple: the pack pads
    strat = st.resolve(st.StrategySpec(
        kind="flasc", density_up=0.25, selector="fused", quant_bits_up=4,
        sparse_aggregate=True, edge_shards=edges))
    pipe = tp.upload_pipeline(st.UploadRule.topk(0.25), quant_bits=4,
                              selector="fused")
    X = jax.random.normal(jax.random.key(clients), (clients, p_len))
    keys = jax.random.split(jax.random.key(100 + clients), clients)
    deltas = jax.vmap(lambda x, k: pipe(x, key=k).values)(X, keys)
    if overflow:                        # one dense row: p_len > cap
        deltas = deltas.at[clients // 2].set(X[clients // 2])
    cap = st.sparse_aggregate_capacity(strat, p_len)
    assert bool(jnp.any(jnp.sum(deltas != 0, axis=1) > cap)) == overflow
    ctx = st.PlanContext(p_len=p_len, n_clients=clients, round_idx=0,
                         rank_idx=None, is_b=None)
    got = jax.jit(lambda d: fedround._aggregate_uploads(strat, d, ctx))(deltas)
    want = jax.jit(lambda d: _packed_aggregate(strat, d, ctx))(deltas)
    bits = lambda a: np.asarray(a).view(np.uint32)      # noqa: E731
    np.testing.assert_array_equal(bits(got), bits(want))
    if overflow:
        np.testing.assert_array_equal(bits(got),
                                      bits(strat.aggregate(deltas, ctx)))

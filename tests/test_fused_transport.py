"""Differential + property tests for the one-pass transport kernels.

The fused pipeline's correctness claim is *bitwise*, not approximate:

  * `FusedSelector(levels=L)` == `HistogramSelector(iters=L)` — same
    masked values, same nnz — on every shape/edge the histogram path
    supports: k=0, k=n, all-zero deltas, tied magnitudes,
    non-block-multiple lengths, vmapped per-client traced keep-counts,
    interpret and jit-compiled paths.
  * the fused mask+quantize pass == the two-stage Top-K -> `quantization.
    quantize_roundtrip` form under the same key, at the stage level too
    (`transport.FusedTopKQuantize` vs `TopKSparsify` + `Quantize`).
  * the batched in-kernel pack == the vmapped per-row
    `fused_transport.pack_values` codec.

The packed wire format and the sparse server aggregation are in
test_sparse_aggregation.py.

Plus the property-based wire-format layer (via tests/_hypcompat.py, so it
runs with or without hypothesis installed): `Pipeline.wire` /
`wire_format` / `CommLedger` coded bytes match the closed-form formulas
for every stage pipeline x quantize width x coding x selector combination
at random shapes/densities.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm
from repro.core import quantization as qz
from repro.core import selectors as sel
from repro.core import sparsity as sp
from repro.core import strategies as st
from repro.core import transport as tp
from repro.kernels import fused_transport as ft
from tests._hypcompat import given, settings, hst

pytestmark = pytest.mark.fast

LEVELS = 12     # matched depth: FusedSelector(levels=L) vs Histogram(iters=L)


def _fused(**kw):
    return sel.FusedSelector(levels=LEVELS, **kw)


def _hist():
    return sel.HistogramSelector(iters=LEVELS)


def _vec(n, seed=0, scale=1.0):
    return jax.random.normal(jax.random.key(seed), (n,)) * scale


# ---------------------------------------------------------------------------
# threshold: binned path-replay == streaming bisection, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [128, 1000, 4096])
@pytest.mark.parametrize("seed", [0, 1])
def test_threshold_from_bins_matches_histogram_bisection(n, seed):
    x = _vec(n, seed)
    a = jnp.abs(x)
    block = min(-(-n // 128) * 128, 1 << 26)
    pad = jnp.pad(a, (0, block - n % block if n % block else 0))
    hi0 = ft.absmax_pallas(pad, block=block, interpret=True)
    hist = ft.bin_counts_pallas(pad, hi0, LEVELS, block=block, interpret=True)
    for k in (0, 1, n // 7, n // 2, n - 1, n):
        got = ft.threshold_from_bins(hist, hi0, jnp.asarray(k), LEVELS)
        want = sp.threshold_histogram_count(a, jnp.asarray(k), LEVELS)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=f"k={k}")


# ---------------------------------------------------------------------------
# bin histogram: the kernel == a plain path replay + bincount, bit for bit
# ---------------------------------------------------------------------------

def _bins_reference(x, hi0, levels):
    """Every element's `levels`-step bisection path from (0, hi0), as the
    kernel replays it, then `jnp.bincount` of the path indices."""
    a = jnp.abs(x.astype(jnp.float32))
    lo = jnp.zeros_like(a)
    hi = jnp.full_like(a, hi0)
    idx = jnp.zeros(a.shape, jnp.int32)
    for _ in range(levels):
        mid = 0.5 * (lo + hi)
        up = a >= mid
        idx = idx * 2 + up.astype(jnp.int32)
        lo = jnp.where(up, mid, lo)
        hi = jnp.where(up, hi, mid)
    return jnp.bincount(idx, length=1 << levels)


def _assert_bins(x, hi0, levels=LEVELS, block=None):
    n = x.shape[-1]
    got = ft.bin_counts_pallas(x, hi0, levels, block=block or n,
                               interpret=True)
    want = _bins_reference(x, hi0, levels)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert int(got.sum()) == n


@pytest.mark.parametrize("block", [384, 1024, 4096, 12288])
def test_bin_counts_multiblock_grid_matches_reference(block):
    """12,288 entries cut into 32, 12, 3 and 1 blocks of 3 to 96 rows."""
    x = _vec(12288, 21)
    _assert_bins(x, jnp.max(jnp.abs(x)), block=block)


def test_bin_counts_vmapped_clients_match_reference():
    """Clients of different scales, each binned against its own `hi0`
    (one of them above its absmax), as the engines' vmapped uploads."""
    X = jax.random.normal(jax.random.key(22), (3, 4096))
    X = X * jnp.asarray([1.0, 1e-3, 40.0])[:, None]
    hi0 = jnp.max(jnp.abs(X), axis=1) * jnp.asarray([1.0, 1.0, 1.5])
    got = jax.vmap(lambda x, h: ft.bin_counts_pallas(
        x, h, LEVELS, block=1024, interpret=True))(X, hi0)
    want = jax.vmap(lambda x, h: _bins_reference(x, h, LEVELS))(X, hi0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("levels", [LEVELS, 11])
def test_bin_counts_edges_match_reference(levels):
    """All zeros (every path runs up: one full top bin), ties on every
    bin midpoint, and a single nonzero entry; 11 levels give nh != nl."""
    ties = jnp.arange(4096, dtype=jnp.float32) / 4096.0   # dyadic midpoints
    one = jnp.zeros((2048,)).at[1234].set(-0.75)
    for x, hi0 in ((jnp.zeros((1024,)), 0.0), (ties, 1.0), (-ties, 1.0),
                   (one, 0.75)):
        _assert_bins(x, jnp.float32(hi0), levels)
        _assert_bins(x, jnp.float32(hi0), levels, block=256)
    zeros = ft.bin_counts_pallas(jnp.zeros((1024,)), jnp.float32(0.0),
                                 levels, block=1024, interpret=True)
    assert int(zeros[-1]) == 1024


# ---------------------------------------------------------------------------
# selector differential: fused == histogram on every edge
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [64, 333, 1000, 4096])
def test_fused_selector_matches_histogram_bitwise(n):
    fused, hist = _fused(), _hist()
    x = _vec(n, 3)
    for k in (0, 1, max(n // 5, 1), n - 1, n):
        vf, cf = fused.sparsify_by_count(x, k)
        vh, ch = hist.sparsify_by_count(x, k)
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vh),
                                      err_msg=f"n={n} k={k}")
        assert int(cf) == int(ch), (n, k)


@pytest.mark.parametrize("density", [0.01, 0.25, 0.5, 1.0])
def test_fused_selector_density_path(density):
    x = _vec(777, 5)
    vf, cf = _fused().sparsify(x, density)
    vh, ch = _hist().sparsify(x, density)
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vh))
    assert int(cf) == int(ch)


def test_fused_selector_edge_vectors():
    fused, hist = _fused(), _hist()
    edges = [
        jnp.zeros((256,)),                                  # all-zero delta
        jnp.concatenate([jnp.full((100,), 2.0),             # tied at the
                         jnp.full((100,), 1.0)]),           # threshold
        jnp.asarray([1e-38] * 50 + [0.0] * 50),             # subnormal-ish
        -jnp.ones((130,)),                                  # full negative
                                                            # ties, odd length
    ]
    for x in edges:
        n = x.shape[0]
        for k in (0, 1, n // 2, n):
            vf, cf = fused.sparsify_by_count(x, k)
            vh, ch = hist.sparsify_by_count(x, k)
            np.testing.assert_array_equal(np.asarray(vf), np.asarray(vh))
            assert int(cf) == int(ch)


def test_fused_selector_vmapped_traced_counts():
    """The engine path: per-client keep-counts ride the vmapped axis as
    tracers (heterogeneous cohorts)."""
    X = jax.random.normal(jax.random.key(9), (5, 640))
    ks = jnp.asarray([0, 1, 64, 639, 640], jnp.int32)
    fused, hist = _fused(), _hist()
    vf, cf = jax.vmap(fused.sparsify_by_count)(X, ks)
    vh, ch = jax.vmap(hist.sparsify_by_count)(X, ks)
    np.testing.assert_array_equal(np.asarray(vf), np.asarray(vh))
    np.testing.assert_array_equal(np.asarray(cf), np.asarray(ch))


def test_fused_selector_under_jit():
    """Compiled (jit) path, including the k=0 / k=n guards as traced
    operands."""
    x = _vec(1000, 11)
    fused, hist = _fused(), _hist()
    f = jax.jit(fused.sparsify_by_count)
    h = jax.jit(hist.sparsify_by_count)
    for k in (0, 1, 100, 999, 1000):
        vf, cf = f(x, jnp.asarray(k))
        vh, ch = h(x, jnp.asarray(k))
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vh))
        assert int(cf) == int(ch)


# ---------------------------------------------------------------------------
# fused quantization: one kernel pass == mask then quantize_roundtrip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [2, 4, 8])
@pytest.mark.parametrize("stochastic", [False, True])
def test_fused_quantize_matches_two_pass(bits, stochastic):
    x = _vec(1000, 2)
    key = jax.random.key(7) if stochastic else None
    fused, hist = _fused(), _hist()
    for k in (0, 1, 250, 1000):
        vf, cf = fused.sparsify_quantized(x, count=k, bits=bits, key=key)
        vh, ch = hist.sparsify_by_count(x, k)
        vq = qz.quantize_roundtrip(vh, bits, key)
        np.testing.assert_array_equal(np.asarray(vf), np.asarray(vq),
                                      err_msg=f"bits={bits} k={k}")
        assert int(cf) == int(ch)


def test_fused_quantize_density_one_shortcut():
    """density >= 1 skips masking entirely — plain quantization, exactly
    like the separate Quantize stage."""
    x = _vec(500, 4)
    key = jax.random.key(3)
    vf, cf = _fused().sparsify_quantized(x, density=1.0, bits=4, key=key)
    np.testing.assert_array_equal(np.asarray(vf),
                                  np.asarray(qz.quantize_roundtrip(x, 4, key)))
    assert int(cf) == x.shape[0]


# ---------------------------------------------------------------------------
# stage-level differential: FusedTopKQuantize == TopKSparsify + Quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("bits", [0, 4])
def test_fused_stage_matches_two_stage_pipeline(bits):
    x = _vec(900, 8)
    key = jax.random.key(5) if bits else None
    two = tp.Pipeline((tp.TopKSparsify(count=90, selector=_hist()),
                       tp.Quantize(bits)))
    one = tp.Pipeline((tp.FusedTopKQuantize(count=90, bits=bits,
                                            selector=_fused()),))
    ma, mb = two(x, key=key), one(x, key=key)
    np.testing.assert_array_equal(np.asarray(ma.values), np.asarray(mb.values))
    assert int(ma.nnz) == int(mb.nnz)
    assert ma.value_bits == mb.value_bits
    assert two.wire(900) == one.wire(900)


def test_upload_pipeline_routes_fused_selector():
    rule = st.UploadRule.topk(0.1)
    pipe = tp.upload_pipeline(rule, quant_bits=4, selector="fused")
    assert len(pipe.stages) == 1
    assert isinstance(pipe.stages[0], tp.FusedTopKQuantize)
    assert tp.resolve_stage("fused_topk_quantize") is tp.FusedTopKQuantize
    # low-rank owns the quantization: the fused stage must not be used
    lr = tp.LowRankCompress(rank=2, bits=4)
    pipe_lr = tp.upload_pipeline(rule, quant_bits=4, selector="fused",
                                 lowrank=lr)
    assert isinstance(pipe_lr.stages[0], tp.TopKSparsify)
    assert pipe_lr.stages[-1] is lr


# ---------------------------------------------------------------------------
# property-based wire-format closed forms (every stage combo x width x
# coding x selector, random shapes/densities)
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=10)
@given(hst.sampled_from(("exact", "histogram", "pallas", "fused")),
       hst.sampled_from((0, 2, 4, 8)),
       hst.sampled_from((0, 2)),
       hst.integers(64, 100_000),
       hst.floats(0.01, 1.0))
def test_wire_format_closed_form_property(selector, bits, lowrank, n, density):
    """`wire_format` and `Pipeline.wire` agree with the closed form for
    every selector x quantize width x coding combination: value width =
    quantize bits (32 if off), dense-coded iff low-rank compressed."""
    spec = st.StrategySpec(kind="flasc", selector=selector,
                           density_up=density, quant_bits_up=bits,
                           lowrank_up=lowrank)
    vb, dense = tp.wire_format(spec, n, "up")
    lr = tp.lowrank_stage(spec, "up")
    lr_active = lr is not None and lr.active(n)
    assert dense is lr_active
    assert vb == (float(bits) if bits else 32.0) / 8.0
    # the actual upload pipeline (which may fuse stages) must declare the
    # same wire format the spec-level dispatch promises
    pipe = tp.upload_pipeline(st.UploadRule.topk(density), bits,
                              selector=selector, lowrank=lr)
    assert pipe.wire(n) == (vb * 8.0, dense)


@settings(deadline=None, max_examples=10)
@given(hst.sampled_from((0, 2, 4, 8)), hst.integers(64, 100_000),
       hst.integers(0, 100_000), hst.integers(1, 32))
def test_ledger_coded_bytes_closed_form_property(bits, n, nnz, clients):
    """`CommLedger` coded bytes == the index-vs-bitmap closed form at the
    pipeline's declared width, per message."""
    nnz = min(nnz, n)
    spec = st.StrategySpec(kind="flasc", selector="fused",
                           quant_bits_up=bits)
    vb, dense = tp.wire_format(spec, n, "up")
    led = comm.CommLedger(total_params=n, up_value_bytes=vb, up_dense=dense)
    led.record_round(clients, 0.0, nnz * clients,
                     up_per_message=[nnz] * clients)
    expect_one = min(int(nnz * (vb + comm.INDEX_BYTES)),
                     int(nnz * vb) + n // 8)
    assert led.up_coded_bytes == clients * expect_one
    assert led.up_bytes == int(nnz * clients * vb)


def test_pack_capacity_contract():
    assert comm.pack_capacity(10_000, 0) == 64           # floor slack
    assert comm.pack_capacity(10_000, 1000) == 1125      # k + k//8
    assert comm.pack_capacity(100, 1000) == 100          # never beyond n
    assert comm.pack_capacity(0, 0) == 0


@settings(deadline=None, max_examples=10)
@given(hst.integers(0, 1_000_000), hst.integers(0, 1_000_000))
def test_pack_capacity_property(n, k):
    """pack_capacity is the shared shape contract between the sync and
    async engines (jit caches and bit-equality line up on it), so the
    closed form is pinned, not just spot-checked."""
    cap = comm.pack_capacity(n, k)
    assert cap == min(n, k + max(k // 8, 64))
    assert 0 <= cap <= n                 # never beyond the buffer
    assert cap >= min(n, k)              # every expected Top-K slot fits
    assert cap >= min(n, 64)             # floor slack
    # monotone in both arguments: growing the buffer or the expected
    # support never shrinks the message shape
    assert comm.pack_capacity(n + 1, k) >= cap
    assert comm.pack_capacity(n, k + 1) >= cap


# ---------------------------------------------------------------------------
# batched in-kernel pack (PR 9): pinned against the per-row codec
# ---------------------------------------------------------------------------

def _sparse_rows(B, n, density, seed):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((B, n)).astype(np.float32)
    keep = rng.random((B, n)) < density
    return jnp.asarray(np.where(keep, X, 0.0))


@pytest.mark.parametrize("n", [64, 100, 300])
def test_pack_values_batch_pins_vmapped_codec(n):
    """The engines' batched pack must stay bit-identical to the wire
    codec `pack_values` — idx (incl. the unpadded sentinel n), val, nnz."""
    X = _sparse_rows(B=5, n=n, density=0.3, seed=n)
    cap = int(jnp.max(jnp.sum(X != 0, axis=1))) + 2
    bidx, bval, bnnz = ft.pack_values_batch(X, cap)
    ridx, rval, rnnz = jax.vmap(lambda v: ft.pack_values(v, cap))(X)
    np.testing.assert_array_equal(np.asarray(bidx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(bval), np.asarray(rval))
    np.testing.assert_array_equal(np.asarray(bnnz), np.asarray(rnnz))
    assert int(jnp.max(bidx)) <= n        # sentinel is the unpadded length


def test_pack_values_batch_overflow_matches_reference():
    """nnz > cap rows must flag overflow identically to `pack_values`
    (same truncation order, same reported total)."""
    X = _sparse_rows(B=4, n=128, density=0.9, seed=7)
    cap = 16                              # far below the true nnz
    bidx, bval, bnnz = ft.pack_values_batch(X, cap)
    ridx, rval, rnnz = jax.vmap(lambda v: ft.pack_values(v, cap))(X)
    np.testing.assert_array_equal(np.asarray(bnnz), np.asarray(rnnz))
    assert bool(jnp.all(bnnz > cap))
    np.testing.assert_array_equal(np.asarray(bidx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(bval), np.asarray(rval))


def test_pack_values_batched_pallas_multiblock_grid():
    """Force a multi-block grid (block < n) through the kernel directly:
    the per-row accumulator carries positions across blocks."""
    n, block = 256, 128
    X = _sparse_rows(B=3, n=n, density=0.2, seed=11)
    cap = int(jnp.max(jnp.sum(X != 0, axis=1))) + 1
    bidx, bval, bnnz = ft.pack_values_batched_pallas(
        X, cap, block=block, interpret=True)
    ridx, rval, rnnz = jax.vmap(lambda v: ft.pack_values(v, cap))(X)
    np.testing.assert_array_equal(np.asarray(bidx), np.asarray(ridx))
    np.testing.assert_array_equal(np.asarray(bval), np.asarray(rval))
    np.testing.assert_array_equal(np.asarray(bnnz), np.asarray(rnnz))

"""Transport-pipeline unit tests for the `LowRankCompress` stage and the
dense-coded wire accounting it introduces:

  * no-op edges: rank 0, rank >= min factor dim (degrades to plain
    quantization when the stage carries factor bits);
  * factor math: random mode reconstructs M Q Qᵀ from the seeded
    projection; learned mode is exact on matrices of rank <= `rank`;
  * composition order vs `Quantize` / `TopKSparsify` (the last sizing
    stage owns nnz; the factor stage owns the wire width);
  * `CommLedger` coded-byte accounting: dense-coded factor messages bill
    exactly nnz * value_bytes, asserted against the closed-form
    rows*rank / rank*(rows+cols) formulas through a real
    `federated_round` + per-message `record_round` drive;
  * the transport stage registry and `wire_format` dispatch.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import comm
from repro.core import fedround
from repro.core import strategies as st
from repro.core import transport as tp
from repro.models.config import FederatedConfig

pytestmark = pytest.mark.fast

N = 1000                                    # -> 32 x 32 factor embedding


@pytest.fixture()
def msg():
    x = jax.random.normal(jax.random.key(0), (N,), jnp.float32)
    return tp.Message.dense(x)


# ---------------------------------------------------------------------------
# stage edges + factor math
# ---------------------------------------------------------------------------

def test_factor_dims_near_square():
    assert tp._factor_dims(1000) == (32, 32)
    assert tp._factor_dims(1024) == (32, 32)
    assert tp._factor_dims(1025) == (33, 32)
    assert tp._factor_dims(1) == (1, 1)
    rows, cols = tp._factor_dims(12345)
    assert rows * cols >= 12345

def test_rank_zero_is_noop(msg):
    stage = tp.LowRankCompress(rank=0)
    assert not stage.active(N)
    assert stage(msg) is msg


def test_rank_at_min_dim_is_noop(msg):
    rows, cols = tp._factor_dims(N)
    stage = tp.LowRankCompress(rank=min(rows, cols))
    assert not stage.active(N)
    assert stage(msg) is msg
    # an inactive stage still owns its factor quantization: it degrades to
    # a plain Quantize of the surviving values
    q = tp.LowRankCompress(rank=min(rows, cols), bits=8)(msg)
    ref = tp.Quantize(8)(msg)
    np.testing.assert_array_equal(np.asarray(q.values), np.asarray(ref.values))
    assert q.value_bits == 8.0


def test_random_mode_is_seeded_projection(msg):
    rows, cols = tp._factor_dims(N)
    stage = tp.LowRankCompress(rank=5, seed=7)
    out = stage(msg)
    assert float(out.nnz) == rows * 5
    assert out.value_bits == 32.0
    q = stage._projection(cols)
    m = jnp.pad(msg.values, (0, rows * cols - N)).reshape(rows, cols)
    ref = ((m @ q) @ q.T).reshape(-1)[:N]
    np.testing.assert_array_equal(np.asarray(out.values), np.asarray(ref))
    # same seed -> same projection -> same message; different seed differs
    np.testing.assert_array_equal(
        np.asarray(tp.LowRankCompress(rank=5, seed=7)(msg).values),
        np.asarray(out.values))
    assert not np.array_equal(
        np.asarray(tp.LowRankCompress(rank=5, seed=8)(msg).values),
        np.asarray(out.values))


def test_random_mode_fold_rotates_projection(msg):
    """`fold` (the round index inside the round loop) refreshes the
    projection, so the dropped subspace rotates across rounds instead of
    pinning the run to one fixed rank-r subspace; equal folds agree (the
    receiver regenerates the same Q)."""
    r0 = tp.LowRankCompress(rank=5, seed=7, fold=jnp.asarray(0))(msg)
    r0b = tp.LowRankCompress(rank=5, seed=7, fold=jnp.asarray(0))(msg)
    r1 = tp.LowRankCompress(rank=5, seed=7, fold=jnp.asarray(1))(msg)
    np.testing.assert_array_equal(np.asarray(r0.values),
                                  np.asarray(r0b.values))
    assert not np.array_equal(np.asarray(r0.values), np.asarray(r1.values))
    # byte accounting is fold-independent
    assert float(r0.nnz) == float(r1.nnz)


def test_learned_mode_exact_on_low_rank_input():
    n = 1024                                # exactly 32 x 32: no padding,
    rows, cols = tp._factor_dims(n)         # so the embedding stays rank-1
    u = jax.random.normal(jax.random.key(1), (rows,))
    v = jax.random.normal(jax.random.key(2), (cols,))
    x = jnp.outer(u, v).reshape(-1)
    out = tp.LowRankCompress(rank=1, mode="learned")(tp.Message.dense(x))
    assert float(out.nnz) == rows + cols
    np.testing.assert_allclose(np.asarray(out.values), np.asarray(x),
                               rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# composition order vs quantize / topk
# ---------------------------------------------------------------------------

def test_topk_then_lowrank_composition(msg):
    rows, _ = tp._factor_dims(N)
    pipe = tp.Pipeline((tp.TopKSparsify(density=0.1),
                        tp.LowRankCompress(rank=3)))
    out = pipe(msg.values)
    # the factor stage owns the transmitted size, not the Top-K support
    assert float(out.nnz) == rows * 3
    bits, dense = pipe.wire(N)
    assert (bits, dense) == (32.0, True)


def test_lowrank_owns_factor_quantization(msg):
    # factor bits narrow the wire width; a Quantize placed *before* the
    # factor stage transforms values but leaves the wire at f32 factors
    own = tp.Pipeline((tp.LowRankCompress(rank=3, bits=8),))
    assert own.wire(N) == (8.0, True)
    assert own(msg.values).value_bits == 8.0
    pre = tp.Pipeline((tp.Quantize(8), tp.LowRankCompress(rank=3)))
    assert pre.wire(N) == (32.0, True)
    assert pre(msg.values).value_bits == 32.0
    # and the two orders genuinely differ in values
    assert not np.array_equal(np.asarray(own(msg.values).values),
                              np.asarray(pre(msg.values).values))


def test_stage_registry():
    assert set(tp.registered_stages()) >= {"mask", "topk", "quantize",
                                           "lowrank"}
    assert tp.resolve_stage("lowrank") is tp.LowRankCompress
    with pytest.raises(KeyError, match="no transport stage"):
        tp.resolve_stage("nope")


def test_wire_format_dispatch():
    plain = st.StrategySpec(kind="flasc")
    assert tp.wire_format(plain, N, "up") == (4.0, False)
    quant = st.StrategySpec(kind="flasc", quant_bits_up=8)
    assert tp.wire_format(quant, N, "up") == (1.0, False)
    lowrank = st.StrategySpec(kind="flasc", lowrank_up=3)
    assert tp.wire_format(lowrank, N, "up") == (4.0, True)
    assert tp.wire_format(lowrank, N, "down") == (4.0, False)
    both = st.StrategySpec(kind="flasc", lowrank_up=3, quant_bits_up=8)
    assert tp.wire_format(both, N, "up") == (1.0, True)
    # inactive rank (>= min factor dim) falls back to the sparse format
    fat = st.StrategySpec(kind="flasc", lowrank_up=32)
    assert tp.wire_format(fat, N, "up") == (4.0, False)
    # the two directions draw distinct projection seeds
    spec = st.StrategySpec(kind="flocora")
    down = tp.lowrank_stage(st.resolve(spec).spec, "down")
    up = tp.lowrank_stage(st.resolve(spec).spec, "up")
    assert down.seed != up.seed


# ---------------------------------------------------------------------------
# ledger accounting: dense-coded factors vs closed-form byte counts
# ---------------------------------------------------------------------------

def test_coded_message_bytes_dense():
    # sparse: min(index, bitmap); dense factors: exactly values * bytes
    assert comm.coded_message_bytes(100, 10_000, 1) == \
        min(100 * 8, 100 * 4 + 10_000 // 8)
    assert comm.coded_message_bytes(100, 10_000, 1, dense=True) == 400
    assert comm.coded_message_bytes(100, 10_000, 1, 1.0, dense=True) == 100


def test_ledger_dense_direction_formulas():
    led = comm.CommLedger(total_params=N, up_dense=True)
    led.record_round(n_clients=4, down_nnz=250, up_nnz_total=4 * 96)
    # dense up: 4 messages x 96 factor entries x 4B, no index/bitmap
    assert led.up_coded_bytes == 4 * 96 * 4
    # sparse down unchanged: per-message min(index, bitmap)
    assert led.down_coded_bytes == \
        4 * comm.coded_message_bytes(250, N, 1)
    assert led.up_values == 4 * 96 and led.up_bytes == 4 * 96 * 4


def _tiny_problem():
    tree0 = {"lora": {"l": {
        "a": 0.1 * jax.random.normal(jax.random.key(1), (16, 3)),
        "b": 0.05 * jax.random.normal(jax.random.key(2), (3, 4))}}}
    meta = fedround.FlatMeta.of(tree0)
    fed = FederatedConfig(n_clients=4, local_batch=2, local_steps=2,
                          client_lr=0.1, client_momentum=0.0, server_lr=0.1)

    def loss_of(bb, tree, mb):
        flat = jnp.concatenate([tree["lora"]["l"]["a"].reshape(-1),
                                tree["lora"]["l"]["b"].reshape(-1)])
        return jnp.sum((flat - jnp.mean(mb["t"])) ** 2)

    batches = {"t": jax.random.normal(jax.random.key(0), (4, 2, 2, 3))}
    return meta, fed, loss_of, batches, meta.flatten(tree0)


@pytest.mark.parametrize("mode", ["random", "learned"])
def test_round_ledger_matches_closed_form(mode):
    """Three compressed rounds through the real round function: ledger
    totals equal the rows*rank / rank*(rows+cols) formulas exactly."""
    meta, fed, loss_of, batches, flatP = _tiny_problem()
    n, r = meta.p_len, 2
    rows, cols = tp._factor_dims(n)
    spec = st.StrategySpec(kind="flasc", density_down=0.5, density_up=0.5,
                           lowrank_down=r, lowrank_up=r, lowrank_mode=mode)
    strat = st.resolve(spec)
    fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, strat))
    server, sstate = fedround.init_server(flatP), strat.init_state(n)
    vb, dense = tp.wire_format(spec, n, "up")
    led = comm.CommLedger(total_params=n, down_value_bytes=vb,
                          up_value_bytes=vb, down_dense=dense, up_dense=dense)
    rounds = 3
    for i in range(rounds):
        flatP, server, sstate, m = fn({}, flatP, server, sstate, batches,
                                      None)
        led.record_round(fed.n_clients, float(m["down_nnz"]),
                         float(m["up_nnz"]),
                         down_per_message=[float(v) for v in
                                           m["down_nnz_clients"]],
                         up_per_message=[float(v) for v in
                                         m["up_nnz_clients"]])
    per_msg = rows * r if mode == "random" else r * (rows + cols)
    expect = rounds * fed.n_clients * per_msg * 4     # f32 factors, 4B each
    assert dense
    assert led.up_coded_bytes == expect
    assert led.down_coded_bytes == expect
    assert led.up_values == rounds * fed.n_clients * per_msg
    assert led.down_values == rounds * fed.n_clients * per_msg


def test_ledger_roundtrips_dense_flags():
    led = comm.CommLedger(total_params=N, up_dense=True, down_dense=False)
    fields = {f.name: getattr(led, f.name)
              for f in dataclasses.fields(led)}      # checkpoint meta form
    back = comm.CommLedger(**fields)
    assert back.up_dense and not back.down_dense

"""Anchors for the million-client population layer (docs/scale.md).

The scaling claims are bitwise, not approximate:

  * the chunked host `PopulationStore` == the dense device-resident
    reference backend, through the full engine loop;
  * prefetch on == prefetch off — the double buffer changes when rows
    move, never which values;
  * hierarchical two-level aggregation (`edge_shards`) == flat
    scatter-add, at the kernel level and through the engine;
  * samplers are pure functions of (config, seed, round): config
    round-trips replay the identical cohort sequence, and
    `fraction` at participation=1.0 is bit-identical to `uniform`;
  * checkpoint/resume mid-flight reproduces the uninterrupted run's
    remaining history bit-for-bit, store contents included.

Plus a 10^4-client smoke (the `scripts/ci_fast.sh` population gate) and
the store/sampler unit layer.
"""
import json

import numpy as np
import pytest

from _fedexp import MODEL, PRETRAIN_STEPS, backbone
from repro.data import datasets as ds
from repro.federated import engine as eng
from repro.federated import population as popn
from repro.federated.api import Experiment
from repro.kernels import fused_transport as ft


@pytest.fixture(scope="module")
def task():
    return ds.make_synth_image(n_examples=128, n_clients=8, n_patches=4,
                               dim=16, seed=0, n_eval=128)


def _experiment(task, rounds=4, **spec_kw):
    defaults = dict(density_down=0.5, density_up=0.5)
    defaults.update(spec_kw)
    return (Experiment(task)
            .with_strategy("flasc", **defaults)
            .with_federation(n_clients=4, local_batch=4, local_steps=2)
            .with_model(**MODEL)
            .with_lora(rank=4)
            .with_training(rounds=rounds, pretrain_steps=PRETRAIN_STEPS,
                           eval_every=2, seed=0)
            .with_params(*backbone(task)))


def _losses(res):
    return [h["loss"] for h in res.history]


def _cohorts(res):
    return [h["cohort"] for h in res.history]


# ---------------------------------------------------------------------------
# the store
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_store_gather_scatter_roundtrip():
    store = popn.PopulationStore(population=1000, row_len=7, chunk=64)
    assert store.n_chunks == 0
    ids = np.asarray([3, 63, 64, 512, 999])
    # unwritten clients read back as zero rows without materializing
    np.testing.assert_array_equal(store.gather(ids),
                                  np.zeros((5, 7), np.float32))
    assert store.n_chunks == 0
    rows = np.arange(35, dtype=np.float32).reshape(5, 7)
    store.scatter(ids, rows)
    np.testing.assert_array_equal(store.gather(ids), rows)
    # only the chunks holding written ids materialized: 0, 1, 8, 15
    assert store.n_chunks == 4
    # neighbours in a touched chunk are still zeros
    np.testing.assert_array_equal(store.gather(np.asarray([4, 65])),
                                  np.zeros((2, 7), np.float32))


@pytest.mark.fast
def test_store_matches_device_reference_backend():
    rng = np.random.default_rng(0)
    host = popn.PopulationStore(population=300, row_len=5, chunk=32)
    dev = popn.DevicePopulationStore(population=300, row_len=5)
    for r in range(5):
        ids = np.unique(rng.integers(0, 300, size=16))
        rows = rng.normal(size=(ids.size, 5)).astype(np.float32)
        host.scatter(ids, rows)
        dev.scatter(ids, rows)
        probe = np.unique(rng.integers(0, 300, size=24))
        np.testing.assert_array_equal(host.gather(probe), dev.gather(probe))


@pytest.mark.fast
def test_store_checkpoint_arrays_roundtrip():
    store = popn.PopulationStore(population=100, row_len=3, chunk=16)
    ids = np.asarray([0, 17, 99])
    rows = np.asarray([[1, 2, 3], [4, 5, 6], [7, 8, 9]], np.float32)
    store.scatter(ids, rows)
    arrays = store.to_arrays()
    # each materialized chunk stays its own array — never one big payload
    assert sorted(arrays["chunks"]) == ["0", "1", "6"]
    clone = popn.PopulationStore(population=100, row_len=3, chunk=16)
    clone.load_arrays(arrays)
    np.testing.assert_array_equal(clone.gather(ids), rows)
    assert clone.n_chunks == 3


@pytest.mark.fast
def test_store_rejects_out_of_range_and_bad_shape():
    store = popn.PopulationStore(population=10, row_len=2)
    with pytest.raises(AssertionError):
        store.gather(np.asarray([10]))
    with pytest.raises(AssertionError):
        store.scatter(np.asarray([0]), np.zeros((1, 3), np.float32))


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_sampler_registry_and_resolve():
    assert set(popn.registered_samplers()) >= {"uniform", "fraction",
                                              "availability"}
    s = popn.resolve_sampler("uniform", population=50, cohort=8, seed=1)
    assert isinstance(s, popn.UniformSampler)
    with pytest.raises(KeyError, match="no sampler registered"):
        popn.resolve_sampler("nope", population=50)
    with pytest.raises(TypeError):
        popn.resolve_sampler(3.14, population=50)


@pytest.mark.fast
def test_sampler_determinism_and_shape():
    s = popn.resolve_sampler("uniform", population=200, cohort=16, seed=5)
    a, b = s.sample(3), s.sample(3)
    np.testing.assert_array_equal(a, b)          # pure in (config, round)
    assert a.shape == (16,) and a.dtype == np.int64
    assert np.all(np.diff(a) > 0)                # ascending, no repeats
    assert not np.array_equal(s.sample(3), s.sample(4))
    # a fresh instance with the same config replays the same sequence
    s2 = popn.resolve_sampler(s.config(), population=200)
    np.testing.assert_array_equal(s.sample(7), s2.sample(7))


@pytest.mark.fast
def test_fraction_at_full_participation_is_uniform_bitwise():
    uni = popn.resolve_sampler("uniform", population=300, cohort=20, seed=2)
    frac = popn.resolve_sampler("fraction", population=300, cohort=20,
                                seed=2, participation=1.0)
    for r in range(6):
        np.testing.assert_array_equal(uni.sample(r), frac.sample(r))


@pytest.mark.fast
def test_fraction_gates_membership():
    frac = popn.resolve_sampler("fraction", population=400, cohort=10,
                                seed=2, participation=0.25)
    for r in range(4):
        elig = frac.eligible(r)
        assert 0 < elig.sum() < 400
        assert elig[frac.sample(r)].all()        # cohort ⊆ eligible
    # too few eligible clients is an error, not a silent short cohort
    tiny = popn.resolve_sampler("fraction", population=20, cohort=19,
                                seed=0, participation=0.05)
    with pytest.raises(RuntimeError, match="eligible"):
        tiny.sample(0)


@pytest.mark.fast
def test_availability_trace_windows():
    from repro.federated import async_clock as ac
    s = popn.resolve_sampler("availability", population=48, cohort=4,
                             seed=0, period=8, duty=0.5)
    # uniform profile: every client on for duty*period=4 rounds of 8,
    # phase-shifted by c % 8; client 0 is on in rounds 0..3 mod 8
    elig0 = [bool(s.eligible(r)[0]) for r in range(8)]
    assert elig0 == [True] * 4 + [False] * 4
    assert s.eligible(0).sum() == 48 // 2
    # heterogeneous profile: slower clients get wider windows
    prof = ac.ClientSystemProfile(speed_factors=(0.5, 2.0))
    h = popn.resolve_sampler("availability", population=8, cohort=2,
                             seed=0, period=8, duty=0.25, profile=prof)
    assert h._window[0] == 4 and h._window[1] == 1
    # config round-trip (profile included) replays identically
    h2 = popn.resolve_sampler(h.config(), population=8)
    for r in range(8):
        np.testing.assert_array_equal(h.eligible(r), h2.eligible(r))


@pytest.mark.fast
def test_availability_trace_file_loader(tmp_path):
    """Recorded on/off traces: every accepted file format reads back the
    same (N, T) matrix, client c follows row c % N, round r reads column
    r % T, and the config spec carries the *path* and replays."""
    windows = np.array([[1, 1, 0, 0],
                        [0, 1, 1, 0],
                        [0, 0, 1, 1]], np.int64)
    npz = tmp_path / "trace.npz"
    np.savez(npz, windows=windows)
    npy = tmp_path / "trace.npy"
    np.save(npy, windows.astype(bool))
    js = tmp_path / "trace.json"
    js.write_text(json.dumps({"windows": windows.tolist()}))
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(windows.tolist()))
    first = tmp_path / "first.npz"      # no "windows" key -> first array
    np.savez(first, w=windows)

    for p in (npz, npy, js, bare, first):
        s = popn.resolve_sampler("availability", population=7, cohort=2,
                                 seed=0, trace=str(p))
        for r in range(9):
            np.testing.assert_array_equal(
                s.eligible(r), windows[np.arange(7) % 3, r % 4].astype(bool),
                err_msg=f"{p} round {r}")

    # determinism + config round-trip: same path -> same cohort sequence
    s = popn.resolve_sampler("availability", population=12, cohort=3,
                             seed=4, trace=str(npz))
    s2 = popn.resolve_sampler(s.config(), population=12)
    assert s2.trace == str(npz)
    for r in range(8):
        np.testing.assert_array_equal(s.eligible(r), s2.eligible(r))
        np.testing.assert_array_equal(s.sample(r), s2.sample(r))

    # a 1-D payload is rejected, not silently broadcast
    bad = tmp_path / "bad.json"
    bad.write_text("[1, 0, 1]")
    with pytest.raises(AssertionError, match="matrix"):
        popn.load_availability_trace(str(bad))


# ---------------------------------------------------------------------------
# the engine anchors
# ---------------------------------------------------------------------------

@pytest.mark.fast
def test_prefetch_on_equals_prefetch_off_bit_for_bit(task):
    on = _experiment(task).with_population(64).run()
    off = _experiment(task).with_population(64, prefetch=False).run()
    assert on.history == off.history        # losses, cohorts, ledger keys
    assert on.final_acc == off.final_acc


@pytest.mark.fast
def test_host_store_equals_device_resident_store(task):
    host = _experiment(task).with_population(64, chunk=16).run()
    dev = _experiment(task).with_population(64, chunk=0).run()
    assert host.history == dev.history
    assert host.final_acc == dev.final_acc


@pytest.mark.fast
def test_population_run_is_deterministic_and_momentum_persists(task):
    a = _experiment(task).with_population(64, sampler="availability",
                                          period=4, duty=0.75).run()
    b = _experiment(task).with_population(64, sampler="availability",
                                          period=4, duty=0.75).run()
    assert a.history == b.history
    assert all(len(h["cohort"]) == 4 for h in a.history)
    # the availability trace actually rotates cohorts across rounds
    assert len({tuple(h["cohort"]) for h in a.history}) > 1


def test_population_checkpoint_resumes_mid_flight_bit_exactly(
        task, tmp_path):
    kw = dict(sampler="fraction", participation=0.6)
    full = _experiment(task, rounds=6).with_population(64, **kw).run()

    class Stop(eng.Callback):
        def on_round_end(self, ev):
            if ev.round == 3:
                raise eng.StopRun()

    d = str(tmp_path / "ckpt")
    part = (_experiment(task, rounds=6).with_population(64, **kw)
            .with_checkpoint(d, every=3).with_callbacks(Stop()).run())
    assert len(part.history) == 4       # stopped after round 3
    resumed = Experiment.resume(d).run()
    assert len(resumed.history) == len(full.history)
    for got, want in zip(resumed.history, full.history):
        assert got["loss"] == want["loss"], want["round"]
        assert got["cohort"] == want["cohort"], want["round"]
    assert resumed.final_acc == full.final_acc


def test_population_trace_sampler_checkpoint_roundtrip(task, tmp_path):
    """A file-backed availability trace rides the sampler config through
    checkpoint/resume: the spec serializes the *path*, resume re-reads
    the file, and the remaining cohort sequence replays bit-exactly."""
    rng = np.random.default_rng(7)
    windows = rng.random((16, 6)) < 0.6
    windows[::4] = True     # every 4th trace row always on: >= cohort elig
    tr = tmp_path / "tr.npz"
    np.savez(tr, windows=windows)
    kw = dict(sampler="availability", trace=str(tr))
    full = _experiment(task, rounds=6).with_population(64, **kw).run()
    assert len({tuple(h["cohort"]) for h in full.history}) > 1

    class Stop(eng.Callback):
        def on_round_end(self, ev):
            if ev.round == 3:
                raise eng.StopRun()

    d = str(tmp_path / "ckpt")
    (_experiment(task, rounds=6).with_population(64, **kw)
     .with_checkpoint(d, every=3).with_callbacks(Stop()).run())
    resumed = Experiment.resume(d).run()
    assert resumed.history == full.history
    assert resumed.final_acc == full.final_acc


@pytest.mark.fast
def test_population_smoke_1e4_clients(task):
    """The ci_fast population gate: a 10^4-client population runs through
    the full prefetched loop, touches only the sampled chunks, and keeps
    the store O(touched), not O(population)."""
    exp = _experiment(task, rounds=2).with_population(10_000, chunk=256)
    res = exp.run()
    assert len(res.history) == 2
    assert all(np.isfinite(h["loss"]) for h in res.history)
    store = exp._population_bundle.store
    assert store.population == 10_000
    # 2 rounds x 4 clients touch at most 8 chunks of the 40 available
    assert 0 < store.n_chunks <= 8


@pytest.mark.fast
def test_async_engine_rejects_population_bundle(task):
    exp = _experiment(task).with_population(64).with_engine("async")
    with pytest.raises(NotImplementedError, match="population store"):
        exp.run()


@pytest.mark.fast
@pytest.mark.parametrize("engine", ["sim", "sharded"])
def test_zero_client_mu_round_equals_plain_round_bit_for_bit(engine):
    """One round factory serves plain and population rounds: a cohort
    whose persistent momentum rows are all zeros computes the plain
    round's weights, server state, strategy state and per-client losses
    bit for bit, on SimEngine and on a (1, 1) ShardedEngine."""
    import jax
    import jax.numpy as jnp
    from repro.core import fedround
    from repro.core import strategies as st
    from repro.models.config import FederatedConfig
    n = 4
    tree = {"a": 0.1 * jax.random.normal(jax.random.key(1), (8, 4)),
            "b": 0.05 * jax.random.normal(jax.random.key(2), (4, 8))}
    meta = fedround.FlatMeta.of(tree)
    params = {"w": jax.random.normal(jax.random.key(3), (8, 8))}

    def loss_of(bb, t, mb):
        return jnp.mean((mb["x"] @ (bb["w"] + t["a"] @ t["b"])) ** 2)

    fed = FederatedConfig(n_clients=n, local_batch=2, local_steps=2,
                          client_lr=0.1)
    strat = st.resolve(st.StrategySpec(kind="flasc_ef", density_down=0.5,
                                       density_up=0.5, quant_bits_up=4))
    plan = eng.RoundTask(loss_of, meta, fed, strat, params=params)
    e = eng.resolve_engine(engine)
    batch = {"x": jax.random.normal(jax.random.key(4), (n, 2, 2, 8))}

    def run(*client_mu):
        # fresh state every call: the sharded step donates it
        flatP = meta.flatten(tree)
        out = e.compile(plan)(
            *e._step_params(plan), flatP, fedround.init_server(flatP),
            strat.init_state(meta.p_len), batch, jax.random.key(5),
            *client_mu)
        return jax.tree.map(np.asarray, out)

    plain = run()
    zero = run(jnp.zeros((n, meta.p_len), jnp.float32))
    assert "client_mu" not in plain[3] and zero[3]["client_mu"].shape == (
        n, meta.p_len)
    for a, b in zip(jax.tree.leaves(plain[:3]), jax.tree.leaves(zero[:3])):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(plain[3]["loss_clients"],
                                  zero[3]["loss_clients"])
    # the finals are real momentum, not the zeros that went in
    assert np.any(zero[3]["client_mu"] != 0)


# ---------------------------------------------------------------------------
# hierarchical two-level aggregation
# ---------------------------------------------------------------------------

@pytest.mark.fast
@pytest.mark.parametrize("edges", [1, 2, 3, 4, 8])
def test_hierarchical_accumulate_equals_flat_bitwise(edges):
    import jax.numpy as jnp
    rng = np.random.default_rng(edges)
    n, k, cap = 1000, 6, 64
    idx = np.sort(rng.integers(0, n + 1, size=(k, cap)).astype(np.int32))
    val = rng.normal(size=(k, cap)).astype(np.float32)
    val[idx == n] = 0.0                         # sentinel slots are empty
    flat = ft.sparse_accumulate(jnp.asarray(idx), jnp.asarray(val), n)
    hier = ft.hierarchical_accumulate(jnp.asarray(idx), jnp.asarray(val),
                                      n, edges)
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))


@pytest.mark.fast
def test_edge_shards_equal_flat_through_engine(task):
    flat = _experiment(task, sparse_aggregate=True).run()
    for edges in (2, 4):
        hier = _experiment(task, sparse_aggregate=True,
                           edge_shards=edges).run()
        assert _losses(hier) == _losses(flat), edges
    # and on the population path
    pflat = (_experiment(task, sparse_aggregate=True)
             .with_population(64).run())
    phier = (_experiment(task, sparse_aggregate=True, edge_shards=4)
             .with_population(64).run())
    assert _losses(phier) == _losses(pflat)


@pytest.mark.fast
def test_edge_shards_spec_validation():
    from repro.core import strategies as st
    with pytest.raises(ValueError, match="edge_shards"):
        st.StrategySpec(kind="flasc", edge_shards=-1)
    with pytest.raises(ValueError, match="phase_len"):
        st.StrategySpec(kind="two_stage_ortho", phase_len=0)

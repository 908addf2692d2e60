"""Data pipeline properties (hypothesis), optimizers, checkpoint roundtrip."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, hst

from repro.checkpoint.io import load_pytree, save_pytree
from repro.data import datasets as ds
from repro.data.partition import dirichlet_partition, label_heterogeneity
from repro.data.pipeline import sample_round
from repro.models.config import FederatedConfig
from repro.optim import (adam_init, adam_update, clip_by_global_norm,
                         cosine_schedule, global_norm, sgd_init, sgd_update)


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------

@settings(deadline=None, max_examples=20)
@given(hst.integers(50, 400), hst.integers(2, 16),
       hst.sampled_from([0.05, 0.5, 100.0]), hst.integers(0, 10 ** 6))
def test_dirichlet_partition_is_a_partition(n, clients, alpha, seed):
    labels = np.random.default_rng(seed).integers(0, 5, n)
    parts = dirichlet_partition(labels, clients, alpha, seed=seed)
    all_idx = np.concatenate(parts)
    assert len(all_idx) == n
    assert len(np.unique(all_idx)) == n          # disjoint cover
    assert all(len(p) >= 1 for p in parts)


def test_dirichlet_partition_tops_up_when_redraws_cannot_help():
    """54 examples over 16 clients: the floor cuts of near-uniform mixtures
    leave the same clients short in every class however smooth alpha gets,
    so redrawing alone never ends.  The last draw is topped up instead."""
    labels = np.random.default_rng(139017).integers(0, 5, 54)
    parts = dirichlet_partition(labels, 16, 100.0, seed=139017)
    all_idx = np.concatenate(parts)
    assert sorted(all_idx.tolist()) == list(range(54))
    assert min(len(p) for p in parts) == 2
    with pytest.raises(ValueError, match="cannot give"):
        dirichlet_partition(labels, 28, 100.0)


def test_dirichlet_alpha_controls_skew():
    labels = np.random.default_rng(0).integers(0, 10, 4000)
    skew_lo = label_heterogeneity(dirichlet_partition(labels, 32, 100.0, 1), labels)
    skew_hi = label_heterogeneity(dirichlet_partition(labels, 32, 0.05, 1), labels)
    assert skew_hi > skew_lo + 0.2


def test_sample_round_shapes_and_determinism():
    task = ds.make_synth_text(n_examples=256, n_clients=16, vocab=64, length=12)
    fed = FederatedConfig(n_clients=4, local_batch=4, local_steps=2)
    b1 = sample_round(task, fed, round_idx=3, seed=9)
    b2 = sample_round(task, fed, round_idx=3, seed=9)
    assert b1["tokens"].shape == (4, 2, 4, 12)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = sample_round(task, fed, round_idx=4, seed=9)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_tasks_are_learnable_by_construction():
    """Class signal must be linearly visible in the synthetic embeddings."""
    task = ds.make_synth_image(n_examples=512, n_clients=8, n_patches=4, dim=32)
    X = task.data["embeds"].reshape(512, -1)
    y = task.data["labels"]
    mu = np.stack([X[y == c].mean(0) for c in range(10)])
    pred = np.argmax(X @ mu.T, -1)   # nearest-prototype readout
    assert (pred == y).mean() > 0.5


# ---------------------------------------------------------------------------
# optimizers
# ---------------------------------------------------------------------------

def test_sgd_momentum_matches_closed_form():
    p = {"w": jnp.asarray([1.0, 2.0])}
    g = {"w": jnp.asarray([0.5, -0.5])}
    st = sgd_init(p)
    p1, st = sgd_update(p, g, st, lr=0.1, momentum=0.9)
    np.testing.assert_allclose(np.asarray(p1["w"]), [0.95, 2.05])
    p2, st = sgd_update(p1, g, st, lr=0.1, momentum=0.9)
    # mu = 0.9*g + g = 1.9g
    np.testing.assert_allclose(np.asarray(p2["w"]), [0.95 - 0.095, 2.05 + 0.095],
                               rtol=1e-6)


def test_adam_first_step_is_lr_sized():
    p = {"w": jnp.asarray([0.0, 0.0])}
    g = {"w": jnp.asarray([10.0, -0.001])}
    st = adam_init(p)
    p1, _ = adam_update(p, g, st, lr=0.01)
    np.testing.assert_allclose(np.abs(np.asarray(p1["w"])), [0.01, 0.01], rtol=1e-3)


@settings(deadline=None, max_examples=20)
@given(hst.floats(0.01, 10.0), hst.integers(0, 2 ** 31 - 1))
def test_clip_by_global_norm(max_norm, seed):
    tree = {"a": jax.random.normal(jax.random.key(seed), (17,)) * 5}
    clipped, pre = clip_by_global_norm(tree, max_norm)
    assert float(global_norm(clipped)) <= max_norm * (1 + 1e-5)


def test_cosine_schedule_endpoints():
    s = cosine_schedule(1.0, 100, final_frac=0.1)
    assert float(s(0)) == pytest.approx(1.0)
    assert float(s(100)) == pytest.approx(0.1, abs=1e-6)


# ---------------------------------------------------------------------------
# checkpointing
# ---------------------------------------------------------------------------

def test_checkpoint_roundtrip():
    tree = {"a": {"b": jnp.arange(6.0).reshape(2, 3)},
            "c": jnp.asarray([1, 2, 3], jnp.int32)}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_pytree(tree, path)
        back = load_pytree(path, like=tree)
        for x, y in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_checkpoint_shape_mismatch_raises():
    tree = {"w": jnp.zeros((2, 2))}
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "ckpt.npz")
        save_pytree(tree, path)
        with pytest.raises(ValueError):
            load_pytree(path, like={"w": jnp.zeros((3, 3))})
        with pytest.raises(KeyError):
            load_pytree(path, like={"v": jnp.zeros((2, 2))})

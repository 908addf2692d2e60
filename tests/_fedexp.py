"""The tiny federated experiment the engine suites share (test_engine.py,
test_population.py, test_async_*.py): the synthetic task, the experiment
builder, the final-state capture, the crash-after-checkpoint callback, the
ledger fields the bit-equality anchors compare, and the async engine's
helpers."""
import jax
import numpy as np
import pytest

from repro.core import strategies as st
from repro.data import datasets as ds
from repro.federated import async_clock as ac
from repro.federated import engine as eng
from repro.federated.api import Experiment

N_CLIENTS = 4
ROUNDS = 4
EVAL_EVERY = 2
MODEL = dict(d_model=16, num_layers=1, num_heads=2, d_ff=32)
PRETRAIN_STEPS = 2

# keys only the async engine writes into history records
ASYNC_KEYS = {"sim_time", "staleness", "applied", "dropped"}

# strategy kwargs of each kind the engine anchors run: the 8 paper kinds,
# then the PR 5 baselines (low-rank message compression, the two-stage
# sparsified-orthogonal schedule)
KIND_KWARGS = {
    "lora": {},
    "flasc": {},
    "flasc_ef": {},
    "sparse_adapter": {},
    "fedselect": {},
    "adapter_lth": dict(lth_prune_every=2, lth_keep=0.9),
    "ffa": {},
    "hetlora": dict(hetlora_ranks=(1, 2, 3, 4)),
    "flocora": dict(lowrank_down=4, lowrank_up=4),
    "two_stage_ortho": {},
}

LEDGER_ATTRS = ("down_values", "up_values", "down_bytes", "up_bytes",
                "total_bytes", "down_coded_bytes", "up_coded_bytes",
                "total_coded_bytes", "rounds")


@pytest.fixture(scope="module")
def task():
    return ds.make_synth_image(n_examples=128, n_clients=8, n_patches=4,
                               dim=16, seed=0, n_eval=128)


@pytest.fixture(scope="module")
def sim_run(task):
    """SimEngine run of a kind at its `KIND_KWARGS`, with its final state —
    run once per module, however many anchors compare against it."""
    runs = {}

    def get(kind):
        if kind not in runs:
            cap = CaptureState()
            res = (experiment(task, kind, **KIND_KWARGS[kind])
                   .with_callbacks(cap).run())
            runs[kind] = res, cap
        return runs[kind]
    return get


_BACKBONES = {}     # id(task) -> (task, (params, cfg))


def backbone(task):
    """The pretrained (params, cfg) every experiment on `task` starts from.
    Pretraining depends only on the task and the model, and each build
    traces and compiles its step anew, so it runs once per task."""
    if id(task) not in _BACKBONES:
        built = (Experiment(task).with_model(**MODEL)
                 .with_training(pretrain_steps=PRETRAIN_STEPS)
                 .build_backbone())
        _BACKBONES[id(task)] = task, built
    return _BACKBONES[id(task)][1]


def experiment(task, kind="flasc", rounds=ROUNDS, **kw):
    defaults = dict(density_down=0.5, density_up=0.5)
    defaults.update(kw)
    spec = st.StrategySpec(kind=kind, **defaults)
    return (Experiment(task, strategy=spec)
            .with_federation(n_clients=N_CLIENTS, local_batch=4)
            .with_model(**MODEL)
            .with_lora(rank=4)
            .with_training(rounds=rounds, eval_every=EVAL_EVERY,
                           pretrain_steps=PRETRAIN_STEPS)
            .with_params(*backbone(task)))


class CaptureState(eng.Callback):
    """Grabs the post-round state so tests can compare final weights."""

    def on_round_end(self, ev):
        self.flatP = np.asarray(ev.state.flatP)
        self.sstate_leaves = [np.asarray(x)
                              for x in jax.tree.leaves(ev.state.sstate)]


class StopAfterCheckpoint(eng.Callback):
    """Simulates a crash right after a snapshot lands on disk."""

    def on_checkpoint(self, ev):
        raise eng.StopRun


def strip_async(record):
    return {k: v for k, v in record.items() if k not in ASYNC_KEYS}


def tiered_engine(**kw):
    kw.setdefault("buffer_size", 2)
    return eng.AsyncEngine(profile=ac.ClientSystemProfile.tiered(N_CLIENTS, 4),
                           **kw)

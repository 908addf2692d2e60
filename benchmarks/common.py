"""Shared helpers for the paper-figure benchmark harnesses.

Every harness prints CSV rows `figure,setting,metric,value` (plus a
human-readable table) and returns the rows so benchmarks/run.py can
aggregate everything into bench_output.txt.

Engine selection: `BENCH_ENGINE=sharded` (or `run(..., engine=...)`)
routes every harness through that registered engine backend instead of
the default single-device sim path.  `BENCH_ENGINE=sharded:4` adds a
rounds_per_call scan chunk.
"""
from __future__ import annotations

import functools
import os
import sys
import time
from typing import Dict, List, Optional

from repro.core.strategies import StrategyLike
from repro.data import datasets as ds
from repro.federated.api import Experiment
from repro.federated.engine import resolve_engine
from repro.launch import compile_cache
from repro.models.config import FederatedConfig

compile_cache.enable()

QUICK = os.environ.get("BENCH_QUICK", "1") != "0"
ENGINE = os.environ.get("BENCH_ENGINE", "sim")

# tiny model shared across figures (paper: ViT-B/GPT2 — reduced for 1 CPU core)
MODEL_KW = dict(d_model=48, num_layers=2, num_heads=4, d_ff=96)
ROUNDS = 30 if QUICK else 120
EVAL_EVERY = 5 if QUICK else 10


@functools.lru_cache(maxsize=None)
def get_task(name: str, alpha: float = 0.1, seed: int = 0):
    if name == "synth_image":
        return ds.make_synth_image(n_examples=1024, n_clients=48, n_patches=8,
                                   dim=48, alpha=alpha, seed=seed)
    if name == "synth_text":
        return ds.make_synth_text(n_examples=768, n_clients=48, vocab=128,
                                  length=24, alpha=alpha, seed=seed)
    if name == "synth_reddit":
        return ds.make_synth_reddit(n_users=96, vocab=128, length=20, seed=seed)
    if name == "synth_flair":
        return ds.make_synth_flair(n_users=96, n_patches=8, dim=48, seed=seed)
    raise KeyError(name)


def default_fed(**kw) -> FederatedConfig:
    base = dict(n_clients=8, local_batch=8, local_steps=1,
                client_lr=5e-3, client_momentum=0.9, server_lr=5e-3)
    base.update(kw)
    return FederatedConfig(**base)


def _engine_for(engine):
    """'sim' | 'sharded' | 'async' | 'sharded:<rounds_per_call>' | an
    Engine instance -> Engine."""
    if not isinstance(engine, str):
        return resolve_engine(engine)       # instance passes through
    if ":" in engine:
        name, k = engine.split(":", 1)
        try:
            return resolve_engine(name, rounds_per_call=int(k))
        except TypeError:
            raise ValueError(
                f"engine {name!r} does not support a rounds_per_call chunk "
                f"(BENCH_ENGINE={name}:{k}); only 'sharded' scans rounds"
            ) from None
    return resolve_engine(engine)


# pretrained (params, cfg) per backbone identity — figure harnesses sweep
# strategies over the SAME task/model/seed, so pretraining once per
# combination instead of once per run cuts harness wall-clock.  Keyed on the
# task object id; the task itself is stored in the entry, which keeps it
# alive and so guarantees the id is never reused by a different task.
_BACKBONES: Dict[tuple, tuple] = {}


def pretrained_backbone(task, model_kw: dict, pretrain_steps: int, seed: int):
    key = (id(task), tuple(sorted(model_kw.items())), pretrain_steps, seed)
    if key not in _BACKBONES:
        exp = (Experiment(task)
               .with_model(**model_kw)
               .with_training(pretrain_steps=pretrain_steps, seed=seed))
        _BACKBONES[key] = (task, exp.build_backbone())
    return _BACKBONES[key][1]


def run(task, spec: StrategyLike, fed: Optional[FederatedConfig] = None,
        rounds: int = None, lora_rank: int = 16, seed: int = 0,
        model_kw: Optional[dict] = None, pretrain_steps: Optional[int] = None,
        full_finetune: bool = False, engine=None, **train_kw):
    """One experiment run.  `engine` is a registry name ('sim', 'sharded',
    'sharded:<k>', 'async') or an Engine instance (e.g. an AsyncEngine
    with a custom ClientSystemProfile); None defers to $BENCH_ENGINE."""
    t0 = time.time()
    model_kw = model_kw or MODEL_KW
    pretrain_steps = ((40 if QUICK else 150) if pretrain_steps is None
                      else pretrain_steps)
    params, cfg = pretrained_backbone(task, model_kw, pretrain_steps, seed)
    exp = (Experiment(task, strategy=spec, federation=fed or default_fed())
           .with_model(**model_kw)
           .with_lora(rank=lora_rank)
           .with_params(params, cfg)
           .with_engine(_engine_for(engine or ENGINE))
           .with_training(
               rounds=rounds or ROUNDS, eval_every=EVAL_EVERY, seed=seed,
               pretrain_steps=pretrain_steps,
               full_finetune=full_finetune, **train_kw))
    res = exp.run()
    res.elapsed = time.time() - t0
    return res


def emit(rows: List[Dict], header: str):
    print(f"\n== {header} ==", flush=True)
    for r in rows:
        print(",".join(str(r[k]) for k in ("figure", "setting", "metric", "value")),
              flush=True)
    return rows


def row(figure, setting, metric, value):
    return {"figure": figure, "setting": setting, "metric": metric,
            "value": round(value, 6) if isinstance(value, float) else value}

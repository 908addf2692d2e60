"""Typed experiment-builder API for federated finetuning.

Replaces the legacy 14-kwarg `run_experiment` signature with three small
config objects plus the strategy registry:

    from repro.federated.api import Experiment

    result = (Experiment(task)
              .with_strategy("flasc", density_down=0.25, density_up=0.25)
              .with_federation(n_clients=8, local_batch=8, client_lr=5e-3)
              .with_model(d_model=48, num_layers=2, num_heads=4, d_ff=96)
              .with_lora(rank=16)
              .with_training(rounds=30, eval_every=10)
              .run())

`with_strategy` accepts a kind string (+ StrategySpec field overrides), a
`StrategySpec`, or any registered `Strategy` instance — including user
strategies added with `@register_strategy` (see docs/strategies.md).
`.with_strategy(selector="pallas")` swaps every Top-K in the round for the
fused kernel path (docs/kernels.md); the selector name round-trips through
checkpoints like every other spec field.
`runtime.run_experiment` remains as a thin backward-compatible shim over
this builder.

Execution is pluggable (docs/engines.md): `.with_engine("sim")` (default,
the single-device jit+vmap path) or `.with_engine("sharded", ...)` /
`.with_engine(ShardedEngine(mesh, rounds_per_call=4))` for SPMD meshes.
`.with_checkpoint(dir, every)` snapshots the run; `Experiment.resume(dir)`
rebuilds the experiment from the snapshot and reproduces the interrupted
run's remaining history bit-for-bit.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import io as ckpt_io
from repro.core import comm as comm_mod
from repro.core import fedround
from repro.core import strategies as st
from repro.core import transport as tp
from repro.data.datasets import FederatedTask
from repro.data.pipeline import sample_round
from repro.federated import engine as eng
from repro.federated import population as popn
from repro.models import lora as lora_mod
from repro.models import model as mdl
from repro.models.config import FederatedConfig, LoRAConfig
from repro.models.layers import init_params


@dataclasses.dataclass(frozen=True)
class ModelOptions:
    """Backbone shape for the task model (see `runtime.model_for_task`)."""
    d_model: int = 64
    num_layers: int = 2
    num_heads: int = 4
    d_ff: int = 128
    vocab: int = 256

    def kwargs(self) -> Dict[str, int]:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class TrainOptions:
    """Everything about the training loop that is not the model, the
    federation geometry, or the strategy."""
    rounds: int = 30
    pretrain_steps: int = 100
    train_head: bool = True
    eval_every: int = 10
    log_every: int = 0          # verbose progress cadence for eval-less runs
    seed: int = 0
    full_finetune: bool = False
    verbose: bool = False


class Experiment:
    """Builder for one federated finetuning experiment.

    Each `with_*` method replaces one config facet and returns the builder,
    so configurations chain and partial configs read top-to-bottom.  `run()`
    assembles the round function from the strategy registry + transport
    pipeline and drives the experiment loop.
    """

    def __init__(self, task: Optional[FederatedTask], *,
                 strategy: st.StrategyLike = "flasc",
                 federation: Optional[FederatedConfig] = None,
                 model: Optional[ModelOptions] = None,
                 lora: Optional[LoRAConfig] = None,
                 train: Optional[TrainOptions] = None,
                 engine: eng.EngineLike = "sim"):
        self.task = task
        self.strategy = st.resolve(strategy)
        self.federation = federation or FederatedConfig(
            n_clients=8, local_batch=8, local_steps=1)
        self.model = model or ModelOptions()
        self.lora = lora or LoRAConfig()
        self.train = train or TrainOptions()
        self.engine = eng.resolve_engine(engine)
        self._params_and_cfg: Optional[Tuple[Any, Any]] = None
        self._data_provider: Optional[eng.DataProvider] = None
        self._checkpoint: Optional[Tuple[str, int]] = None
        self._callbacks: List[eng.Callback] = []
        self._restore: Optional[Tuple[Any, Dict[str, Any]]] = None
        self._frozen_written = False
        self._population: Optional[Dict[str, Any]] = None
        self._population_bundle: Optional[popn.Population] = None

    # --- builder facets ----------------------------------------------------
    def with_strategy(self, strategy: Optional[st.StrategyLike] = None,
                      **overrides) -> "Experiment":
        """Kind string + StrategySpec field overrides, a StrategySpec, or a
        Strategy instance."""
        if strategy is None:
            spec = dataclasses.replace(self.strategy.spec, **overrides)
        elif isinstance(strategy, str):
            spec = st.StrategySpec(kind=strategy, **overrides)
        else:
            assert not overrides, "pass overrides with a kind string"
            spec = strategy
        self.strategy = st.resolve(spec)
        return self

    def with_federation(self, federation: Optional[FederatedConfig] = None,
                        **overrides) -> "Experiment":
        if federation is None:
            federation = dataclasses.replace(self.federation, **overrides)
        else:
            assert not overrides, "pass overrides without a config object"
        self.federation = federation
        return self

    def with_model(self, model: Optional[ModelOptions] = None,
                   **overrides) -> "Experiment":
        if model is not None:
            assert not overrides, "pass overrides without a config object"
        self.model = model or dataclasses.replace(self.model, **overrides)
        return self

    def with_lora(self, rank: Optional[int] = None,
                  alpha: Optional[float] = None,
                  config: Optional[LoRAConfig] = None) -> "Experiment":
        if config is not None:
            assert rank is None and alpha is None, \
                "pass overrides without a config object"
        if config is None:
            kw = {}
            if rank is not None:
                kw["rank"] = rank
            if alpha is not None:
                kw["alpha"] = alpha
            config = dataclasses.replace(self.lora, **kw)
        self.lora = config
        return self

    def with_training(self, train: Optional[TrainOptions] = None,
                      **overrides) -> "Experiment":
        if train is not None:
            assert not overrides, "pass overrides without a config object"
        self.train = train or dataclasses.replace(self.train, **overrides)
        return self

    def with_params(self, params, cfg) -> "Experiment":
        """Escape hatch: reuse an already-built (params, ModelConfig) pair
        instead of building + pretraining from `ModelOptions`."""
        self._params_and_cfg = (params, cfg)
        return self

    def with_engine(self, engine: eng.EngineLike, **kwargs) -> "Experiment":
        """Execution backend: "sim" (default), "sharded", an Engine class,
        or an instance.  kwargs go to the backend constructor, e.g.
        `.with_engine("sharded", rounds_per_call=4)`."""
        self.engine = eng.resolve_engine(engine, **kwargs)
        return self

    def with_mesh(self, shape: Tuple[int, int] = (1, 1), *,
                  fsdp: bool = False, rounds_per_call: int = 1,
                  donate: bool = True) -> "Experiment":
        """2-D client-axis × model-axis ShardedEngine: `shape=(c, m)`
        builds `launch.mesh.make_train_mesh(c, m)` (Auto axes) — the vmapped
        client dimension shards over "data" while the backbone params
        (which `run()` passes as an explicit step argument) TP-shard over
        "model"; `fsdp=True` overlays ZeRO-3 so weight storage dims shard
        over the client axis too (docs/engines.md "Sharded backbone
        params").  Pass a prebuilt `Mesh` instead of a shape tuple to
        bring your own axes.  c*m must not exceed `len(jax.devices())`."""
        from repro.launch.mesh import make_train_mesh
        mesh = (make_train_mesh(*shape)
                if isinstance(shape, (tuple, list)) else shape)
        self.engine = eng.ShardedEngine(mesh, fsdp=fsdp,
                                        rounds_per_call=rounds_per_call,
                                        donate=donate)
        return self

    def with_data(self, provider: eng.DataProvider) -> "Experiment":
        """Replace the default `sample_round`-based batch provider with
        `provider(round_idx) -> client_batches` (leaves shaped
        (n_clients, local_steps, local_batch, ...)).  Lets task-less
        drivers (launch/train.py) reuse the engine loop."""
        self._data_provider = provider
        return self

    def with_checkpoint(self, directory: str, every: int = 10) -> "Experiment":
        """Snapshot the run into `directory` every `every` rounds;
        `Experiment.resume(directory)` restarts from the latest snapshot."""
        self._checkpoint = (directory, int(every))
        return self

    def with_callbacks(self, *callbacks: eng.Callback) -> "Experiment":
        """Append user callbacks to the engine's hook pipeline (they run
        after the built-in ledger/eval/logging/checkpoint callbacks)."""
        self._callbacks.extend(callbacks)
        return self

    def with_population(self, population: int, *,
                        sampler: popn.SamplerLike = "uniform",
                        chunk: int = 4096, prefetch: bool = True,
                        **sampler_kw) -> "Experiment":
        """Scale the client *population* past the device cohort
        (docs/scale.md): every round samples `n_clients` ids out of
        `population` with the named `CohortSampler` (e.g.
        `sampler="fraction", participation=0.3`), gathers their momentum
        rows from a chunked host-resident `PopulationStore` (`chunk`
        clients per chunk; 0 selects the dense device test backend), and
        commits the finals back after the round.  `prefetch` stages the
        next cohort host-to-device while the current round computes.
        Synchronous engines only (AsyncEngine takes `sampler=` itself)."""
        self._population = {"population": int(population),
                           "sampler": sampler, "chunk": int(chunk),
                           "prefetch": bool(prefetch),
                           "sampler_kw": dict(sampler_kw)}
        return self

    # --- assembly ----------------------------------------------------------
    def build_backbone(self):
        """(params, ModelConfig) for the frozen backbone — pretrained unless
        supplied via `with_params`.  Public so harnesses can cache it."""
        from repro.federated import runtime as rt
        t = self.train
        if self._params_and_cfg is not None:
            params, cfg = self._params_and_cfg
            return params, cfg
        cfg = rt.model_for_task(self.task, **self.model.kwargs())
        params = init_params(mdl.model_spec(cfg), jax.random.key(t.seed))
        if t.pretrain_steps:
            params, _ = rt.pretrain(params, cfg, self.task, t.pretrain_steps,
                                    seed=t.seed)
        return params, cfg

    def _build_trainable(self, params, cfg):
        t = self.train
        if t.full_finetune:
            trainable: Dict[str, Any] = {"lora": {}, "head": {},
                                         "backbone": params}
            return trainable, fedround.FlatMeta.of(trainable), 1.0
        lora0 = lora_mod.init_lora(cfg, self.lora, jax.random.key(t.seed + 1))
        trainable = {"lora": lora0}
        if t.train_head and cfg.num_classes > 0:
            trainable["head"] = {"cls_head": params["cls_head"],
                                 "final_norm": params["final_norm"]}
        return trainable, fedround.FlatMeta.of(trainable), self.lora.scale

    def build_ledger(self, p_len: int) -> comm_mod.CommLedger:
        """Ledger whose per-value wire widths and coding (sparse
        index/bitmap vs dense low-rank factors) come from the spec's
        transport configuration (`transport.wire_format`)."""
        spec = self.strategy.spec
        down_vb, down_dense = tp.wire_format(spec, p_len, "down")
        up_vb, up_dense = tp.wire_format(spec, p_len, "up")
        return comm_mod.CommLedger(total_params=p_len,
                                   down_value_bytes=down_vb,
                                   up_value_bytes=up_vb,
                                   down_dense=down_dense,
                                   up_dense=up_dense)

    # --- the experiment loop ----------------------------------------------
    def _default_data(self) -> eng.DataProvider:
        task, fed, seed = self.task, self.federation, self.train.seed

        def data(r: int):
            batch_np = sample_round(task, fed, r, seed=seed)
            return {k: jnp.asarray(v) for k, v in batch_np.items()}
        return data

    def run(self):
        from repro.federated import runtime as rt
        task, fed, t = self.task, self.federation, self.train
        if task is None:
            assert self._data_provider is not None and \
                self._params_and_cfg is not None, \
                "task-less experiments need with_data(...) and with_params(...)"
        params, cfg = self.build_backbone()
        trainable, meta, scale = self._build_trainable(params, cfg)

        # the backbone enters every engine step as its leading argument
        # (docs/engines.md), so a ShardedEngine can apply TRAIN_RULES/FSDP
        # in_shardings to it
        def loss_of(bb, tree, mb):
            if t.full_finetune:
                return rt.task_loss(tree["backbone"], cfg, mb)
            p = dict(bb)
            if "head" in tree:
                p.update(tree["head"])
            return mdl.loss_fn(p, cfg, rt._task_batch(cfg, mb),
                               lora=tree["lora"], lora_scale=scale)

        pop = None
        if self._population is not None:
            ps = self._population
            pop = popn.Population.build(
                ps["population"], meta.p_len, cohort=fed.n_clients,
                sampler=ps["sampler"], seed=t.seed, chunk=ps["chunk"],
                prefetch=ps["prefetch"], **ps["sampler_kw"])
            self._population_bundle = pop
        plan = eng.RoundTask(loss_of, meta, fed, self.strategy, seed=t.seed,
                             population=pop, params=params,
                             param_spec=mdl.model_spec(cfg))
        if self._restore is not None:
            state, ledger, saved_acc = self._restore_state(plan, meta)
        else:
            state = eng.RunState.fresh(plan, meta.flatten(trainable),
                                       rounds=t.rounds)
            ledger, saved_acc = self.build_ledger(meta.p_len), 0.0

        callbacks: List[eng.Callback] = [eng.LedgerCallback(ledger)]
        eval_cb = None
        if task is not None:
            eval_cb = eng.EvalCallback(
                lambda flatP: rt.evaluate(params, cfg, trainable, meta, task,
                                          scale, flatP),
                every=t.eval_every)
            eval_cb.acc = saved_acc
            callbacks.append(eval_cb)
        callbacks.append(eng.LoggingCallback(t.verbose, every=t.log_every))
        if self._checkpoint is not None:
            assert task is not None, "checkpointing needs a FederatedTask"
            if self._params_and_cfg is not None and self._restore is None \
                    and cfg != rt.model_for_task(task, **self.model.kwargs()):
                raise ValueError(
                    "with_checkpoint cannot snapshot a custom ModelConfig "
                    "supplied via with_params: resume rebuilds the config "
                    "from ModelOptions — configure the model through "
                    "with_model(...) instead")
            directory, every = self._checkpoint
            callbacks.append(eng.CheckpointCallback(
                directory, every,
                lambda d, s: self._save_checkpoint(d, s, params, ledger,
                                                   eval_cb)))
        callbacks.extend(self._callbacks)

        data = self._data_provider or self._default_data()
        state = self.engine.run_rounds(state, data, callbacks)
        acc = eval_cb.acc if eval_cb is not None else 0.0
        return rt.ExperimentResult(state.history, ledger, acc)

    # --- checkpoint / resume ----------------------------------------------
    def _save_checkpoint(self, directory: str, state: eng.RunState,
                         params, ledger, eval_cb) -> str:
        task = self.task
        arrays = {"P": state.flatP, "server": state.server,
                  "strategy": state.sstate}
        if state.aux is not None:   # engine-owned state (async event queue)
            arrays["aux"] = state.aux
        frozen = {        # run-constant payload, written once per directory
            "params": params,
            "task": {"parts": {str(i): p for i, p in enumerate(task.parts)},
                     "data": task.data, "eval_data": task.eval_data},
        }
        directory_, every = self._checkpoint
        meta_json = {
            "version": 1,
            "round": state.round,
            "history": state.history,
            "acc": float(eval_cb.acc) if eval_cb is not None else 0.0,
            "ledger": {f.name: getattr(ledger, f.name)
                       for f in dataclasses.fields(ledger)},
            "strategy": dataclasses.asdict(self.strategy.spec),
            "federation": dataclasses.asdict(self.federation),
            "model": self.model.kwargs(),
            "lora": dataclasses.asdict(self.lora),
            "train": dataclasses.asdict(self.train),
            "task_meta": {"name": task.name, "kind": task.kind,
                          "n_classes": task.n_classes},
            "checkpoint": {"dir": directory_, "every": every},
            "engine": {"name": self.engine.name,
                       "config": self.engine.config(),
                       "rounds_per_call":
                           int(getattr(self.engine, "rounds_per_call", 1))},
        }
        if self._population_bundle is not None:
            # the store payload itself rides state.aux (chunked arrays);
            # the meta keeps only the JSON facets needed to rebuild
            meta_json["population"] = self._population_bundle.config()
        # the first save of a fresh (non-resumed) run replaces any frozen
        # payload a previous run left in the directory
        overwrite = not (self._frozen_written or self._restore is not None)
        self._frozen_written = True
        return ckpt_io.save_experiment_checkpoint(directory, arrays, meta_json,
                                                  frozen=frozen,
                                                  overwrite_frozen=overwrite)

    def _restore_state(self, plan: eng.RoundTask, meta: fedround.FlatMeta):
        arrays, mj = self._restore
        sstate = arrays.get("strategy")
        if sstate is None:                      # stateless strategy: {} saves
            sstate = plan.strategy.init_state(meta.p_len)  # as zero leaves
        state = eng.RunState(plan, jnp.asarray(arrays["P"]), arrays["server"],
                             sstate, round=int(mj["round"]),
                             rounds=self.train.rounds,
                             history=list(mj["history"]),
                             aux=arrays.get("aux"))
        ledger = comm_mod.CommLedger(**mj["ledger"])
        return state, ledger, float(mj.get("acc", 0.0))

    @classmethod
    def resume(cls, directory: str,
               task: Optional[FederatedTask] = None) -> "Experiment":
        """Rebuild an experiment from its latest checkpoint.  `.run()` then
        executes exactly the remaining rounds: restored history + new
        records reproduce the uninterrupted run bit-for-bit.  Extend the
        run by chaining `.with_training(rounds=...)` before `.run()`.

        The saved engine backend (name + `Engine.config()` kwargs) is
        restored so the remaining rounds take the same numerical path; an
        AsyncEngine also restores its event queue (in-flight jobs, server
        buffer, virtual time) from the snapshot's `aux` payload.  A
        ShardedEngine comes back on its default mesh — re-apply
        `.with_engine(...)` for a custom one."""
        from repro.federated import runtime as rt
        arrays, mj = ckpt_io.load_experiment_checkpoint(directory)
        if task is None:
            tm, tarr = mj["task_meta"], arrays["task"]
            parts = [np.asarray(tarr["parts"][str(i)])
                     for i in range(len(tarr["parts"]))]
            task = FederatedTask(tm["name"], tm["kind"], parts,
                                 tarr["data"], tarr["eval_data"],
                                 tm["n_classes"])
        sj = dict(mj["strategy"])
        for k in ("client_densities", "hetlora_ranks"):
            sj[k] = tuple(sj.get(k, ()))
        lj = dict(mj["lora"])
        lj["targets"] = tuple(lj.get("targets", ()))
        exp = cls(task,
                  strategy=st.StrategySpec(**sj),
                  federation=FederatedConfig(**mj["federation"]),
                  model=ModelOptions(**mj["model"]),
                  lora=LoRAConfig(**lj),
                  train=TrainOptions(**mj["train"]))
        cfg = rt.model_for_task(task, **exp.model.kwargs())
        exp.with_params(arrays["params"], cfg)
        exp.with_checkpoint(mj["checkpoint"]["dir"], mj["checkpoint"]["every"])
        ej = mj.get("engine", {"name": "sim"})
        ekw = ej.get("config")
        if ekw is None:     # pre-config checkpoints only stored the chunk
            ekw = ({"rounds_per_call": ej["rounds_per_call"]}
                   if ej.get("rounds_per_call", 1) > 1 else {})
        exp.with_engine(ej["name"], **ekw)
        pj = mj.get("population")
        if pj is not None:
            # the sampler config() spec carries cohort/seed; the store
            # arrays come back through the snapshot's aux payload when
            # run() enters the population round loop
            exp._population = {"population": int(pj["population"]),
                               "sampler": dict(pj["sampler"]),
                               "chunk": int(pj["chunk"]),
                               "prefetch": bool(pj["prefetch"]),
                               "sampler_kw": {}}
        exp._restore = (arrays, mj)
        return exp

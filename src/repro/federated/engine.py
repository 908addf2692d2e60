"""Pluggable execution engines: one federated round loop, many backends.

The FLASC round loop used to exist three times — `Experiment.run()`'s
inline Python loop, `launch/train.py`'s hand-rolled copy, and the sharded
step builders in `launch/steps.py`.  This module unifies them behind an
`Engine` protocol:

    compile(plan)  -> step       # one device call = one (or k) FL rounds
    run_rounds(state, data, callbacks) -> state'

Three registered backends:

  SimEngine      — the current jit+vmap single-device path, extracted out
                   of `Experiment.run()` and bit-identical to it.
  ShardedEngine  — the same experiment under jit(in_shardings=...,
                   donate_argnums=...) on a device mesh, reusing the
                   launch-layer sharding rules (`TRAIN_RULES`,
                   `activation_sharding`, `train_spmd_axes`).  An optional
                   `rounds_per_call` runs k rounds per device call through
                   `fedround.make_scanned_round_fn`, amortizing host
                   dispatch.
  AsyncEngine    — an event-driven virtual-clock simulator (paper Fig. 3
                   bandwidth scenarios): clients with heterogeneous
                   compute speed and up/down bandwidth
                   (`async_clock.ClientSystemProfile`) train against
                   stale server snapshots, and the server applies
                   FedBuff-style buffered, staleness-discounted
                   aggregation through the `Strategy.aggregate` hook.
                   With full concurrency, a full buffer, and a uniform
                   profile it reduces bit-exactly to SimEngine.

The loop body is a `Callback` hook pipeline (`on_round_end` / `on_eval` /
`on_checkpoint`): `LedgerCallback` (communication accounting, incl. the
practical coded-bytes wire format), `EvalCallback`, `LoggingCallback`,
and `CheckpointCallback` (periodic `checkpoint/io` snapshots that
`Experiment.resume` restarts from).  Callbacks may raise `StopRun` to end
a run cleanly — the interrupted-run path the checkpoint tests exercise.

Engines are registered like strategies: `resolve_engine("sim")`,
`resolve_engine("sharded", rounds_per_call=4)`, or pass an instance.
See docs/engines.md.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, ClassVar, Dict, List, Optional, Sequence, Type, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import comm as comm_mod
from repro.core import fedround
from repro.core import strategies as st
from repro.core import transport as tp
from repro.core.spans import gc_spans
from repro.federated import async_clock as ac
from repro.federated import population as popn
from repro.models.config import FederatedConfig

DataProvider = Callable[[int], Any]
# data(round_idx) -> client_batches pytree, leaves (n_clients, steps, bs, ...)


def _mean_f32(values) -> float:
    """Sequential float32 mean — the canonical, engine-independent
    reduction for recorded metrics.  XLA picks a fused reduction's
    association per program, so the same per-client values can average to
    scalars an ulp apart under different backends; a fixed host-side
    order cannot."""
    vals = np.asarray(values, np.float32)
    acc = np.float32(0.0)
    for v in vals:
        acc = np.float32(acc + v)
    return float(np.float32(acc / np.float32(max(vals.size, 1))))


def _sum_f32(values) -> float:
    """Sequential float32 sum (see `_mean_f32`)."""
    acc = np.float32(0.0)
    for v in np.asarray(values, np.float32):
        acc = np.float32(acc + v)
    return float(acc)


@dataclasses.dataclass
class RoundTask:
    """The static facets of one experiment — what an engine compiles (the
    `plan` argument of `Engine.compile(plan)`).

    loss_of  — a `fedround.LossFn`,
               `loss_of(params, trainable_tree, microbatch) -> scalar`.
    meta     — `fedround.FlatMeta` for the trainable tree: treedef, leaf
               shapes, the flat length `p_len`, and the LoRA rank/is-B
               index maps strategies use for structured masks.
    fed      — federation geometry + client/server optimizer settings.
    strategy — the *resolved* `Strategy` instance (not a spec/kind).
    params   — the frozen backbone pytree, passed as the leading step
               argument by every engine (never donated: the same
               buffers feed every round).  This is what lets the
               ShardedEngine apply TRAIN_RULES/FSDP in_shardings to the
               backbone so the big `configs/` entries fit a pod mesh
               (docs/engines.md "Sharded backbone params").
    seed     — base rng seed; engines derive per-round keys as
               `fold_in(key(seed + 2), round_idx)`.
    population — optional `population.Population` bundle (host-resident
               per-client state store + cohort sampler + prefetch flag);
               when set, the synchronous engines run
               `_run_population_rounds`: cohorts of `fed.n_clients` are
               sampled out of a population that can be orders of
               magnitude larger, with each client's momentum row
               gathered from / committed back to the host store.
    param_spec — optional logical-axes `P` spec tree matching `params`
               (e.g. `models.model.model_spec(cfg)`); the ShardedEngine
               translates it through its sharding rules into the
               backbone in_shardings.  None replicates the backbone.
    """
    loss_of: fedround.LossFn
    meta: fedround.FlatMeta
    fed: FederatedConfig
    strategy: st.Strategy
    params: Any
    seed: int = 0
    population: Optional[popn.Population] = None
    param_spec: Any = None


@dataclasses.dataclass
class RunState:
    """Everything that changes between rounds.

    `round` is the next round to execute (== len of a gap-free `history`);
    a checkpoint of a RunState resumes exactly there.  `flatP` is the flat
    trainable vector, `server` the server optimizer state dict
    (`fedround.init_server`), `sstate` the strategy's persistent pytree.
    `aux` is engine-owned auxiliary state serialized alongside checkpoints
    — `None` for the synchronous engines; the AsyncEngine keeps its
    virtual-clock snapshot (event queue, buffer, in-flight deltas) here so
    resume is bit-exact mid-flight.
    """
    plan: RoundTask
    flatP: Any
    server: Any
    sstate: Any
    round: int = 0
    rounds: int = 0
    history: List[Dict[str, Any]] = dataclasses.field(default_factory=list)
    aux: Optional[Dict[str, Any]] = None

    @classmethod
    def fresh(cls, plan: RoundTask, flatP, *, rounds: int) -> "RunState":
        return cls(plan, flatP, fedround.init_server(flatP),
                   plan.strategy.init_state(plan.meta.p_len),
                   round=0, rounds=rounds)


class StopRun(Exception):
    """Raised by a callback to end `run_rounds` cleanly after the current
    hook dispatch (simulates an interrupted run for checkpoint tests).

    With a scan-chunked engine (rounds_per_call > 1) raise it only on
    rounds where your callback's `wants_state` returns True: chunks end
    there, so `state.flatP` matches `state.round`.  Stopping at an
    interior round of a chunk would return weights from the chunk's last
    round with history/round still at the stop point."""


@dataclasses.dataclass
class RoundEvent:
    """Mutable context handed to every callback hook for one round."""
    round: int
    state: RunState
    metrics: Dict[str, Any]             # raw device metrics for this round
    record: Dict[str, Any]              # the history record being built
    evaluated: bool = False             # set by EvalCallback
    checkpoint_due: bool = False        # set by CheckpointCallback
    checkpoint_path: Optional[str] = None


class Callback:
    """Round-loop hook protocol; all hooks default to no-ops.

    `on_round_end(ev)` runs after every round with the round's raw device
    metrics and the mutable history `ev.record` being built; `on_eval(ev)`
    runs afterwards on rounds where an EvalCallback evaluated
    (`ev.evaluated`); `on_checkpoint(ev)` runs last on rounds a
    CheckpointCallback marked due.  Any hook may raise `StopRun` to end
    the run cleanly after this round's bookkeeping.

    `wants_state(round_idx, rounds)` marks rounds where the callback needs
    host access to the post-round state — scan-chunked engines end their
    chunks there so `state.flatP` is materialized.  Under the AsyncEngine
    a "round" is one buffered aggregation event and `ev.record` also
    carries the virtual-time keys (`sim_time`, `staleness`, `applied`,
    `dropped`), so callbacks can key behavior on simulated time as well
    as round index.
    """

    def wants_state(self, round_idx: int, rounds: int) -> bool:
        return False

    def on_round_end(self, ev: RoundEvent) -> None:
        pass

    def on_eval(self, ev: RoundEvent) -> None:
        pass

    def on_checkpoint(self, ev: RoundEvent) -> None:
        pass


class LedgerCallback(Callback):
    """Per-round communication accounting with full per-message nnz detail
    (the index-vs-bitmap coded-bytes minimum is taken per client message).

    A synchronous round bills one message per cohort client; engines whose
    rounds carry a different message count (the AsyncEngine's buffered
    aggregation events) set `metrics["n_messages"]` explicitly.  The
    average/total entry counts fed to `record_round` are derived from the
    per-message lists with the canonical host reductions, so ledger totals
    agree bit-for-bit across engine backends."""

    def __init__(self, ledger):
        self.ledger = ledger

    def on_round_end(self, ev: RoundEvent) -> None:
        m, led = ev.metrics, self.ledger
        n_messages = int(m.get("n_messages", ev.state.plan.fed.n_clients))
        # one bulk device->host transfer per direction; a float(v)
        # comprehension over a device array syncs once per client.  The
        # f32 round-trip is value-identical: every entry is an f32 nnz
        # count (or a python float thereof) already.
        down_pm = np.asarray(m["down_nnz_clients"], np.float32).tolist()
        up_pm = np.asarray(m["up_nnz_clients"], np.float32).tolist()
        led.record_round(
            n_messages, _mean_f32(down_pm), _sum_f32(up_pm),
            down_per_message=down_pm, up_per_message=up_pm)
        ev.record.update(
            down_bytes=led.down_bytes, up_bytes=led.up_bytes,
            total_bytes=led.total_bytes, coded_bytes=led.total_coded_bytes,
            down_coded_bytes=led.down_coded_bytes,
            up_coded_bytes=led.up_coded_bytes)


class EvalCallback(Callback):
    """Runs `eval_fn(flatP) -> acc` every `every` rounds and on the final
    round; records the result in the round's history record."""

    def __init__(self, eval_fn: Callable[[Any], float], every: int = 10):
        self.eval_fn = eval_fn
        self.every = every
        self.acc = 0.0

    def _due(self, round_idx: int, rounds: int) -> bool:
        at_cadence = self.every > 0 and (round_idx + 1) % self.every == 0
        return at_cadence or round_idx == rounds - 1

    def wants_state(self, round_idx: int, rounds: int) -> bool:
        return self._due(round_idx, rounds)

    def on_round_end(self, ev: RoundEvent) -> None:
        if self._due(ev.round, ev.state.rounds):
            self.acc = self.eval_fn(ev.state.flatP)
            ev.record["acc"] = self.acc
            ev.evaluated = True


class LoggingCallback(Callback):
    """Prints the classic one-line progress record on eval rounds, and —
    for runs without an EvalCallback — every `every` rounds."""

    def __init__(self, verbose: bool = True, every: int = 0):
        self.verbose = verbose
        self.every = every

    def _line(self, ev: RoundEvent) -> str:
        rec = ev.record
        acc = f" acc={rec['acc']:.4f}" if "acc" in rec else ""
        t = f" t={rec['sim_time']:.1f}s" if "sim_time" in rec else ""
        return (f"  round {ev.round + 1:4d} loss={rec['loss']:.4f}{acc} "
                f"comm={rec.get('total_bytes', 0) / 1e6:.2f}MB{t}")

    def on_round_end(self, ev: RoundEvent) -> None:
        if (self.verbose and not ev.evaluated and self.every > 0
                and (ev.round + 1) % self.every == 0):
            print(self._line(ev))

    def on_eval(self, ev: RoundEvent) -> None:
        if self.verbose:
            print(self._line(ev))


class CheckpointCallback(Callback):
    """Saves a resumable snapshot every `every` rounds via `save_fn(dir,
    state) -> path` (wired by `Experiment.with_checkpoint` to
    `checkpoint/io.save_experiment_checkpoint`)."""

    def __init__(self, directory: str, every: int,
                 save_fn: Callable[[str, RunState], str]):
        self.directory = directory
        self.every = max(int(every), 1)
        self.save_fn = save_fn
        self.last_path: Optional[str] = None

    def _due(self, round_idx: int) -> bool:
        return (round_idx + 1) % self.every == 0

    def wants_state(self, round_idx: int, rounds: int) -> bool:
        return self._due(round_idx)

    def on_round_end(self, ev: RoundEvent) -> None:
        if self._due(ev.round):
            ev.checkpoint_due = True

    def on_checkpoint(self, ev: RoundEvent) -> None:
        self.last_path = self.save_fn(self.directory, ev.state)
        ev.checkpoint_path = self.last_path


# ---------------------------------------------------------------------------
# the engine protocol + registry
# ---------------------------------------------------------------------------

def _tree_stack(trees: Sequence[Any]):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


class Engine:
    """Execution backend: compiles a `RoundTask` into a device step and
    drives the callback-instrumented round loop."""

    name: ClassVar[str] = "base"
    rounds_per_call: int = 1

    def config(self) -> Dict[str, Any]:
        """JSON-serializable constructor kwargs for checkpoint metadata:
        `resolve_engine(self.name, **self.config())` must rebuild an
        equivalent backend on resume (non-serializable facets like a
        device mesh fall back to their defaults)."""
        return {}

    def _step_params(self, plan: RoundTask) -> tuple:
        """Leading step arguments: the frozen backbone, which every engine
        prepends to every step call.  The ShardedEngine overrides this to
        place the backbone into its FSDP/TP storage layout once per run."""
        return (plan.params,)

    def compile(self, plan: RoundTask):
        """-> step(params, flatP, server, sstate, batch, key,
        client_mu=None) -> (flatP', server', sstate', metrics).
        `client_mu` is the population round's (cohort, p_len) momentum
        gather staged from the host store; with it, `metrics["client_mu"]`
        carries the finals back for the scatter commit."""
        raise NotImplementedError

    def _compile_chunk(self, plan: RoundTask):
        """-> chunk(params, flatP, server, sstate, batches, round_ids,
        base_key), leaves of `batches` carrying a leading rounds axis.
        Only engines with rounds_per_call > 1 need this."""
        raise NotImplementedError

    # --- the one round loop -----------------------------------------------
    def run_rounds(self, state: RunState, data: DataProvider,
                   callbacks: Sequence[Callback] = ()) -> RunState:
        """Run rounds [state.round, state.rounds); mutates and returns
        `state`.  Rng schedule: fold_in(key(seed + 2), round_idx)."""
        if state.plan.population is not None:
            return self._run_population_rounds(state, data, callbacks)
        plan = state.plan
        pargs = self._step_params(plan)
        base_key = jax.random.key(plan.seed + 2)
        step = self.compile(plan)
        chunk_step = None
        try:
            with gc_spans():
                r = state.round
                while r < state.rounds:
                    n = self._chunk_len(r, state, callbacks)
                    with TraceAnnotation("engine.round", round=r, rounds=n):
                        if n == 1:
                            with TraceAnnotation("engine.data"):
                                batch = data(r)
                            with TraceAnnotation("engine.dispatch"):
                                key = jax.random.fold_in(base_key, r)
                                (state.flatP, state.server, state.sstate,
                                 metrics) = step(
                                    *pargs, state.flatP, state.server,
                                    state.sstate, batch, key)
                            per_round = [metrics]
                        else:
                            if chunk_step is None:
                                chunk_step = self._compile_chunk(plan)
                            with TraceAnnotation("engine.data"):
                                batches = _tree_stack(
                                    [data(i) for i in range(r, r + n)])
                            with TraceAnnotation("engine.dispatch"):
                                rids = jnp.arange(r, r + n, dtype=jnp.int32)
                                (state.flatP, state.server, state.sstate,
                                 ms) = chunk_step(
                                    *pargs, state.flatP, state.server,
                                    state.sstate, batches, rids, base_key)
                            per_round = [jax.tree.map(lambda x, i=i: x[i], ms)
                                         for i in range(n)]
                        for i, m in enumerate(per_round):
                            with TraceAnnotation("engine.finish"):
                                self._finish_round(state, r + i, m, callbacks)
                    r += n
        except StopRun:
            pass
        return state

    # --- the population round loop -----------------------------------------
    def _run_population_rounds(self, state: RunState, data: DataProvider,
                               callbacks: Sequence[Callback] = ()
                               ) -> RunState:
        """The host-population variant of the round loop (docs/scale.md).

        Each round: sample a cohort of `fed.n_clients` ids from the
        population, gather their momentum rows from the host store and
        stage them with ONE `jax.device_put` of the stacked block (never
        a per-client transfer), run the unchanged vmapped round, then
        scatter the final rows back.  With `population.prefetch` on,
        round r+1's sample+gather+H2D happens between round r's async
        dispatch and its blocking device pull, so staging overlaps
        device compute — the double buffer.  Prefetch never changes
        values (see `CohortPrefetcher`), only when they move.

        The store rides `RunState.aux` (`{"population": ...}`) with the
        same snapshot cadence as the AsyncEngine's clock: on rounds a
        callback wants host state, plus a final snapshot — checkpoints
        resume mid-flight bit-exactly.  Rounds run one device call each
        (`rounds_per_call` is ignored: the scatter commit needs the
        host between rounds)."""
        plan = state.plan
        pop = plan.population
        assert pop is not None
        n = plan.fed.n_clients
        assert pop.sampler.cohort == n, \
            f"sampler cohort {pop.sampler.cohort} != fed.n_clients {n}"
        assert pop.store.row_len == plan.meta.p_len, \
            (pop.store.row_len, plan.meta.p_len)
        if state.aux and "population" in state.aux:
            pop.store.load_arrays(state.aux["population"])
        pargs = self._step_params(plan)
        base_key = jax.random.key(plan.seed + 2)
        step = self.compile(plan)
        # always stage through the prefetcher: its cold take() is the
        # same sample+gather+put the inline path would run, and its
        # wait/H2D counters instrument both modes (population_bench.py)
        pre = popn.CohortPrefetcher(pop.store, pop.sampler)
        pop.last_prefetcher = pre
        try:
            with gc_spans():
                r = state.round
                while r < state.rounds:
                    with TraceAnnotation("engine.round", round=r, rounds=1):
                        with TraceAnnotation("engine.stage"):
                            ids, mu_dev = pre.take(r)
                        with TraceAnnotation("engine.data"):
                            batch = data(r)
                        with TraceAnnotation("engine.dispatch"):
                            key = jax.random.fold_in(base_key, r)
                            (state.flatP, state.server, state.sstate,
                             metrics) = step(
                                *pargs, state.flatP, state.server,
                                state.sstate, batch, key, mu_dev)
                        if pop.prefetch and r + 1 < state.rounds:
                            # the jitted step dispatched asynchronously:
                            # stage round r+1 while round r computes;
                            # `exclude` defers any gather the commit below
                            # would invalidate
                            with TraceAnnotation("engine.stage"):
                                pre.prefetch(r + 1, exclude=ids)
                        # this pull blocks on round r's device work
                        with TraceAnnotation("engine.sync"):
                            mu_out = np.asarray(metrics.pop("client_mu"),
                                                np.float32)
                        pop.store.commit_cohort(ids, mu_out)
                        if any(cb.wants_state(r, state.rounds)
                               for cb in callbacks):
                            state.aux = {"population": pop.store.to_arrays()}
                        with TraceAnnotation("engine.finish"):
                            self._finish_round(state, r, metrics, callbacks,
                                               extra={"cohort": ids.tolist()})
                    r += 1
        except StopRun:
            pass
        state.aux = {"population": pop.store.to_arrays()}
        return state

    def _chunk_len(self, r: int, state: RunState,
                   callbacks: Sequence[Callback]) -> int:
        """Rounds to run in the next device call: capped by rounds_per_call
        and cut so rounds needing host state access end a chunk."""
        max_n = min(self.rounds_per_call, state.rounds - r)
        for i in range(max_n - 1):
            if any(cb.wants_state(r + i, state.rounds) for cb in callbacks):
                return i + 1
        return max_n

    def _finish_round(self, state: RunState, round_idx: int, metrics,
                      callbacks: Sequence[Callback],
                      extra: Optional[Dict[str, Any]] = None) -> None:
        # the recorded loss is the canonical host mean of the per-client
        # losses, identical across engine backends (see `_mean_f32`); its
        # pull is the round's first wait on the device
        with TraceAnnotation("engine.sync"):
            loss = (_mean_f32(metrics["loss_clients"])
                    if "loss_clients" in metrics else float(metrics["loss"]))
        record: Dict[str, Any] = {"round": round_idx, "loss": loss}
        if extra:
            record.update(extra)
        ev = RoundEvent(round=round_idx, state=state, metrics=metrics,
                        record=record)
        # A StopRun from any hook still finishes this round's bookkeeping
        # (history append + round advance) first, so ledger totals, history
        # length, and state.round stay mutually consistent on early stops.
        stop: Optional[StopRun] = None
        try:
            for cb in callbacks:
                _call_hook(cb, cb.on_round_end, ev)
            if ev.evaluated:
                for cb in callbacks:
                    _call_hook(cb, cb.on_eval, ev)
        except StopRun as e:
            stop = e
        state.history.append(record)
        state.round = round_idx + 1
        if ev.checkpoint_due and stop is None:
            for cb in callbacks:
                _call_hook(cb, cb.on_checkpoint, ev)
        if stop is not None:
            raise stop


def _call_hook(cb: Callback, hook, ev: RoundEvent) -> None:
    """One callback hook call, under the span `engine.callback.<Class>`."""
    with TraceAnnotation("engine.callback." + type(cb).__name__):
        hook(ev)


_ENGINES: Dict[str, Type[Engine]] = {}


def register_engine(name: str):
    """Class decorator: `@register_engine("sim")` makes the backend
    reachable from `Experiment.with_engine("sim")` and `BENCH_ENGINE`."""
    def deco(cls: Type[Engine]) -> Type[Engine]:
        assert issubclass(cls, Engine), cls
        cls.name = name
        _ENGINES[name] = cls
        return cls
    return deco


def registered_engines():
    return tuple(sorted(_ENGINES))


EngineLike = Union[Engine, str, Type[Engine]]


def resolve_engine(obj: EngineLike, **kwargs) -> Engine:
    """Engine instance / registered name / Engine class -> instance.

    A name or class is constructed with `**kwargs` (e.g.
    `resolve_engine("sharded", rounds_per_call=4)` or
    `resolve_engine("async", buffer_size=4)`); an already-built instance
    is passed through unchanged and rejects kwargs.  Unknown names raise
    `KeyError` listing `registered_engines()`."""
    if isinstance(obj, Engine):
        assert not kwargs, "pass constructor kwargs with a name, not an instance"
        return obj
    if isinstance(obj, str):
        try:
            cls = _ENGINES[obj]
        except KeyError:
            raise KeyError(f"no engine registered as {obj!r}; known: "
                           f"{registered_engines()}") from None
        return cls(**kwargs)
    if isinstance(obj, type) and issubclass(obj, Engine):
        return obj(**kwargs)
    raise TypeError(f"cannot resolve {obj!r} to an Engine")


# ---------------------------------------------------------------------------
# backends
# ---------------------------------------------------------------------------

@register_engine("sim")
class SimEngine(Engine):
    """Single-device jit+vmap simulation — the path `Experiment.run()`
    always took, now behind the protocol (and bit-identical to it)."""

    def config(self) -> Dict[str, Any]:
        # explicit (not inherited): the engine-config lint contract is
        # that every registered engine states its round-trip kwargs
        return {}

    def compile(self, plan: RoundTask):
        # donation is deliberately absent: the sim path runs on CPU/GPU
        # dev boxes where XLA ignores donation (with a warning), and
        # callers snapshot flatP across calls for the equality anchors
        return jax.jit(  # reprolint: disable=jit-no-donate -- see above
            fedround.make_round_fn(plan.loss_of, plan.meta,
                                   plan.fed, plan.strategy))


class _ShardedStep:
    """Deferred-jit wrapper: in_shardings need the concrete arg pytrees, so
    the jit is built on first call and executed under the engine's
    activation-sharding context (required at trace time for `constrain`).

    The step takes the backbone at position 0, stored under
    `param_shardings` and never donated; the client batch follows
    flatP/server/sstate, and trailing operands (the key, the population
    round's momentum rows) ride replicated.  After the first call,
    `in_shardings` and `donate_argnums` record what the jit was built
    with — the multi-device differential suite inspects them (plus the
    compiled executable's input shardings) to assert that FSDP param
    sharding actually applied and that the backbone is never donated
    (tests/test_sharded_multidevice.py)."""

    def __init__(self, engine: "ShardedEngine", fn, batch_client_axis: int,
                 param_shardings):
        self.engine = engine
        self.fn = fn
        self.batch_client_axis = batch_client_axis
        # a NamedSharding tree, built by the engine from plan.param_spec
        # through its rules
        self.param_shardings = param_shardings
        self.in_shardings = None
        self.donate_argnums: tuple = ()
        self._jitted = None

    def _build(self, server, sstate, batch, rest):
        from jax.sharding import NamedSharding, PartitionSpec
        from repro.launch.shardings import logical_to_pspec
        mesh = self.engine.mesh
        rules = self.engine.rules
        rep = NamedSharding(mesh, PartitionSpec())

        def rep_tree(tree):
            return jax.tree.map(lambda _: rep, tree)

        def batch_sharding(x):
            axes: List[Optional[str]] = [None] * x.ndim
            axes[self.batch_client_axis] = "clients"
            return NamedSharding(
                mesh, logical_to_pspec(x.shape, tuple(axes), mesh, rules))

        shardings = (self.param_shardings, rep, rep_tree(server),
                     rep_tree(sstate), jax.tree.map(batch_sharding, batch),
                     *(rep_tree(x) for x in rest))
        # flatP/server/sstate are consumed and rebuilt every round; the
        # backbone params are NOT — the same buffers feed every call, so
        # donating position 0 would be a use-after-donate on round 2
        donate = (1, 2, 3) if self.engine.donate else ()
        self.in_shardings = shardings
        self.donate_argnums = donate
        return jax.jit(self.fn, in_shardings=shardings, donate_argnums=donate)

    def __call__(self, *args):
        from repro.launch.shardings import activation_sharding
        _, _, server, sstate, batch, *rest = args
        if self._jitted is None:
            self._jitted = self._build(server, sstate, batch, rest)
        with activation_sharding(self.engine.mesh, self.engine.rules,
                                 exact=self.engine.exact):
            return self._jitted(*args)


@register_engine("sharded")
class ShardedEngine(Engine):
    """SPMD backend: the identical round function lowered with
    jit(in_shardings=..., donate_argnums=(1, 2, 3)) on a device mesh.

    The vmapped client axis is sharded over the mesh's data(+pod) axes
    (`train_spmd_axes`), activations follow the launch-layer `TRAIN_RULES`,
    and the weight vector / server state are replicated and donated.  On a
    single CPU device this degenerates to a (1, 1) mesh and is the
    end-to-end testable version of what the multi-pod dry-run lowers.

    `rounds_per_call=k` scans k rounds inside one device call
    (`fedround.make_scanned_round_fn`); chunks are cut at rounds where a
    callback needs host state (eval, checkpoint), so cadences still hold.

    Sharded backbone params: the step takes the frozen backbone as its
    leading argument (never donated) and its
    *storage* in_shardings come from `plan.param_spec` through
    `param_rules` — on a 2-D client×model mesh the vmapped client axis
    shards over "data" while backbone storage dims shard over "model"
    (and, with `fsdp=True`, over "data" too: the ZeRO-3 overlay).  With
    `exact=True` (the default) compute gathers the backbone to full
    replicas at use and model-axis activation rules are dropped, so the
    sharded program is bit-identical to SimEngine — the differential
    anchor tests/test_sharded_multidevice.py holds on a real 8-device
    mesh.  `exact=False` keeps full TP activation sharding (the dry-run
    lowering), trading the bit-equality anchor for sharded compute
    (docs/engines.md "Sharded backbone params").
    """

    def __init__(self, mesh=None, *, rounds_per_call: int = 1,
                 donate: bool = True, rules=None, fsdp: bool = False,
                 exact: bool = True):
        self._mesh = mesh
        self.rounds_per_call = max(int(rounds_per_call), 1)
        self.donate = donate
        self.fsdp = bool(fsdp)
        self.exact = bool(exact)
        self._rules = rules
        # the most recently compiled _ShardedStep: after a run, its
        # recorded in_shardings/donate_argnums let tests and harnesses
        # inspect what the round was actually built with
        self.last_step: Optional[_ShardedStep] = None
        # backbone placed into its storage layout, cached per params id:
        # re-placing every round would re-transfer the whole backbone
        self._placed_params: Optional[tuple] = None

    # mesh/rules are live device/partition objects (not serializable) and
    # donate only matters with a mesh: a resumed engine comes back on its
    # defaults (documented in Experiment.resume)
    def config(self) -> Dict[str, Any]:  # reprolint: disable=engine-config -- see above
        cfg: Dict[str, Any] = {}
        if self.rounds_per_call > 1:
            cfg["rounds_per_call"] = self.rounds_per_call
        if self.fsdp:
            cfg["fsdp"] = True
        if not self.exact:
            cfg["exact"] = False
        return cfg

    @property
    def mesh(self):
        if self._mesh is None:
            from repro.launch.mesh import make_train_mesh
            self._mesh = make_train_mesh(1, 1)
        return self._mesh

    @property
    def rules(self):
        """Activation rules for the step trace.  In `exact` mode the
        model-axis entries are dropped: per-client compute stays local
        and full (gather-at-use), so only the client axis shards compute
        — what keeps sim==sharded bitwise.  Param *storage* still shards
        over the model axis through `param_rules`."""
        if self._rules is None:
            from repro.launch.steps import TRAIN_RULES
            if self.exact:
                self._rules = {k: (() if "model" in v else v)
                               for k, v in TRAIN_RULES.items()}
            else:
                self._rules = TRAIN_RULES
        return self._rules

    @property
    def param_rules(self):
        """Storage rules for the backbone step argument: TP dims over
        "model" (TRAIN_RULES), plus the ZeRO-3 `embed` overlay over the
        data axes with `fsdp=True`."""
        from repro.launch.steps import TRAIN_FSDP_RULES, TRAIN_RULES
        return TRAIN_FSDP_RULES if self.fsdp else TRAIN_RULES

    def _param_shardings(self, plan: RoundTask):
        """NamedSharding tree for the backbone step argument.
        `plan.param_spec` (logical P axes) translates through
        `param_rules`; without a spec the backbone replicates."""
        if plan.param_spec is None:
            from jax.sharding import NamedSharding, PartitionSpec
            rep = NamedSharding(self.mesh, PartitionSpec())
            return jax.tree.map(lambda _: rep, plan.params)
        from repro.launch.shardings import spec_tree_shardings
        return spec_tree_shardings(plan.param_spec, self.mesh,
                                   self.param_rules)

    def _step_params(self, plan: RoundTask) -> tuple:
        """Place the backbone into its sharded storage layout ONCE per
        run (matching the step's in_shardings, so no per-round reshard)
        and feed the placed copy to every step call."""
        key = id(plan.params)
        if self._placed_params is None or self._placed_params[0] != key:
            placed = jax.device_put(plan.params, self._param_shardings(plan))
            self._placed_params = (key, placed)
        return (self._placed_params[1],)

    def _round_fn(self, plan: RoundTask):
        from repro.launch.steps import train_spmd_axes
        return fedround.make_round_fn(plan.loss_of, plan.meta, plan.fed,
                                      plan.strategy,
                                      spmd_axis_name=train_spmd_axes(self.mesh))

    def compile(self, plan: RoundTask):
        self.last_step = _ShardedStep(
            self, self._round_fn(plan), batch_client_axis=0,
            param_shardings=self._param_shardings(plan))
        return self.last_step

    def _compile_chunk(self, plan: RoundTask):
        self.last_step = _ShardedStep(
            self,
            fedround.make_scanned_round_fn(self._round_fn(plan)),
            batch_client_axis=1,
            param_shardings=self._param_shardings(plan))
        return self.last_step


@register_engine("async")
class AsyncEngine(Engine):
    """Event-driven async backend: virtual-clock client timing + FedBuff-
    style buffered, staleness-weighted aggregation.

    Clients draw compute speed and up/down bandwidth from a
    `ClientSystemProfile`; a client job downloads the current server
    snapshot, trains locally, and uploads its delta, completing at

        t_start + coded_down_bytes / down_bw
                + local_steps * step_time / speed
                + coded_up_bytes / up_bw

    on the virtual clock — both transfers charged over the *coded* wire
    bytes (`comm.coded_message_bytes`, the same index/bitmap minimum the
    `CommLedger` bills).  The server buffers arriving updates; when
    `buffer_size` have arrived it aggregates them — each delta scaled by
    the `staleness_weight` of (current version - start version) — through
    the unmodified `Strategy.aggregate` hook, applies the server
    optimizer, and advances one "round".  Updates staler than
    `max_staleness` are dropped (their traffic is still billed).

    One aggregation event == one round of the callback pipeline: history
    records additionally carry `sim_time`, `staleness`, `applied`, and
    `dropped`, so eval/logging/checkpoint cadences are keyed by virtual
    time as well as round index.  Checkpoints snapshot the whole event
    queue (in-flight deltas included) into `RunState.aux`; resume is
    bit-exact mid-flight.

    Sync-equivalence anchor: with `concurrency == n_clients`,
    `buffer_size == n_clients`, and a uniform profile (the defaults),
    every aggregation event is one full fresh cohort at staleness 0, and
    the run reproduces `SimEngine` history — weights, losses, ledger —
    bit for bit (tests/test_async_engine.py, all registered strategy
    kinds).

    Client participation: an optional `sampler=` (a registered
    `population.CohortSampler` name, spec dict, or instance) gates which
    idle clients may start a job against each server version —
    participation fractions and availability traces on the async path.
    A version whose every startable client is gated falls back to
    ignoring the trace (the FedBuff-timeout analog), so the event loop
    cannot starve.  Non-uniform aggregation (`hetlora_weighted`) runs
    under partial / stale / version-repeat buffers by specializing the
    server phase to the buffer's slot tuple (`cohort_slots`), so rank
    coverage counts exactly the rows present.

    Not supported: DP aggregation (`fed.dp_clip > 0`) — its noise
    calibration assumes one uniform synchronous cohort.
    """

    def __init__(self, *, concurrency: Optional[int] = None,
                 buffer_size: Optional[int] = None,
                 staleness_alpha: float = 0.5,
                 max_staleness: Optional[int] = None,
                 allow_version_repeats: bool = False,
                 profile=None, sampler=None):
        if isinstance(profile, dict):   # checkpoint meta round-trip
            profile = ac.ClientSystemProfile(
                **{k: tuple(v) if isinstance(v, list) else v
                   for k, v in profile.items()})
        self.concurrency = None if concurrency is None else int(concurrency)
        self.buffer_size = None if buffer_size is None else int(buffer_size)
        self.staleness_alpha = float(staleness_alpha)
        self.max_staleness = (None if max_staleness is None
                              else int(max_staleness))
        assert self.max_staleness is None or self.max_staleness >= 0
        # by default a client waits for the server version to advance
        # before starting its next job (FedBuff samples cohorts without
        # replacement); True lets fast clients train continuously, with
        # repeat jobs folding fresh quantization keys
        self.allow_version_repeats = bool(allow_version_repeats)
        self.profile = profile if profile is not None \
            else ac.ClientSystemProfile()
        # None, a registered sampler name, a CohortSampler instance, or a
        # config() spec dict ({"kind": "fraction", "participation": ...}):
        # gates which idle clients may start a job each server version —
        # the participation-fraction / availability-trace leg of the
        # population work, on the async path (docs/scale.md)
        self.sampler = sampler

    def config(self) -> Dict[str, Any]:
        sampler = (self.sampler.config()
                   if isinstance(self.sampler, popn.CohortSampler)
                   else self.sampler)
        return {"concurrency": self.concurrency,
                "buffer_size": self.buffer_size,
                "staleness_alpha": self.staleness_alpha,
                "max_staleness": self.max_staleness,
                "allow_version_repeats": self.allow_version_repeats,
                "profile": dataclasses.asdict(self.profile),
                "sampler": sampler}

    def compile(self, plan: RoundTask):
        raise NotImplementedError(
            "AsyncEngine has no single-round step: it drives split client/"
            "server phases (fedround.make_client_phase_fn / "
            "make_server_phase_fn) from run_rounds")

    # --- the event loop ----------------------------------------------------
    def run_rounds(self, state: RunState, data: DataProvider,
                   callbacks: Sequence[Callback] = ()) -> RunState:
        plan = state.plan
        fed, meta = plan.fed, plan.meta
        if fed.dp_clip > 0.0:
            raise NotImplementedError(
                "AsyncEngine: DP aggregation (dp_clip > 0) under buffered/"
                "partial aggregation is the open ROADMAP item 'DP noise "
                "calibration under buffered/partial aggregation' (Million-"
                "client cohorts): the noise scale assumes one uniform "
                "synchronous cohort, and stale/partial buffers change each "
                "client's effective sensitivity.  Run DP on SimEngine or "
                "ShardedEngine — sync mode draws fresh noise every round "
                "(the PR 6 key-rotation fix, pinned in tests/test_engine.py)")
        if plan.population is not None:
            raise NotImplementedError(
                "AsyncEngine: the host population store is a synchronous-"
                "engine path (the async cohort IS the client population); "
                "pass sampler= to the engine for participation/"
                "availability gating instead")
        n = fed.n_clients
        concurrency = (n if self.concurrency is None
                       else min(self.concurrency, n))
        buffer_size = n if self.buffer_size is None else self.buffer_size
        assert concurrency >= 1 and buffer_size >= 1, (concurrency,
                                                       buffer_size)
        sampler = (None if self.sampler is None
                   else popn.resolve_sampler(self.sampler, population=n))
        prof = self.profile
        spec = plan.strategy.spec
        # per-direction wire format from the transport config — the same
        # (value_bytes, dense-coded) pair the CommLedger bills, so job
        # durations and ledger bytes stay mutually consistent for every
        # spec (quantized, low-rank-compressed, or plain f32 sparse)
        down_vb, down_dense = tp.wire_format(spec, meta.p_len, "down")
        up_vb, up_dense = tp.wire_format(spec, meta.p_len, "up")
        # sparse aggregation (spec.sparse_aggregate): jobs carry packed
        # (index, value) rows at this static capacity instead of dense
        # (p_len,) deltas, and buffers of all-packed jobs aggregate
        # through the scatter-add server phase; 0 means "stay dense"
        pack_cap = st.sparse_aggregate_capacity(
            st.resolve(plan.strategy), meta.p_len)
        base_key = jax.random.key(plan.seed + 2)
        # no donation on either phase: flatP/sstate snapshots outlive the
        # call — in-flight client jobs keep reading the captured version,
        # so donating here would be a use-after-donate
        server_fn = jax.jit(  # reprolint: disable=jit-no-donate -- see above
            fedround.make_server_phase_fn(meta, fed, plan.strategy))
        sparse_server_fn = None if not pack_cap else \
            jax.jit(  # reprolint: disable=jit-no-donate -- see above
                fedround.make_server_phase_fn(meta, fed, plan.strategy,
                                              sparse=True))
        server_fns = (server_fn, sparse_server_fn)
        full_slots = tuple(range(n))
        slot_server_fns: Dict[Any, Any] = {}

        def get_server_fns(slots):
            """(dense_fn, sparse_fn_or_None) for a buffer aggregating the
            jobs of `slots` (seq order, duplicates allowed).  Uniform
            aggregation — and the full fresh cohort of the
            sync-equivalence anchor — reuses the two precompiled phases;
            a weighted `Strategy.aggregate` (hetlora_weighted's rank
            coverage) bakes the slot identities into the phase via
            `cohort_slots`, so partial / stale / version-repeat buffers
            scale every entry by the coverage of the rows actually
            present instead of refusing to run.  One compile per
            distinct slots tuple (at most one per buffer composition
            seen)."""
            if plan.strategy.uniform_aggregation or slots == full_slots:
                return server_fns
            if slots not in slot_server_fns:
                def mk(sp):
                    return jax.jit(  # reprolint: disable=jit-no-donate -- see above
                        fedround.make_server_phase_fn(
                            meta, fed, plan.strategy, sparse=sp,
                            cohort_slots=slots))
                slot_server_fns[slots] = (mk(False),
                                          mk(True) if pack_cap else None)
            return slot_server_fns[slots]

        client_fns: Dict[Any, Any] = {}
        clock = (ac.VirtualClock.from_arrays(state.aux, n, meta.p_len)
                 if state.aux is not None
                 else ac.VirtualClock(n, meta.p_len))
        # job index -> cohort batch; data(j) is deterministic, so entries a
        # straggler still needs can be evicted and recomputed — the cap
        # matters because min(job_counts) lags arbitrarily far behind fast
        # clients under heterogeneous profiles
        data_cache: Dict[int, Any] = {}
        data_cache_cap = max(2 * n, 16)

        def fetch(j: int):
            if j not in data_cache:
                if len(data_cache) >= data_cache_cap:
                    del data_cache[next(iter(data_cache))]   # oldest insert
                data_cache[j] = data(j)
            return data_cache[j]

        def client_fn(slots, repeats):
            if not (spec.quant_bits_up or spec.quant_bits_down):
                # repeats only perturb quantization keys; without them,
                # normalize the cache key so repeat jobs
                # (allow_version_repeats) never force a recompile
                repeats = (0,) * len(slots)
            key = (slots, repeats)
            if key not in client_fns:
                # no donation (see server_fn): the same flatP snapshot is
                # fed to every concurrent client job at this version
                client_fns[key] = jax.jit(  # reprolint: disable=jit-no-donate -- see above
                    fedround.make_client_phase_fn(
                        plan.loss_of, meta, fed, plan.strategy, slots,
                        repeats, pack_cap=pack_cap or None))
            return client_fns[key]

        pargs = self._step_params(plan)

        def launch(slots):
            version = state.round
            repeats = tuple(clock.version_repeat(c, version) for c in slots)
            rows = [jax.tree.map(lambda x, c=c: x[c],
                                 fetch(int(clock.job_counts[c])))
                    for c in slots]
            batch = jax.tree.map(lambda *xs: jnp.stack(xs), *rows)
            rng = jax.random.fold_in(base_key, version)
            out = client_fn(slots, repeats)(
                *pargs, state.flatP, state.sstate,
                jnp.asarray(version, jnp.int32), batch, rng)
            deltas, up_nnzs, losses, down_nnzs = out[:4]
            # double-buffered data staging: the client phase dispatched
            # asynchronously, so warm each starter's *next* job batch from
            # the provider now — the host-side data prep overlaps the
            # device compute instead of serializing before the next launch
            for c in slots:
                fetch(int(clock.job_counts[c]) + 1)
            # one bulk pull per output: per-index float()/row indexing on
            # the device arrays would sync the stream once per job in this
            # loop, and device rows held in Jobs would pin the whole stacked
            # cohort result until the last straggler aggregates
            down_host = np.asarray(down_nnzs, np.float32)
            up_host = np.asarray(up_nnzs, np.float32)
            loss_host = np.asarray(losses, np.float32)
            if pack_cap:
                # sparse aggregation: bulk-transfer the packed pair —
                # O(pack_cap) per job instead of O(p_len) — and pull a
                # dense row only for a message whose support overflowed
                # the static capacity (that job aggregates densely)
                pidx, pval, pnnz = out[4:]
                idx_host = np.asarray(pidx, np.int32)
                val_host = np.asarray(pval, np.float32)
                pn_host = np.asarray(pnnz)
                delta_rows = [
                    (idx_host[i], val_host[i])
                    if int(pn_host[i]) <= pack_cap
                    else np.asarray(deltas[i], np.float32)
                    for i in range(len(slots))]
            else:
                delta_host = np.asarray(deltas, np.float32)
                delta_rows = [delta_host[i] for i in range(len(slots))]
            for i, c in enumerate(slots):
                dn, un = float(down_host[i]), float(up_host[i])
                dur = (prof.down_time(c, comm_mod.coded_message_bytes(
                           int(dn), meta.p_len, 1, down_vb, down_dense))
                       + prof.compute_time(c, fed.local_steps)
                       + prof.up_time(c, comm_mod.coded_message_bytes(
                           int(un), meta.p_len, 1, up_vb, up_dense)))
                clock.submit(ac.Job(
                    slot=c, version=version, seq=clock.next_seq(),
                    t_start=clock.now, t_finish=clock.now + dur,
                    delta=delta_rows[i], loss=loss_host[i],
                    down_nnz=dn, up_nnz=un))
                clock.job_counts[c] += 1

        def start_jobs():
            version = state.round
            budget = max(concurrency - len(clock.inflight), 0)
            startable = [c for c in clock.idle
                         if (self.allow_version_repeats
                             or clock.last_version[c] < version)]
            if sampler is not None:
                elig = sampler.eligible(version)
                avail = [c for c in startable if bool(elig[c])]
                if not avail and startable and not clock.inflight \
                        and not clock.buffer:
                    # availability starvation: every startable client is
                    # outside its trace window with nothing in flight or
                    # buffered.  The server version only advances through
                    # an aggregation and eligibility is a function of the
                    # version, so waiting would deadlock — ignore the
                    # trace for this version (FedBuff-timeout analog)
                    avail = startable
            else:
                avail = startable
            starters = avail[:budget]
            taken = set(starters)
            clock.idle = [c for c in clock.idle if c not in taken]
            if not starters:
                return
            slots = tuple(sorted(starters))
            if slots == tuple(range(n)) or len(slots) == 1:
                # a full fresh cohort runs as ONE vmapped device call — the
                # sync-equivalence anchor needs the identical program shape
                launch(slots)
            else:
                # partial cohorts launch per client: at most n+1 compiled
                # programs total, instead of one per slot combination
                for c in slots:
                    launch((c,))
            # every future job index is >= the slowest client's count, so
            # these can never be requested again
            low = int(clock.job_counts.min())
            for stale in [j for j in data_cache if j < low]:
                del data_cache[stale]

        try:
            while state.round < state.rounds:
                if not clock.pending:
                    start_jobs()
                    if not clock.inflight:
                        # every client already contributed to this version:
                        # the buffer can never reach K — flush it partially
                        # (FedBuff timeout semantics)
                        assert clock.buffer, "async engine deadlocked"
                        self._aggregate(state, clock, get_server_fns, callbacks)
                        continue
                    clock.pull_completions()
                job = clock.pending.pop(0)
                clock.idle.append(job.slot)
                staleness = state.round - job.version
                if (self.max_staleness is not None
                        and staleness > self.max_staleness):
                    clock.drop(job)
                    continue
                clock.buffer.append(job)
                if len(clock.buffer) >= buffer_size:
                    self._aggregate(state, clock, get_server_fns, callbacks)
        except StopRun:
            pass
        state.aux = clock.to_arrays()
        return state

    def _aggregate(self, state: RunState, clock: "ac.VirtualClock",
                   get_server_fns, callbacks: Sequence[Callback]) -> None:
        """Apply one buffered aggregation event and run the round-end
        callback pipeline for it.  Updates aggregate in submission (seq)
        order, so results don't depend on arrival jitter within a buffer —
        and a full fresh cohort aggregates in slot order, exactly like the
        synchronous round.

        `get_server_fns(slots)` (built in `run_rounds`) resolves the
        (dense_fn, sparse_fn_or_None) pair for this buffer's slot tuple —
        slot-specialized under a non-uniform `Strategy.aggregate`, the
        shared precompiled pair otherwise.  A buffer of all-packed jobs
        goes through the scatter-add sparse phase; any dense row in the
        buffer (sparse aggregation off, or a capacity-overflowed message)
        flips the whole event to the dense phase, with packed peers
        densified on the host first."""
        jobs, clock.buffer = sorted(clock.buffer, key=lambda j: j.seq), []
        server_fn, sparse_fn = get_server_fns(
            tuple(int(j.slot) for j in jobs))
        staleness = [state.round - j.version for j in jobs]
        weights = jnp.asarray(
            [ac.staleness_weight(s, self.staleness_alpha) for s in staleness],
            jnp.float32)
        # jobs carry host rows (see launch): one H2D upload of the stacked
        # buffer, instead of stacking per-job device remnants
        if sparse_fn is not None and all(isinstance(j.delta, tuple)
                                         for j in jobs):
            idx = jnp.asarray(np.stack([j.delta[0] for j in jobs]))
            val = jnp.asarray(np.stack([j.delta[1] for j in jobs]))
            state.flatP, state.server, state.sstate = sparse_fn(
                state.flatP, state.server, state.sstate, idx, val, weights)
        else:
            deltas = jnp.asarray(np.stack(
                [ac.dense_delta(j.delta, clock.p_len) for j in jobs]))
            state.flatP, state.server, state.sstate = server_fn(
                state.flatP, state.server, state.sstate, deltas, weights)
        drop_down, drop_up = clock.take_drops()
        down_list = [j.down_nnz for j in jobs] + drop_down
        up_list = [j.up_nnz for j in jobs] + drop_up
        metrics: Dict[str, Any] = {
            # one full fresh cohort in seq order carries the same values in
            # the same order as the synchronous round's metrics, so the
            # canonical host reductions reproduce its record bit-for-bit
            "loss_clients": [j.loss for j in jobs],
            "down_nnz": _mean_f32(down_list),
            "up_nnz": _sum_f32(up_list),
            "down_nnz_clients": down_list,
            "up_nnz_clients": up_list,
            "n_messages": len(down_list),
        }
        extra = {"sim_time": clock.now,
                 "staleness": _mean_f32(staleness),
                 "applied": len(jobs), "dropped": len(drop_down)}
        # snapshot the simulator *before* the hooks so a checkpoint taken
        # by this event captures a resumable event queue — but only on
        # rounds where a callback asked for host state (serializing every
        # in-flight delta per event is pure waste otherwise; a StopRun at
        # any round is still covered by the final snapshot in run_rounds)
        if any(cb.wants_state(state.round, state.rounds)
               for cb in callbacks):
            state.aux = clock.to_arrays()
        self._finish_round(state, state.round, metrics, callbacks,
                           extra=extra)

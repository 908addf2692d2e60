"""Federated training cells: the system's normal round loop,
`Experiment(...).run()` on `SimEngine`, as `repro.launch.train` assembles
it, over a backbone and token batches made on the device from the seed.

One `Experiment` runs from the seed: the first `check_rounds` rounds feed
the correctness comparison, the first `warmup_rounds` (which compile) are
set-up, and then a callback of the benchmark's own times every round on
the host clock until `--seconds` have passed, and ends the run with
`StopRun`.  Each round's metrics are already pulled to the host at round
end by the system's ledger callback, so a round's wall time is the time
between two round ends.

Afterwards, with the program's state freed, the plain reference
(`references/federated.py` over the configuration's model reference)
replays the first `check_rounds` rounds from the same seed, and the run is
correct when each number compared is within its limit:

    loss_gap    every client's loss in every checked round, largest
                relative gap
    grad_gap    the first pseudo-gradient as Adam received it (its first
                moment after round one, / (1 - b1)), worst leaf
    change_gap  the adapter's change over the checked rounds, worst leaf
    ledger_gap  the coded bytes of the checked rounds' messages, relative
    ledger_sums the system's ledger against the closed forms over its own
                message sizes, for every round of the run (exact, limit 0)
"""
from __future__ import annotations

import gc
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, weights
from bench.record import Check, Run
from bench.system import check_layout, lora_config, model_config
from references import federated as fedref

from repro.federated import engine as eng
from repro.federated.api import Experiment
from repro.models.config import FederatedConfig


class TracedSimEngine(eng.SimEngine):
    """`SimEngine` with the benchmark's span around each round call.  It
    keeps the compiled step and the shapes of its first call's arguments,
    so a traced run can read the step's HLO for its kernels' names."""

    step = None
    shapes = None

    def compile(self, plan):
        step = self.step = super().compile(plan)

        def traced(*args):
            if self.shapes is None:
                self.shapes = jax.tree.map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
            with jax.profiler.TraceAnnotation("bench.round"):
                return step(*args)
        return traced

    def hlo_text(self) -> str:
        return self.step.lower(*self.shapes).compile().as_text()


class Window(eng.Callback):
    """Captures what the checks read during the first rounds, then times
    every round of the window and stops the run when it has lasted
    `seconds`."""

    def __init__(self, ctx, warmup: int, check_rounds: int):
        self.ctx = ctx
        self.warmup = warmup
        self.check_rounds = check_rounds
        self.losses: List[List[float]] = []     # per round, per client
        self.messages: List[tuple] = []      # (down nnz list, up nnz list)
        self.m0 = None
        self.p_check = None
        self.t0 = None
        self.ends: List[float] = []
        self.window_losses: List[float] = []
        self.compiles_before = None

    def on_round_end(self, ev):
        with jax.profiler.TraceAnnotation("bench.callback"):
            now = time.perf_counter()
            r = ev.round
            m = ev.metrics
            self.messages.append(
                (np.asarray(m["down_nnz_clients"], np.float64).tolist(),
                 np.asarray(m["up_nnz_clients"], np.float64).tolist()))
            loss = float(ev.record["loss"])
            if r < self.check_rounds:
                self.losses.append(
                    np.asarray(m["loss_clients"], np.float64).tolist())
            if r == 0:
                self.m0 = np.asarray(ev.state.server["opt"]["m"])
                self.ctx.mark("first_round_s")
            if r == self.check_rounds - 1:
                self.p_check = np.asarray(ev.state.flatP)
            if r == self.warmup - 1:
                self.compiles_before = self.ctx.clock.snapshot()["compiles"]
                self.ctx.window_started()
                self.t0 = time.perf_counter()
                return
            if r >= self.warmup:
                self.ends.append(now)
                self.window_losses.append(loss)
                self.ctx.unit_done()
                if now - self.t0 >= self.ctx.seconds:
                    self.ctx.window_ended()
                    raise eng.StopRun()


def _wire(sizes, n: int, value_bytes: float) -> int:
    """Coded bytes of messages of these sizes, each of n entries."""
    return sum(fedref.coded_bytes(int(v), n, value_bytes) for v in sizes)


def _coded(messages, n: int, down_vb: float, up_vb: float) -> float:
    return float(_wire(messages[0], n, down_vb) + _wire(messages[1], n, up_vb))


def run(cell, ctx) -> Run:
    ref = cell.reference()
    d = ref.dims(cell.config)
    tr = cell.traffic
    co = tr["cohort"]
    C, B, S, steps = (co["clients"], co["sequences_per_client"],
                      co["seq_len"], co["local_steps"])
    warmup, n_check = int(tr["warmup_rounds"]), int(tr["check_rounds"])
    if not warmup >= n_check >= 1:
        raise ValueError(f"need warmup_rounds >= check_rounds >= 1, got "
                         f"{warmup} and {n_check}")
    seed = ctx.seed
    base = jax.random.key(seed)
    k_weights, k_data = jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)

    params = weights.backbone(ref.backbone_shapes(d), k_weights)
    mcfg = model_config(cell.config["name"], d)
    check_layout(params, mcfg)
    ctx.mark("weights_s")

    make_tokens = jax.jit(lambda r: jax.random.randint(
        jax.random.fold_in(k_data, r), (C, steps, B, S), 0, d["vocab"],
        dtype=jnp.int32))

    def provider(r):
        with jax.profiler.TraceAnnotation("bench.data"):
            return {"tokens": make_tokens(r)}

    opt = tr["optimizer"]
    strat = dict(tr["strategy"])
    fed = FederatedConfig(
        n_clients=C, local_batch=B, local_steps=steps,
        client_lr=opt["client_lr"], client_momentum=opt["client_momentum"],
        server_lr=opt["server_lr"], adam_b1=opt["adam_b1"],
        adam_b2=opt["adam_b2"], adam_eps=opt["adam_eps"],
        density_down=strat.get("density_down", 1.0),
        density_up=strat.get("density_up", 1.0))
    win = Window(ctx, warmup, n_check)
    engine = TracedSimEngine()
    kind = strat.pop("kind")
    exp = (Experiment(None, federation=fed)
           .with_strategy(kind, **strat)
           .with_lora(config=lora_config(cell.config))
           .with_training(rounds=1 << 30, eval_every=0, pretrain_steps=0,
                          train_head=False, seed=seed)
           .with_engine(engine)
           .with_params(params, mcfg)
           .with_data(provider)
           .with_callbacks(win))
    # the adapter the run starts from, as the system builds it
    trainable, meta, _ = exp._build_trainable(params, mcfg)
    p0_prog = np.asarray(meta.flatten(trainable))
    del trainable
    result = exp.run()
    t_end = win.ends[-1]

    out = Run(cell=cell, dims=d, device=ctx.device, peaks=ctx.peaks)
    out.setup_s = win.t0 - ctx.t_start
    out.window_s = t_end - win.t0
    edges = [win.t0] + win.ends
    out.unit_s = [b - a for a, b in zip(edges[:-1], edges[1:])]
    out.attempted = len(win.ends)
    out.failed = int(sum(not np.isfinite(x) for x in win.window_losses))
    out.work = {"rounds": float(len(win.ends)),
                "compiles_in_window":
                    float(ctx.clock.snapshot()["compiles"]
                          - win.compiles_before)}
    ctx.read_memory()
    if ctx.capture is not None:
        out.hlo_texts = [engine.hlo_text()]

    # --- the system's ledger against its own message sizes (every round)
    led = result.ledger
    n = led.total_params
    closed_down = sum(_wire(m[0], n, led.down_value_bytes)
                      for m in win.messages)
    closed_up = sum(_wire(m[1], n, led.up_value_bytes) for m in win.messages)
    sums_gap = float(abs(led.down_coded_bytes - closed_down)
                     + abs(led.up_coded_bytes - closed_up)
                     + abs(led.rounds - len(win.messages)))
    prog = {"loss": win.losses, "m0": win.m0, "p": win.p_check,
            "p0": p0_prog, "messages": win.messages[:n_check],
            "vb": (led.down_value_bytes, led.up_value_bytes)}
    del exp, result, params, win, engine
    gc.collect()

    refout = reference_rounds(ref, d, cell, seed, n_check)
    out.checks = checks(ref, d, cell, prog, refout) + [
        Check("ledger_sums", sums_gap, 0.0)]
    out.notes.update(program=prog, reference=refout)
    return out


def reference_rounds(ref, d, cell, seed: int, rounds: int,
                     precision: str = "f32", use_clients: int = 0):
    """The plain reference's first `rounds` rounds from the same seed."""
    tr = cell.traffic
    co = tr["cohort"]
    C, B, S, steps = (co["clients"], co["sequences_per_client"],
                      co["seq_len"], co["local_steps"])
    base = jax.random.key(seed)
    k_weights, k_data = jax.random.fold_in(base, 0), jax.random.fold_in(base, 1)
    wts = weights.backbone(ref.backbone_shapes(d), k_weights)
    make_tokens = jax.jit(lambda r: jax.random.randint(
        jax.random.fold_in(k_data, r), (C, steps, B, S), 0, d["vocab"],
        dtype=jnp.int32))
    cache: Dict[int, Any] = {}

    def batches_of(r, c):
        if r not in cache:
            cache.clear()
            cache[r] = make_tokens(r)
        return cache[r][c]

    def loss_fn(w, flat, mb):
        return ref.loss(w, flat, mb, d, precision)

    p0 = ref.lora_init(d, jax.random.key(seed + 1))
    out = fedref.run_rounds(loss_fn, wts, p0, batches_of,
                            fedref.Round.from_traffic(tr), rounds, seed, C,
                            use_clients=use_clients)
    out["p0"] = np.asarray(p0)
    return out


def numbers(ref, d, cell, prog: dict, refout: dict) -> Dict[str, float]:
    """The compared numbers of a run against a reference run."""
    o = cell.traffic["optimizer"]
    g_ref = refout["m0"] / (1 - o["adam_b1"])
    g_prog = prog["m0"] / (1 - o["adam_b1"])
    ref_leaves = ref.split_leaves(g_ref, d)
    counted = compare.counted_leaves(compare.leaf_norms(ref_leaves))
    grad_gap = compare.worst_leaf_gap(ref.split_leaves(g_prog, d),
                                      ref_leaves, counted)
    change_gap = compare.worst_leaf_gap(
        ref.split_leaves(prog["p"] - prog["p0"], d),
        ref.split_leaves(refout["params"][-1] - refout["p0"], d), counted)
    n = len(refout["p0"])
    dvb, uvb = prog["vb"]
    coded_prog = sum(_coded(m, n, dvb, uvb) for m in prog["messages"])
    coded_ref = sum(_coded((dn, up), n, dvb, uvb)
                    for dn, up in zip(refout["down_nnz"], refout["up_nnz"]))
    loss_gap = max(compare.max_rel_gap(p, r) for p, r in
                   zip(prog["loss"], refout["loss_clients"]))
    return {"loss_gap": loss_gap,
            "grad_gap": grad_gap, "change_gap": change_gap,
            "ledger_gap": abs(coded_prog - coded_ref) / max(coded_ref, 1.0)}


def checks(ref, d, cell, prog: dict, refout: dict) -> List[Check]:
    return [Check(k, v, cell.limits.get(k))
            for k, v in numbers(ref, d, cell, prog, refout).items()]


def as_program(refout: dict, value_bytes) -> dict:
    """A reference run in the shape `numbers` reads a program run in (the
    control, and the planted faults, put in the program's place)."""
    return {"loss": refout["loss_clients"], "m0": refout["m0"],
            "p": refout["params"][-1], "p0": refout["p0"],
            "messages": list(zip(refout["down_nnz"], refout["up_nnz"])),
            "vb": value_bytes}

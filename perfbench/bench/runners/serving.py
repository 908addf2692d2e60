"""Serving cells: the system's `ServingEngine.run` over successive seeded
chunks of requests, with tenant adapters in a `PagedAdapterCache` and the
grouped adapter kernel on the decode path.

Set-up makes the backbone and every tenant's adapter on the device from
the seed, builds the engine, and runs `warmup_chunks` chunks, which compile
every prefill bucket, the lane write, the decode step and the page writes.
The window then runs chunk after chunk, each under the benchmark's span,
until `--seconds` have passed; a chunk's requests all arrive at once.

Afterwards, with the engine freed, a sample of the window's requests drawn
from the seed, the longest among them, is run through the plain reference
with its prompt and served tokens, and the run is correct when

    logit_gap   the widest gap by which a served token's reference logit
                lies below the reference's best at its position
    incomplete  requests of the window that did not get their budget of
                tokens (exact, limit 0)

are within their limits.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, traffic, weights
from bench.record import Check, Run
from bench.system import check_layout, lora_config, model_config

from repro.serving import HostAdapterStore, PagedAdapterCache, ServingEngine
from repro.serving.trace import Request

# chunk indices of the warm-up, apart from the window's 0, 1, 2, ...
WARMUP_INDEX = 1 << 20


def _requests(dicts: List[Dict]) -> List[Request]:
    return [Request(**r) for r in dicts]


def build(cell, seed: int):
    """(engine, cache, sizes) of a cell, from the seed."""
    ref = cell.reference()
    d = ref.dims(cell.config)
    tr = cell.traffic
    base = jax.random.key(seed)
    params = weights.backbone(ref.backbone_shapes(d), jax.random.fold_in(base, 0))
    mcfg = model_config(cell.config["name"], d)
    check_layout(params, mcfg)
    layout = ref.lora_layout(d)
    flat = np.asarray(weights.tenant_adapters(
        layout, jax.random.fold_in(base, 2), int(tr["tenants"]),
        float(tr["adapter_b_std"])))
    store = HostAdapterStore()
    for t in range(int(tr["tenants"])):
        store.put(t, weights.adapter_tree(flat[t], layout))
    del flat
    cache = PagedAdapterCache(store, store.get(0), pages=int(tr["pages"]))
    engine = ServingEngine(params, mcfg, cache, n_lanes=int(tr["lanes"]),
                           lora_scale=lora_config(cell.config).scale,
                           max_len=int(tr["max_len"]))
    return engine, cache, d


def run(cell, ctx) -> Run:
    tr = cell.traffic
    seed = ctx.seed
    engine, cache, d = build(cell, seed)
    ctx.mark("built_s")
    rid = 0
    for i in range(int(tr.get("warmup_chunks", 1))):
        reqs = traffic.chunk(tr, seed, WARMUP_INDEX + i, d["vocab"], rid0=rid)
        rid += len(reqs)
        engine.run(_requests(reqs))
    stats0 = cache.stats()
    compiles0 = ctx.clock.snapshot()["compiles"]
    ctx.window_started()
    t0 = time.perf_counter()
    served: List[Dict] = []
    chunk_s: List[float] = []
    tokens = 0
    index = 0
    while True:
        reqs = traffic.chunk(tr, seed, index, d["vocab"], rid0=rid)
        rid += len(reqs)
        index += 1
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.chunk"):
            rep = engine.run(_requests(reqs))
        now = time.perf_counter()
        chunk_s.append(now - t)
        tokens += rep.generated_tokens
        ctx.unit_done(tokens=rep.generated_tokens)
        for r in reqs:
            served.append(dict(r, tokens=rep.completions.get(r["rid"], [])))
        if now - t0 >= ctx.seconds:
            break
    ctx.window_ended()
    t_end = time.perf_counter()
    stats1 = cache.stats()

    out = Run(cell=cell, dims=d, device=ctx.device, peaks=ctx.peaks)
    out.setup_s = t0 - ctx.t_start
    out.window_s = t_end - t0
    out.unit_s = chunk_s
    hits = stats1["hits"] - stats0["hits"]
    misses = stats1["misses"] - stats0["misses"]
    out.work = {"tokens": float(tokens), "requests": float(len(served)),
                "chunk_s": float(sum(chunk_s)),
                "adapter_hits": float(hits), "adapter_misses": float(misses),
                "compiles_in_window":
                    float(ctx.clock.snapshot()["compiles"] - compiles0)}
    incomplete = sum(len(r["tokens"]) != r["gen_len"] for r in served)
    out.attempted = len(served)
    out.failed = int(incomplete)
    ctx.read_memory()
    del engine, cache
    gc.collect()

    sample = check_sample(served, seed, int(tr["check_requests"]))
    gaps = reference_gaps(cell, d, seed, sample)
    out.checks = [Check("logit_gap", max(gaps["served"]),
                        cell.limits.get("logit_gap")),
                  Check("incomplete", float(incomplete), 0.0)]
    out.notes.update(sample=sample, gaps=gaps["served"],
                     checked_tokens=sum(len(r["tokens"]) for r in sample))
    return out


def check_sample(served: List[Dict], seed: int, n: int) -> List[Dict]:
    """`n` requests of the window drawn from the seed, the longest first."""
    done = [r for r in served if len(r["tokens"]) == r["gen_len"]]
    longest = max(range(len(done)),
                  key=lambda i: done[i]["prompt_len"] + done[i]["gen_len"])
    rng = np.random.default_rng([seed, 7])
    rest = [i for i in range(len(done)) if i != longest]
    pick = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [done[longest]] + [done[rest[i]] for i in sorted(pick)]


def reference_gaps(cell, d, seed: int, sample: List[Dict],
                   controls=()) -> Dict[str, List[float]]:
    """Per sampled request: the served tokens' widest gap under the f32
    reference ('served'), and for each precision in `controls` the gap of
    the tokens that precision puts first."""
    ref = cell.reference()
    tr = cell.traffic
    base = jax.random.key(seed)
    wts = weights.backbone(ref.backbone_shapes(d), jax.random.fold_in(base, 0))
    adapters = weights.tenant_adapters(
        ref.lora_layout(d), jax.random.fold_in(base, 2), int(tr["tenants"]),
        float(tr["adapter_b_std"]))
    T = int(tr["max_len"])
    fns = {p: jax.jit(lambda w, a, t, p=p: ref.logits(w, a, t, d, p))
           for p in ("f32",) + tuple(controls)}
    out: Dict[str, List[float]] = {"served": []}
    out.update({p: [] for p in controls})
    for r in sample:
        seq = list(r["prompt"]) + list(r["tokens"][:-1])
        toks = jnp.asarray(seq + [0] * (T - len(seq)), jnp.int32)
        lo, hi = r["prompt_len"] - 1, r["prompt_len"] - 1 + r["gen_len"]
        lg = np.asarray(fns["f32"](wts, adapters[r["client"]], toks))[lo:hi]
        out["served"].append(compare.logit_gap(lg, r["tokens"]))
        for p in controls:
            lc = np.asarray(fns[p](wts, adapters[r["client"]], toks))[lo:hi]
            out[p].append(compare.logit_gap(lg, list(lc.argmax(-1))))
    return out

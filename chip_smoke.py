"""Drive the system's main path once on a TPU, at GPT-2 Small's full width.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # one host with four chips (2x2 mesh)

Everything runs in this one process; nothing is started beside it.  With
no arguments the phases are:

1. Device check: the first device must be a TPU, or the script exits
   non-zero before doing any work.
2. Federated training through `Experiment`, as `repro.launch.train`
   builds it: GPT-2 Small (12 layers, d_model 768, vocab 50257) with
   random weights from `--seed`, 8 clients x 4 sequences of 256
   synthetic tokens, LoRA rank 16, `flasc` at density 0.25, 3 rounds on
   the default engine.  Then the same rounds with sparse aggregation, the
   fused selector and 4-bit uploads, so the packing and fused transport
   kernels run natively, and once more with the `histogram` selector at
   the fused selector's depth, which the fused run must match bit for
   bit.  Losses must be finite and
   the ledger must match the closed forms of its own messages.
3. Serving: `ServingEngine` on the same backbone, 8 tenant adapters, 4
   pages, 4 lanes, 16 requests, with the `grouped_pallas` kernel native.
   Every request must complete, and one request's tokens must equal the
   single-adapter prefill/decode path's.

`--four-chips` runs only the sharded phase: the same `flasc` rounds through
`Experiment.with_mesh((2, 2), fsdp=True)` over four devices, compared with
`SimEngine` on the first device; final weights and ledgers must be equal.

Any failure raises and exits non-zero.  The last line printed is the JSON
result, `{"ok": true, "device": {...}}`; it is printed only when every
phase passed.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_config  # noqa: E402
from repro.core import comm  # noqa: E402
from repro.core import selectors as sel  # noqa: E402
from repro.core import sparsity as sp  # noqa: E402
from repro.federated import engine as eng  # noqa: E402
from repro.federated.api import Experiment  # noqa: E402
from repro.kernels import fused_transport as ft  # noqa: E402
from repro.kernels.lora_matmul import (PallasGroupedKernel,  # noqa: E402
                                       resolve_grouped_kernel)
from repro.launch import compile_cache  # noqa: E402
from repro.models import lora as lora_mod  # noqa: E402
from repro.models import model as mdl  # noqa: E402
from repro.models.config import FederatedConfig, LoRAConfig  # noqa: E402
from repro.models.layers import init_params  # noqa: E402
from repro.serving import (HostAdapterStore, PagedAdapterCache,  # noqa: E402
                           ServingEngine, synth_trace)

CLIENTS, LOCAL_BATCH, SEQ, RANK, ROUNDS, DENSITY = 8, 4, 256, 16, 3, 0.25
TENANTS, PAGES, LANES, REQUESTS, MAX_LEN = 8, 4, 4, 16, 48


@sel.register_selector("histogram-matched")
class MatchedHistogram(sel.HistogramSelector):
    """`histogram` at the fused selector's depth: `FusedSelector(levels=L)`
    is bit-identical to `HistogramSelector(iters=L)`, while the registered
    `histogram` default bisects 24 times to the fused default's 12."""

    def __init__(self):
        super().__init__(iters=ft.LEVELS)


class CompileClock:
    """Seconds spent in XLA compiles and persistent-cache hits, from JAX's
    own monitoring events."""

    def __init__(self):
        self.compile_s = 0.0
        self.cache_hits = 0

        def on_duration(event, secs, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self):
        return self.compile_s, self.cache_hits


def device_check():
    devs = jax.devices()
    dev = devs[0]
    print(f"[smoke] jax {jax.__version__}; device {dev.platform} "
          f"kind={dev.device_kind!r} count={len(devs)}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit(f"[smoke] no TPU: the first device is a "
                         f"{dev.platform!r} device")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


class Capture(eng.Callback):
    """Round-end hook keeping what the checks read: final weights and
    every round's per-message sizes and losses."""

    def __init__(self):
        self.down, self.up, self.losses = [], [], []
        self.flatP = None

    def on_round_end(self, ev):
        m = ev.metrics
        self.down.append(
            [int(v) for v in np.asarray(m["down_nnz_clients"]).reshape(-1)])
        self.up.append(
            [int(v) for v in np.asarray(m["up_nnz_clients"]).reshape(-1)])
        self.losses.append(ev.record["loss"])
        self.flatP = np.asarray(ev.state.flatP)


def build_experiment(cfg, params, seed, cap, **strategy):
    """The `repro.launch.train` assembly: task-less Experiment over a given
    backbone, synthetic token batches drawn on the device from `seed`."""
    fed = FederatedConfig(n_clients=CLIENTS, local_batch=LOCAL_BATCH,
                          local_steps=1, client_lr=1e-3, server_lr=2e-3)
    data_key = jax.random.key(seed + 1)

    def batches(r):
        return {"tokens": jax.random.randint(
            jax.random.fold_in(data_key, r),
            (CLIENTS, 1, LOCAL_BATCH, SEQ), 0, cfg.vocab_size)}

    return (Experiment(None, federation=fed)
            .with_strategy("flasc", density_down=DENSITY,
                           density_up=DENSITY, **strategy)
            .with_lora(config=LoRAConfig(rank=RANK))
            .with_training(rounds=ROUNDS, eval_every=0, pretrain_steps=0,
                           train_head=False)
            .with_params(params, cfg)
            .with_data(batches)
            .with_callbacks(cap))


def check_ledger(name, led, cap, up_value_bytes):
    """Ledger == the closed forms over the run's own message sizes:
    values are the summed nnz, values bytes are nnz times the wire width,
    coded bytes are the cheaper of index and bitmap coding per message."""
    p = led.total_params
    assert len(cap.losses) == ROUNDS, (name, cap.losses)
    assert all(math.isfinite(x) for x in cap.losses), (name, cap.losses)
    down = [v for r in cap.down for v in r]
    up = [v for r in cap.up for v in r]
    assert len(down) == len(up) == ROUNDS * CLIENTS, (name, down, up)
    assert led.rounds == ROUNDS, name
    assert led.down_values == sum(down), (name, led.down_values)
    assert led.up_values == sum(up), (name, led.up_values)
    assert led.up_value_bytes == up_value_bytes, (name, led.up_value_bytes)
    assert led.down_bytes == int(sum(down) * comm.VALUE_BYTES), name
    assert led.up_bytes == int(sum(up) * up_value_bytes), name
    assert led.down_coded_bytes == sum(
        comm.coded_message_bytes(v, p, 1, comm.VALUE_BYTES) for v in down), \
        name
    assert led.up_coded_bytes == sum(
        comm.coded_message_bytes(v, p, 1, up_value_bytes) for v in up), name
    assert np.all(np.isfinite(cap.flatP)), name


def run_logged(name, exp, clock):
    c0, h0 = clock.snapshot()
    t0 = time.perf_counter()
    res = exp.run()
    wall = time.perf_counter() - t0
    c1, h1 = clock.snapshot()
    led = res.ledger
    print(f"[smoke] {name}: losses {[r['loss'] for r in res.history]} "
          f"wall {wall:.1f}s compile {c1 - c0:.1f}s cache-hits {h1 - h0} "
          f"| ledger down {led.down_bytes} B (coded {led.down_coded_bytes}) "
          f"up {led.up_bytes} B (coded {led.up_coded_bytes}) "
          f"| peak HBM {peak_bytes()} B", flush=True)
    return res


def check_pack_kernel(p_len, seed):
    """The batched pack kernel, native, against the jnp reference codec."""
    k = sp.density_count(p_len, DENSITY)
    cap = comm.pack_capacity(p_len, k)
    kx, km = jax.random.split(jax.random.key(seed + 2))
    x = jax.random.normal(kx, (CLIENTS, p_len))
    x = x * (jax.random.uniform(km, x.shape) < DENSITY)
    got = jax.jit(lambda v: ft.pack_values_batch(v, cap))(x)
    want = jax.jit(jax.vmap(lambda v: ft.pack_values(v, cap)))(x)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    print(f"[smoke] pack_values_batch == pack_values on ({CLIENTS}, {p_len}) "
          f"cap {cap}", flush=True)
    return cap


def train_phase(cfg, seed, clock):
    params = init_params(mdl.model_spec(cfg), jax.random.key(seed))
    cap = Capture()
    res = run_logged("flasc (default engine)",
                     build_experiment(cfg, params, seed, cap), clock)
    led = res.ledger
    check_ledger("flasc", led, cap, 4.0)
    # the default exact selector keeps exactly k entries per message
    k = sp.density_count(led.total_params, DENSITY)
    assert {v for r in cap.down + cap.up for v in r} == {k}, (k, cap.down)

    pack_cap = check_pack_kernel(led.total_params, seed)
    sparse = dict(sparse_aggregate=True, quant_bits_up=4)
    fused_cap, hist_cap = Capture(), Capture()
    fused = run_logged(
        "flasc sparse_aggregate + fused selector + 4-bit up",
        build_experiment(cfg, params, seed, fused_cap, selector="fused",
                         **sparse), clock)
    check_ledger("fused", fused.ledger, fused_cap, 0.5)
    # every upload fit the packed capacity, so aggregation ran packed
    assert max(v for r in fused_cap.up for v in r) <= pack_cap, pack_cap
    hist = run_logged(
        "flasc sparse_aggregate + matched histogram selector + 4-bit up",
        build_experiment(cfg, params, seed, hist_cap,
                         selector="histogram-matched", **sparse), clock)
    np.testing.assert_array_equal(fused_cap.flatP, hist_cap.flatP)
    assert fused.history == hist.history
    assert vars(fused.ledger) == vars(hist.ledger), \
        (vars(fused.ledger), vars(hist.ledger))
    print("[smoke] fused == histogram: final weights, history and ledger "
          "bitwise equal", flush=True)
    return params


def serve_phase(cfg, params, seed, clock):
    kern = resolve_grouped_kernel(None)
    assert isinstance(kern, PallasGroupedKernel) and not kern._interpret(), \
        kern
    lcfg = LoRAConfig(rank=RANK, alpha=2 * RANK, dtype="float32")
    akey = jax.random.key(seed + 3)
    store = HostAdapterStore()
    for c in range(TENANTS):
        kc = jax.random.fold_in(akey, c)
        lt = lora_mod.init_lora(cfg, lcfg, kc)
        store.put(c, jax.tree.map(
            lambda x: x + 0.02 * jax.random.normal(
                jax.random.fold_in(kc, 7), x.shape, x.dtype), lt))
    cache = PagedAdapterCache(store, store.get(0), pages=PAGES)
    trace = synth_trace(REQUESTS, TENANTS, cfg.vocab_size, seed=seed,
                        prompt_buckets=(8, 16), gen_range=(4, 12))
    eng = ServingEngine(params, cfg, cache, n_lanes=LANES,
                        lora_scale=lcfg.scale, max_len=MAX_LEN)
    c0, h0 = clock.snapshot()
    rep = eng.run(trace)
    c1, h1 = clock.snapshot()
    assert len(rep.completions) == REQUESTS == rep.requests, rep.requests
    for req in trace:
        assert len(rep.completions[req.rid]) == req.gen_len, req
    print(f"[smoke] serving ({kern!r}): {len(rep.completions)}/{REQUESTS} "
          f"requests, {rep.generated_tokens} tokens in {rep.wall_s:.1f}s "
          f"(compile {c1 - c0:.1f}s, cache-hits {h1 - h0}), occupancy "
          f"{rep.mean_occupancy:.2f}/{LANES}, cache {rep.cache} "
          f"| peak HBM {peak_bytes()} B", flush=True)

    req = trace[0]
    lt = jax.tree.map(jnp.asarray, store.get(req.client))
    toks = jnp.asarray(np.asarray(req.prompt, np.int32)[None])
    logits, c = mdl.prefill(params, cfg, {"tokens": toks}, lora=lt,
                            lora_scale=lcfg.scale, max_len=MAX_LEN)
    want = [int(jnp.argmax(logits[0, -1]))]
    pos = req.prompt_len
    for _ in range(req.gen_len - 1):
        lg, c = mdl.decode_step(params, cfg,
                                jnp.asarray([want[-1]], jnp.int32),
                                jnp.asarray(pos, jnp.int32), c, lora=lt,
                                lora_scale=lcfg.scale)
        want.append(int(jnp.argmax(lg[0, 0])))
        pos += 1
    assert rep.completions[req.rid] == want, (rep.completions[req.rid], want)
    print(f"[smoke] request {req.rid} (tenant {req.client}) == single-adapter "
          f"path: {want}", flush=True)


def four_chip_phase(cfg, seed, clock):
    assert len(jax.devices()) >= 4, jax.devices()
    params = init_params(mdl.model_spec(cfg), jax.random.key(seed))
    cap_sim, cap_sh = Capture(), Capture()
    sim = run_logged("flasc SimEngine (device 0)",
                     build_experiment(cfg, params, seed, cap_sim), clock)
    exp = build_experiment(cfg, params, seed, cap_sh).with_mesh(
        (2, 2), fsdp=True)
    sh = run_logged("flasc ShardedEngine 2x2 fsdp", exp, clock)
    step = exp.engine.last_step
    specs = [str(s.spec) for s in jax.tree.leaves(step.in_shardings[0])]
    placed = exp.engine._placed_params[1]
    ndev = max(len(x.sharding.device_set) for x in jax.tree.leaves(placed))
    print(f"[smoke] backbone in_shardings: {sum('data' in s for s in specs)} "
          f"leaves over data, {sum('model' in s for s in specs)} over model; "
          f"widest placed leaf spans {ndev} devices", flush=True)
    assert ndev == 4, ndev
    diff = float(np.max(np.abs(cap_sim.flatP - cap_sh.flatP)))
    print(f"[smoke] sharded vs sim: max |flatP diff| {diff!r}; history "
          f"equal {sim.history == sh.history}", flush=True)
    check_ledger("sim", sim.ledger, cap_sim, 4.0)
    check_ledger("sharded", sh.ledger, cap_sh, 4.0)
    assert vars(sim.ledger) == vars(sh.ledger)
    np.testing.assert_array_equal(cap_sim.flatP, cap_sh.flatP)
    assert sim.history == sh.history


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded phase and its SimEngine "
                         "reference (needs four devices)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds weights, adapters, data and the trace")
    args = ap.parse_args()

    print(f"[smoke] compile cache: {compile_cache.enable()}", flush=True)
    device = device_check()
    clock = CompileClock()
    cfg = get_config("gpt2-small")
    print(f"[smoke] {cfg.name}: {cfg.num_layers}L d_model {cfg.d_model} "
          f"vocab {cfg.vocab_size}", flush=True)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(cfg, args.seed, clock)
    else:
        params = train_phase(cfg, args.seed, clock)
        serve_phase(cfg, params, args.seed, clock)
    compile_s, hits = clock.snapshot()
    print(f"[smoke] all phases passed in {time.perf_counter() - t0:.1f}s; "
          f"compile {compile_s:.1f}s total, {hits} persistent-cache hits",
          flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

"""Multi-tenant serving launcher: a thin CLI over `serving.ServingEngine`.

Builds a per-client adapter library (one LoRA tree per client, all seeded
from --seed), a paged device cache, and a Zipf-popularity request trace,
then runs the continuous-batching loop and prints the throughput and
cache report.

  PYTHONPATH=src python -m repro.launch.serve --arch yi-9b \
      --clients 8 --pages 4 --lanes 4 --requests 16

`--full` serves the full-width config (e.g. `--arch gpt2-small --full`);
`--profile DIR` writes a profile of the serving loop (docs/serving.md).

The heavy lifting all lives in `repro.serving` (see docs/serving.md);
this module only assembles the reduced architecture and the synthetic
tenant population.
"""
from __future__ import annotations

import argparse
import contextlib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds params, adapters and the request trace")
    ap.add_argument("--clients", type=int, default=8,
                    help="tenant population (adapters in the host store)")
    ap.add_argument("--pages", type=int, default=4,
                    help="device-resident adapter pages")
    ap.add_argument("--lanes", type=int, default=4,
                    help="concurrent decode lanes")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rank", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=48,
                    help="per-lane KV cache capacity (prompt + generation)")
    ap.add_argument("--full", action="store_true",
                    help="serve the full-width config instead of the "
                         "reduced smoke variant")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="serve under jax.profiler.trace(DIR): the serving "
                         "loop's and the cache's spans land in one trace "
                         "with the device's ops (docs/serving.md)")
    args = ap.parse_args()

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax

    from repro.configs.registry import get_config
    from repro.models import lora as lora_mod
    from repro.models import model as mdl
    from repro.models.config import LoRAConfig
    from repro.models.layers import init_params
    from repro.serving import (HostAdapterStore, PagedAdapterCache,
                               ServingEngine, synth_trace)

    cfg = get_config(args.arch, smoke=not args.full)
    dev = jax.devices()[0]
    print(f"[serve] device {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}, compile cache {cache_dir}")
    if cfg.encoder_decoder or cfg.embed_inputs or cfg.num_classes:
        raise SystemExit(f"[serve] {args.arch} is not a causal token LM; "
                         "the serving engine needs one")
    pkey, akey = jax.random.split(jax.random.key(args.seed))
    params = init_params(mdl.model_spec(cfg), pkey)
    lcfg = LoRAConfig(rank=args.rank, alpha=2 * args.rank, dtype="float32")

    # one trained-looking adapter per tenant (b is zero at init; perturb it
    # so the adapters actually disagree and the paged path is observable).
    store = HostAdapterStore()
    for c in range(args.clients):
        kc = jax.random.fold_in(akey, c)
        lt = lora_mod.init_lora(cfg, lcfg, kc)
        lt = jax.tree.map(
            lambda x: x + 0.02 * jax.random.normal(
                jax.random.fold_in(kc, 7), x.shape, x.dtype), lt)
        store.put(c, lt)
    cache = PagedAdapterCache(store, store.get(0), pages=args.pages)

    trace = synth_trace(args.requests, args.clients, cfg.vocab_size,
                        seed=args.seed, prompt_buckets=(8, 16),
                        gen_range=(4, 12))
    print(f"[serve] {args.arch} "
          f"({'full' if args.full else 'reduced'}: "
          f"{cfg.num_layers}L d{cfg.d_model}) "
          f"{args.clients} tenants / {args.pages} pages / {args.lanes} lanes")
    eng = ServingEngine(params, cfg, cache, n_lanes=args.lanes,
                        lora_scale=lcfg.scale, max_len=args.max_len)
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        rep = eng.run(trace)
    st = rep.cache
    print(f"[serve] {len(rep.completions)}/{rep.requests} requests served: "
          f"{rep.generated_tokens} tokens in {rep.wall_s:.2f}s "
          f"({rep.tokens_per_s:.1f} tok/s), "
          f"occupancy {rep.mean_occupancy:.2f}/{args.lanes} lanes")
    print(f"[serve] cache: hit-rate {st['hit_rate']:.2f} "
          f"({st['hits']} hits / {st['misses']} misses / "
          f"{st['evictions']} evictions), resident {st['resident']}"
          f"/{st['pages']} pages, {rep.stalls} admission stalls")


if __name__ == "__main__":
    main()

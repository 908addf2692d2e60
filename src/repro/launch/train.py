"""Production training launcher: federated LoRA finetuning of any assigned
architecture — a thin CLI over `Experiment` + the engine registry.

  # real compute at CPU scale (reduced variant, synthetic federated data):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --rounds 20

  # scan-chunked dispatch (4 rounds per device call):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --rounds-per-call 4

  # the full-width config (GPT-2 Small fits one TPU v5e chip whole):
  PYTHONPATH=src python -m repro.launch.train --arch gpt2-small --full

  # a profile of the rounds, host spans and device ops on one clock:
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --profile /tmp/prof

  # production lowering of the FULL config against the pod mesh (no compute):
  PYTHONPATH=src python -m repro.launch.train --arch yi-9b --dry-run [--multi-pod]

The round loop itself lives in `federated/engine.py` (the same loop every
benchmark and experiment uses); this module only assembles the reduced
architecture, a synthetic batch provider, and the ShardedEngine, then
reports the full communication ledger — per-client averages and the
practical coded-bytes wire totals included.
"""
from __future__ import annotations

import argparse
import contextlib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--rounds", type=int, default=20)
    ap.add_argument("--rank", type=int, default=8)
    ap.add_argument("--density", type=float, default=0.25)
    ap.add_argument("--strategy", default="flasc")
    ap.add_argument("--engine", default="sharded",
                    help="registered engine backend (sim | sharded)")
    ap.add_argument("--rounds-per-call", type=int, default=1,
                    help="scan-chunk k rounds into one device call (sharded)")
    ap.add_argument("--mesh", default=None, metavar="CxM",
                    help="2-D client-axis x model-axis mesh for the sharded "
                         "engine, e.g. 4x2 (needs >= C*M jax devices)")
    ap.add_argument("--fsdp", action="store_true",
                    help="overlay ZeRO-3 backbone param sharding over the "
                         "client axis (sharded engine with --mesh)")
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds param init and the synthetic data stream")
    ap.add_argument("--full", action="store_true",
                    help="train the full-width config instead of the "
                         "reduced smoke variant")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="run the rounds under jax.profiler.trace(DIR): the "
                         "round loop's spans and the round's named scopes "
                         "land in one trace (docs/engines.md)")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    args = ap.parse_args()

    if args.dry_run:
        # delegate to the dry-run module (it must own process startup so the
        # forced device count precedes jax initialization)
        import os
        import subprocess
        import sys
        cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", args.arch,
               "--shape", "train_4k"] + (["--multi-pod"] if args.multi_pod else [])
        raise SystemExit(subprocess.call(cmd, env=dict(os.environ)))

    from repro.launch import compile_cache
    cache_dir = compile_cache.enable()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.federated.api import Experiment
    from repro.models import model as mdl
    from repro.models.config import FederatedConfig, LoRAConfig
    from repro.models.layers import init_params

    cfg = get_config(args.arch, smoke=not args.full)
    dev = jax.devices()[0]
    print(f"[train] device {dev.platform} ({dev.device_kind}) "
          f"x{len(jax.devices())}, compile cache {cache_dir}")
    print(f"[train] {args.arch} "
          f"({'full' if args.full else 'reduced'}: "
          f"{cfg.num_layers}L d{cfg.d_model}) "
          f"strategy={args.strategy} d={args.density} r={args.rank} "
          f"engine={args.engine}")
    params = init_params(mdl.model_spec(cfg), jax.random.key(args.seed))
    fed = FederatedConfig(n_clients=args.clients, local_batch=4, local_steps=1,
                          client_lr=1e-3, server_lr=2e-3)

    S = 32
    rng = np.random.default_rng(args.seed)

    def batch_for_round(r):
        b = {"tokens": jnp.asarray(rng.integers(
            0, cfg.vocab_size, (fed.n_clients, 1, fed.local_batch, S)), jnp.int32)}
        if cfg.encoder_decoder:
            b["frames"] = jnp.asarray(rng.normal(
                0, .1, (fed.n_clients, 1, fed.local_batch, cfg.encoder_seq,
                        cfg.d_model)), jnp.float32)
        if cfg.num_image_tokens:
            b["image_embeds"] = jnp.asarray(rng.normal(
                0, .1, (fed.n_clients, 1, fed.local_batch,
                        cfg.num_image_tokens, cfg.vision_embed_dim)), jnp.float32)
        return b

    engine_kw = ({"rounds_per_call": args.rounds_per_call}
                 if args.engine == "sharded" else {})
    exp = (Experiment(None, federation=fed)
           .with_strategy(args.strategy, density_down=args.density,
                          density_up=args.density)
           .with_lora(config=LoRAConfig(rank=args.rank))
           .with_training(rounds=args.rounds, eval_every=0, log_every=5,
                          pretrain_steps=0, train_head=False, verbose=True)
           .with_params(params, cfg)
           .with_data(batch_for_round))
    if args.mesh is not None:
        assert args.engine == "sharded", "--mesh needs --engine sharded"
        c, m = (int(x) for x in args.mesh.lower().split("x"))
        assert c * m <= len(jax.devices()), \
            f"mesh {c}x{m} needs {c * m} devices, have {len(jax.devices())}"
        exp.with_mesh((c, m), fsdp=args.fsdp,
                      rounds_per_call=args.rounds_per_call)
        print(f"[train] mesh data={c} model={m} fsdp={args.fsdp}")
    else:
        assert not args.fsdp, "--fsdp needs --mesh"
        exp.with_engine(args.engine, **engine_kw)
    with (jax.profiler.trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        res = exp.run()

    led = res.ledger
    n, r = fed.n_clients, max(led.rounds, 1)
    dense = max(led.dense_equivalent_bytes(n), 1)
    print(f"[train] done after {led.rounds} rounds; "
          f"final loss={res.history[-1]['loss']:.4f}")
    print(f"[train] traffic: total {led.total_bytes/1e6:.2f}MB "
          f"({led.total_bytes/dense:.2%} of dense) | "
          f"coded wire format {led.total_coded_bytes/1e6:.2f}MB "
          f"(down {led.down_coded_bytes/1e6:.2f} / up {led.up_coded_bytes/1e6:.2f})")
    print(f"[train] per client per round: "
          f"down {led.down_bytes/(r*n)/1e3:.1f}kB "
          f"({led.down_values/(r*n):.0f} values), "
          f"up {led.up_bytes/(r*n)/1e3:.1f}kB "
          f"({led.up_values/(r*n):.0f} values)")


if __name__ == "__main__":
    main()

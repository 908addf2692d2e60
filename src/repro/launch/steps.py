"""Step builders + abstract input specs for the multi-pod dry-run.

Three lowered objects per architecture:

  train_step  — ONE FLASC federated round (Algorithm 1): per-client local
                SGD over LoRA under vmap (clients sharded over data/pod),
                Top-K download/upload masking, FedAdam server update.
  prefill_step — full-sequence forward returning logits + KV cache.
  decode_step  — one-token serve step against a seq-sharded KV cache.

`input_specs` produces ShapeDtypeStructs (never allocates) and
`input_shardings` produces the matching NamedSharding pytrees for
jit(in_shardings=...).lower().
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from repro.core import fedround
from repro.core import strategies as st
from repro.models import lora as lora_mod
from repro.models import model as mdl
from repro.models.config import (FederatedConfig, InputShape, LoRAConfig,
                                 ModelConfig)
from repro.models.layers import P, spec_to_shape_dtype
from repro.launch.shardings import (DEFAULT_RULES, fsdp_rules,
                                    logical_to_pspec, spec_tree_shardings)


def fed_for_mesh(mesh, shape: InputShape) -> FederatedConfig:
    """Clients fill the data(+pod) axes; local batch makes up the rest."""
    data_size = int(np.prod([mesh.shape[a] for a in mesh.shape if a != "model"]))
    n_clients = min(data_size, shape.global_batch)
    return FederatedConfig(n_clients=n_clients,
                           local_batch=max(shape.global_batch // n_clients, 1))


# ---------------------------------------------------------------------------
# abstract inputs
# ---------------------------------------------------------------------------

def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def batch_specs(cfg: ModelConfig, lead: Tuple[int, ...], seq: int):
    """Model input dict specs with leading dims `lead` (e.g. (n, steps, bs))."""
    b = {"tokens": P(lead + (seq,), (None,) * len(lead) + (None,), dtype="int32")}
    # leading axis is the client/batch axis -> shard over data(+pod)
    axes0 = ("clients",) + (None,) * (len(lead) - 1)
    b["tokens"] = P(lead + (seq,), axes0 + (None,), dtype="int32")
    if cfg.encoder_decoder:
        b["frames"] = P(lead + (cfg.encoder_seq, cfg.d_model), axes0 + (None, None),
                        dtype=cfg.param_dtype)
    if cfg.num_image_tokens > 0:
        b["image_embeds"] = P(lead + (cfg.num_image_tokens, cfg.vision_embed_dim),
                              axes0 + (None, None), dtype=cfg.param_dtype)
    return b


def train_inputs(cfg: ModelConfig, lcfg: LoRAConfig, fed: FederatedConfig,
                 shape: InputShape):
    """Spec trees (P) for (params, flatP, server, sstate, batches, rng)."""
    pspec = mdl.model_spec(cfg)
    lspec = lora_mod.lora_spec(cfg, lcfg)
    p_len = sum(int(np.prod(p.shape)) for p in
                jax.tree.leaves(lspec, is_leaf=lambda x: isinstance(x, P)))
    flat = P((p_len,), (None,), dtype="float32")
    server = {"opt": {"m": flat, "v": flat, "count": P((), (), dtype="int32")},
              "round": P((), (), dtype="int32")}
    batches = batch_specs(cfg, (fed.n_clients, fed.local_steps, fed.local_batch),
                          shape.seq_len)
    return {"params": pspec, "flatP": flat, "server": server, "sstate": {},
            "batches": batches}


def prefill_inputs(cfg: ModelConfig, lcfg: Optional[LoRAConfig],
                   shape: InputShape):
    pspec = mdl.model_spec(cfg)
    lspec = lora_mod.lora_spec(cfg, lcfg) if lcfg else {}
    batch = batch_specs(cfg, (shape.global_batch,), shape.seq_len)
    return {"params": pspec, "lora": lspec, "batch": batch}


def decode_inputs(cfg: ModelConfig, lcfg: Optional[LoRAConfig],
                  shape: InputShape, window: Optional[int] = None):
    pspec = mdl.model_spec(cfg)
    lspec = lora_mod.lora_spec(cfg, lcfg) if lcfg else {}
    cache = mdl.cache_spec(cfg, shape.global_batch, shape.seq_len, window)
    token = P((shape.global_batch,), ("batch",), dtype="int32")
    pos = P((), (), dtype="int32")
    return {"params": pspec, "lora": lspec, "token": token, "pos": pos,
            "cache": cache}


def specs_to_abstract(spec_tree):
    return spec_to_shape_dtype(spec_tree)


def specs_to_shardings(spec_tree, mesh):
    return spec_tree_shardings(spec_tree, mesh)


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def build_train_step(cfg: ModelConfig, lcfg: LoRAConfig, fed: FederatedConfig,
                     strategy: st.StrategyLike, meta: fedround.FlatMeta,
                     window=None, spmd_axis_name=None):
    """-> train_step(params, flatP, server, sstate, batches, rng) — the
    same params-as-leading-argument shape the engine layer runs
    (`fedround.make_round_fn`), so the dry-run lowers exactly the program
    the ShardedEngine executes."""

    def loss_of(params, lora_tree, mb):
        return mdl.loss_fn(params, cfg, mb, lora=lora_tree,
                           lora_scale=lcfg.scale, window=window)

    return fedround.make_round_fn(loss_of, meta, fed, st.resolve(strategy),
                                  spmd_axis_name=spmd_axis_name)


def train_spmd_axes(mesh):
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


# activation rules for the federated train step: the vmapped client axis
# carries the data/pod sharding, so per-client batch dims stay local.
TRAIN_RULES = dict(DEFAULT_RULES, batch=())

# the FSDP overlay on the train rules: backbone weight storage dims
# (`embed`) shard over the data/pod axes too (ZeRO-3) — what
# `ShardedEngine(fsdp=True)` and `launch.train --fsdp` apply to the
# params step argument (docs/engines.md "Sharded backbone params").
TRAIN_FSDP_RULES = fsdp_rules(TRAIN_RULES)


def abstract_flat_meta(cfg: ModelConfig, lcfg: LoRAConfig) -> fedround.FlatMeta:
    """FlatMeta built from specs without allocating LoRA params."""
    lspec = lora_mod.lora_spec(cfg, lcfg)
    abstract = spec_to_shape_dtype(lspec)
    return fedround.FlatMeta.of(abstract, with_rank_map=False)


def build_prefill_step(cfg: ModelConfig, lcfg: Optional[LoRAConfig], window=None):
    scale = lcfg.scale if lcfg else 1.0

    def prefill_step(params, lora, batch):
        return mdl.prefill(params, cfg, batch, lora=lora or None,
                           lora_scale=scale, window=window)
    return prefill_step


def build_decode_step(cfg: ModelConfig, lcfg: Optional[LoRAConfig], window=None):
    scale = lcfg.scale if lcfg else 1.0

    def decode_step(params, lora, token, pos, cache):
        return mdl.decode_step(params, cfg, token, pos, cache,
                               lora=lora or None, lora_scale=scale,
                               window=window)
    return decode_step

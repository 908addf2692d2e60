"""Glue between the benchmark's configuration files and the system under
test: its model and adapter configs for the reference's sizes."""
from __future__ import annotations

import jax

from repro.models import model as mdl
from repro.models.config import LoRAConfig, ModelConfig
from repro.models.layers import spec_to_shape_dtype


def model_config(name: str, d: dict) -> ModelConfig:
    """The system's ModelConfig for the reference's sizes `d`."""
    return ModelConfig(
        name=name, family="dense", num_layers=d["layers"],
        d_model=d["d_model"], num_heads=d["heads"],
        num_kv_heads=d["kv_heads"], head_dim=d["head_dim"],
        d_ff=d["d_ff"], vocab_size=d["vocab"], activation=d["activation"],
        rope_theta=d["rope_theta"], norm_eps=d["eps"],
        tie_embeddings=d["tied"], use_learned_pos=d["learned_pos"],
        max_seq=d["max_pos"] if d["learned_pos"] else 0,
        param_dtype="bfloat16", compute_dtype="bfloat16")


def lora_config(cfg: dict) -> LoRAConfig:
    lo = cfg["lora"]
    return LoRAConfig(rank=int(lo["rank"]), alpha=float(lo["alpha"]),
                      targets=tuple(lo["targets"]), dtype=lo["dtype"])


def check_layout(params, mcfg: ModelConfig) -> None:
    """The generated backbone has exactly the system's leaves and shapes."""
    want = jax.tree.map(lambda s: (s.shape, str(s.dtype)),
                        spec_to_shape_dtype(mdl.model_spec(mcfg)))
    got = jax.tree.map(lambda x: (x.shape, str(x.dtype)), params)
    if want != got:
        raise ValueError(f"backbone layout {got} is not the system's {want}")

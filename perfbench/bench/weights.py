"""Seeded weights and adapters, made on the device in one jitted call.

The same key gives the same arrays.  The backbone comes in bf16, the type
it is served and fine-tuned in, in the nested layout the system under test
takes (`groups/g0/...`, layers stacked on the leading axis); the plain
reference reads the same layout.  Matrices are N(0, 1/fan_in), the token
and position tables N(0, 0.02^2), norm weights 1 + N(0, 0.05^2).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np


def _leaf(key, shape, init):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "table":
        z = z * 0.02
    elif init == "norm":
        z = 1.0 + 0.05 * z
    else:
        z = z / math.sqrt(shape[-2])
    return z.astype(jnp.bfloat16)


def backbone(spec, key) -> Dict:
    """The whole backbone from `key`, in one device call.  `spec` lists
    (path, shape, init) per leaf, as a reference's `backbone_shapes` gives
    it."""

    @jax.jit
    def make(key):
        tree: Dict = {}
        for i, (path, shape, init) in enumerate(spec):
            node = tree
            for p in path[:-1]:
                node = node.setdefault(p, {})
            node[path[-1]] = _leaf(jax.random.fold_in(key, i), shape, init)
        return tree

    return make(key)


def tenant_adapters(layout, key, tenants: int, b_std: float):
    """(tenants, n) float32 flat adapters in `layout` order (a reference's
    `lora_layout`): a ~ N(0, 1/d_in), b ~ N(0, b_std^2)."""

    @jax.jit
    def make(key):
        parts = []
        for i, (_, ab, s) in enumerate(layout):
            z = jax.random.normal(jax.random.fold_in(key, i),
                                  (tenants,) + s, jnp.float32)
            z = z / math.sqrt(s[-2]) if ab == "a" else z * b_std
            parts.append(z.reshape(tenants, -1))
        return jnp.concatenate(parts, axis=1)

    return make(key)


def adapter_tree(flat: np.ndarray, layout) -> Dict:
    """One flat adapter as the system's LoRA tree: g0/attn/<t>/{a,b}."""
    attn: Dict = {}
    off = 0
    for t, ab, s in layout:
        n = math.prod(s)
        attn.setdefault(t, {})[ab] = np.asarray(flat[off:off + n]).reshape(s)
        off += n
    return {"g0": {"attn": attn}}

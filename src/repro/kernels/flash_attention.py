"""Pallas TPU kernel: flash attention (online-softmax tiling).

TPU-native tiling for the 32k-prefill hot spot: grid (B*H, S/bq); each
program streams KV blocks of `bkv` rows from the head's K/V panels through
VMEM, maintaining running (max, sumexp, acc) in f32.  Causal masking skips
nothing structurally (Pallas grid is static) but masked blocks contribute
zero — block-level skipping is a recorded hillclimb follow-up.

Oracle: kernels/ref.py::flash_attention_ref (and the pure-jnp
models/attention.py::chunked_attention used by the model itself).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq, bkv, T, scale, causal):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (bq, hd)
    nkv = T // bkv

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * bkv, bkv), :]
        v = v_ref[0, pl.ds(j * bkv, bkv), :]
        k = k.astype(jnp.float32)
        v = v.astype(jnp.float32)
        s = q @ k.T                                     # (bq, bkv)
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 0)
            k_pos = j * bkv + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=1)
        acc_new = acc * corr[:, None] + p @ v
        return m_new, l_new, acc_new

    hd_v = v_ref.shape[-1]
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    a0 = jnp.zeros((bq, hd_v), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nkv, body, (m0, l0, a0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, bq=128, bkv=128, causal=True,
                           interpret: bool = False):
    """q (B,S,H,hd); k,v (B,T,H,hd) (kv heads pre-broadcast to H).
    Returns (B,S,H,hd_v)."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    hd_v = v.shape[-1]
    bq = min(bq, S)
    bkv = min(bkv, T)
    assert S % bq == 0 and T % bkv == 0
    scale = 1.0 / math.sqrt(hd)

    qf = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, T, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, T, hd_v)

    out = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bkv=bkv, T=T, scale=scale,
                          causal=causal),
        grid=(B * H, S // bq),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda h, i: (h, i, 0)),
            pl.BlockSpec((1, T, hd), lambda h, i: (h, 0, 0)),
            pl.BlockSpec((1, T, hd_v), lambda h, i: (h, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, hd_v), lambda h, i: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd_v), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, hd_v).transpose(0, 2, 1, 3)

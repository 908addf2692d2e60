"""Ahead-of-time compiles of the main-path Pallas kernels for a described
TPU v5e chip, at GPT-2 Small's sizes (and Yi-9B's for the bin counts).

Interpret mode accepts block shapes and in-kernel ops that the TPU
compiler refuses (sub-(8, 128) blocks, rank-1 scalar blocks, scatters).
These tests lower each kernel natively for a `v5e:2x2` topology that is
described, not attached, so a kernel the chip would refuse fails here:

  * the upload transform at the flat LoRA length of GPT-2 Small, rank 16
    on wq/wk/wv/wo, for a cohort of 8 clients: `absmax_pallas`,
    `bin_counts_pallas`, `fused_mask_quantize_pallas` and
    `topk_mask_pallas` vmapped over the clients (the fused selector),
    `fused_mask_quantize_pack_pallas` and `threshold_count_pallas`;
    `absmax_pallas` and `bin_counts_pallas` also at the flat length of
    Yi-9B cut to 8 layers (3,276,800 entries);
  * `pack_values_batched_pallas` at `comm.pack_capacity` for density 0.25
    (the sparse aggregation pack);
  * `grouped_pallas` at K = 768, R = 16 and every N of the model.

Nothing here runs a kernel: results are pinned by the interpret-mode
differential tests.  The topology is described inside a fixture, so only
the worker that runs this file loads the TPU compiler.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.paper_models import GPT2_SMALL
from repro.configs.yi_9b import CONFIG as YI_9B
from repro.core import comm
from repro.core import sparsity as sp
from repro.kernels import fused_transport as ft
from repro.kernels import topk_mask as tk
from repro.kernels.lora_matmul import PallasGroupedKernel
from repro.launch.steps import abstract_flat_meta
from repro.models.config import LoRAConfig

CLIENTS = 8
DENSITY = 0.25


@pytest.fixture(scope="module")
def chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-chip compile can be written to the persistent cache but
    # never read back without a chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def sizes():
    n = abstract_flat_meta(GPT2_SMALL, LoRAConfig(rank=16)).p_len
    return {"n": n, "n_pad": -(-n // tk.BLOCK) * tk.BLOCK,
            "cap": comm.pack_capacity(n, sp.density_count(n, DENSITY))}


def _compile(chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


F32 = jnp.float32


def test_gpt2_small_flat_length(sizes):
    assert sizes["n"] == 12 * 4 * 2 * 768 * 16


def _yi9b_8l_flat_length():
    cfg = dataclasses.replace(YI_9B, num_layers=8)
    return abstract_flat_meta(cfg, LoRAConfig(rank=16)).p_len


@pytest.mark.parametrize("model", ["gpt2-small", "yi-9b-8l"])
def test_absmax_and_bin_counts(chip, sizes, model):
    b = CLIENTS
    if model == "gpt2-small":
        n = sizes["n_pad"]
    else:                       # 50 whole blocks: no padding
        n = _yi9b_8l_flat_length()
        assert n == 3_276_800 and n % tk.BLOCK == 0

    def fn(x):
        hi0 = jax.vmap(ft.absmax_pallas)(x)
        return jax.vmap(ft.bin_counts_pallas)(x, hi0)

    _compile(chip, fn, ((b, n), F32))


@pytest.mark.parametrize("stochastic", [False, True])
def test_fused_mask_quantize(chip, sizes, stochastic):
    b, n = CLIENTS, sizes["n_pad"]

    def fn(x, t, s, u):
        return jax.vmap(functools.partial(
            ft.fused_mask_quantize_pallas, bits=4))(
                x, t, s, u if stochastic else None)

    _compile(chip, fn, ((b, n), F32), ((b,), F32), ((b,), F32), ((b, n), F32))


def test_fused_mask_quantize_pack(chip, sizes):
    n, cap = sizes["n_pad"], sizes["cap"]

    def fn(x, t, s, u):
        return ft.fused_mask_quantize_pack_pallas(x, t, s, u, 4, cap,
                                                  sizes["n"])

    _compile(chip, fn, ((n,), F32), ((), F32), ((), F32), ((n,), F32))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_mask_and_count(chip, sizes, dtype):
    b, n = CLIENTS, sizes["n_pad"]

    def fn(x, t):
        return (jax.vmap(tk.topk_mask_pallas)(x, t),
                jax.vmap(tk.threshold_count_pallas)(x, t))

    _compile(chip, fn, ((b, n), dtype), ((b,), F32))


def test_pack_values_batched(chip, sizes):
    cap = sizes["cap"]
    _compile(chip, lambda x: ft.pack_values_batched_pallas(x, cap),
             ((CLIENTS, sizes["n_pad"]), F32))


@pytest.mark.parametrize("N", [768, 2304, 3072])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_pallas(chip, N, dtype):
    K, R, G, M = 768, 16, 4, 8
    kern = PallasGroupedKernel(interpret=False)
    _compile(chip, lambda x, a, b, g: kern.delta(x, a, b, g, 2.0),
             ((M, K), dtype), ((G, K, R), dtype), ((G, R, N), dtype),
             ((M,), jnp.int32))

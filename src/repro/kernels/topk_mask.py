"""Pallas TPU kernel: magnitude-threshold masking (FLASC's sparsifier).

The FLASC hot spot is `x * (|x| >= t)` over the flattened adapter vector
(tens of millions of entries, every round, on download and per-client
upload).  On TPU this is a pure VPU streaming op: the vector is viewed
as (rows, 128) tiles, a block of rows sits in VMEM and the scalar
threshold in SMEM, and each block writes its masked tile plus a (1, 128)
partial count.  The fused count output feeds the histogram
threshold-refinement loop so the bisection never re-reads the vector
from HBM more than once per iteration.

These kernels are the production path behind the `pallas` selector
(`core/selectors.py`): `threshold_count_pallas` is the per-iteration
bisection pass, `topk_mask_pallas` materializes the final mask + nnz in
one go.  The selector layer owns padding to the block multiple, backend
dispatch (interpret mode off-TPU), and the keep-count contract; callers
should go through it rather than invoking these raw kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 64 * 1024  # 256 KiB f32 per block — comfortably inside VMEM
LANES = 128        # a flat stream is viewed as (rows, LANES) tiles


def tiles(x: jax.Array) -> jax.Array:
    """(..., n) stream -> (..., n // LANES, LANES) tile view, n % LANES == 0."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // LANES, LANES))


def stream_spec(block: int) -> pl.BlockSpec:
    """One (block // LANES, LANES) tile of a `tiles` view per grid step."""
    return pl.BlockSpec((block // LANES, LANES), lambda i: (i, 0))


def partial_spec() -> pl.BlockSpec:
    """Per-block (1, LANES) partial reduction of a (nblocks, 1, LANES)
    output; the caller finishes the (order-free) reduction."""
    return pl.BlockSpec((None, 1, LANES), lambda i: (i, 0, 0))


def scalars(*vals) -> jax.Array:
    """Pack kernel scalars as one (1, k) f32 SMEM operand.  The 2-D shape
    keeps the spec valid when vmap adds a leading batch axis."""
    return jnp.stack([jnp.asarray(v, jnp.float32) for v in vals])[None]


SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def check_stream(n: int, block: int):
    """A stream of n entries cuts into whole blocks of whole tile rows."""
    assert block % LANES == 0 and n % block == 0, (n, block)


def _mask_kernel(thr_ref, x_ref, out_ref, cnt_ref):
    x = x_ref[...]
    keep = jnp.abs(x.astype(jnp.float32)) >= thr_ref[0, 0]
    out_ref[...] = jnp.where(keep, x, jnp.zeros_like(x))
    cnt_ref[...] = jnp.sum(keep.astype(jnp.int32), axis=0, keepdims=True)


def topk_mask_pallas(x: jax.Array, threshold: jax.Array, *,
                     block: int = BLOCK, interpret: bool = False):
    """x (n,) with n % block == 0 (pad upstream). threshold scalar.
    Returns (masked x, kept count)."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    # compare in x's dtype, as the reference does: the cast back to f32
    # for SMEM is exact
    thr = scalars(threshold.astype(x.dtype))
    masked, counts = pl.pallas_call(
        _mask_kernel,
        grid=grid,
        in_specs=[SMEM, stream_spec(block)],
        out_specs=[stream_spec(block), partial_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((n // LANES, LANES), x.dtype),
            jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(thr, tiles(x))
    return masked.reshape(n), jnp.sum(counts)


def _count_kernel(thr_ref, x_ref, cnt_ref):
    keep = jnp.abs(x_ref[...].astype(jnp.float32)) >= thr_ref[0, 0]
    cnt_ref[...] = jnp.sum(keep.astype(jnp.int32), axis=0, keepdims=True)


def threshold_count_pallas(x: jax.Array, threshold: jax.Array, *,
                           block: int = BLOCK, interpret: bool = False):
    """Count of |x| >= threshold (one streaming pass)."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    counts = pl.pallas_call(
        _count_kernel,
        grid=grid,
        in_specs=[SMEM, stream_spec(block)],
        out_specs=partial_spec(),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
        interpret=interpret,
    )(scalars(threshold.astype(x.dtype)), tiles(x))
    return jnp.sum(counts)

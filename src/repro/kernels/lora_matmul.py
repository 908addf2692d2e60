"""Pallas TPU kernels: fused LoRA matmul + the grouped multi-adapter delta.

Two serving/training hot paths live here:

* `lora_matmul_pallas` — single-adapter fused  y = x @ W + scale*(x@a)@b.
  MXU tiling: grid (M/bm, N/bn, K/bk) with an f32 VMEM accumulator; the
  low-rank path (xa @ b, rank r padded to the 128 lane width) is added in
  the K-epilogue so the LoRA contribution costs one extra (bm, r) x (r, bn)
  MXU pass per output tile instead of a separate kernel launch + HBM
  round-trip for the xW result.  `xa = x @ a` (M x r, tiny) is computed
  outside and passed in.

* the **grouped-kernel registry** — the multi-tenant serving path
  (punica / S-LoRA-style BGMV): one batch whose rows belong to *different*
  clients' adapters, applied in a single fused gather+matmul.  A
  `GroupedLoraKernel` computes  delta[m] = scale * (x[m] @ a[g[m]]) @ b[g[m]]
  for a page pool a (G, K, R), b (G, R, N) and per-row page indices
  g (M,).  Implementations register behind `@register_grouped_kernel`
  (the `core.selectors` registry idiom):

    - ``grouped_ref``    — per-row reference loop (`lax.map`).  The
      bit-exact semantics the serving tests freeze.
    - ``grouped_gather`` — batched `jnp.take` + einsum, pure jnp.  The
      CPU/GPU production path (XLA batches the row matmuls).
    - ``grouped_pallas`` — scalar-prefetch Pallas kernel: the page
      indices arrive as a `PrefetchScalarGridSpec` scalar operand so each
      row's (K, R)/(R, bn) pages are gathered by the BlockSpec index maps
      while the row is multiplied — one fused pass, no (M, K, R) gather
      materialized in HBM.  Bit-identical to ``grouped_ref`` by
      construction (same two-dot f32 contraction per row); off TPU it
      runs under Pallas interpret mode automatically.

`models.layers.linear` dispatches here whenever a LoRA dict carries a
`gidx` leaf (see `serving.cache.paged_lora`), so the whole model stack —
attention, MLP, SSM projections — serves mixed-adapter batches without
threading any new argument.  See docs/serving.md.
"""
from __future__ import annotations

import functools
from typing import ClassVar, Dict, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, xa_ref, b_ref, scale_ref, o_ref, acc_ref, *, nk):
    k_idx = pl.program_id(2)

    @pl.when(k_idx == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(k_idx == nk - 1)
    def _epilogue():
        lora = jnp.dot(xa_ref[...], b_ref[...],
                       preferred_element_type=jnp.float32)
        o_ref[...] = (acc_ref[...] + scale_ref[0] * lora).astype(o_ref.dtype)


def lora_matmul_pallas(x, w, a, b, scale: float, *, bm=128, bn=128, bk=512,
                       interpret: bool = False):
    """x (M,K), w (K,N), a (K,r), b (r,N) -> (M,N). Dims must tile evenly."""
    M, K = x.shape
    N = w.shape[1]
    r = a.shape[1]
    assert M % bm == 0 and N % bn == 0 and K % bk == 0, (M, N, K, bm, bn, bk)
    nk = K // bk
    xa = jnp.dot(x, a, preferred_element_type=jnp.float32).astype(x.dtype)
    scale_arr = jnp.full((1,), scale, jnp.float32)

    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(M // bm, N // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),    # x
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),    # w
            pl.BlockSpec((bm, r), lambda i, j, k: (i, 0)),     # xa
            pl.BlockSpec((r, bn), lambda i, j, k: (0, j)),     # b
            pl.BlockSpec((1,), lambda i, j, k: (0,)),          # scale
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],   # f32 accumulator
        interpret=interpret,
    )(x, w, xa, b, scale_arr)


# ---------------------------------------------------------------------------
# grouped multi-adapter delta (the multi-tenant serving hot path)
# ---------------------------------------------------------------------------

class GroupedLoraKernel:
    """Batched-adapter LoRA delta protocol.

    `delta(x, a, b, gidx, scale)` with x (M, K), page pools a (G, K, R) /
    b (G, R, N), and per-row page indices gidx (M,) int32 returns the
    (M, N) LoRA contribution  scale * (x[m] @ a[g]) @ b[g]  in x.dtype.
    Implementations must be pure jax (jit-safe) and must not reorder the
    per-row contraction: two dots per row, f32 accumulation, scale applied
    to the second product — the contract `grouped_ref` freezes and
    `grouped_pallas` matches bit-for-bit.
    """

    name: ClassVar[str] = "base"

    def delta(self, x: jax.Array, a: jax.Array, b: jax.Array,
              gidx: jax.Array, scale) -> jax.Array:
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}()"


_GROUPED_REGISTRY: Dict[str, Type[GroupedLoraKernel]] = {}
_GROUPED_DEFAULTS: Dict[str, GroupedLoraKernel] = {}


def register_grouped_kernel(name: str):
    """Class decorator: `@register_grouped_kernel("grouped_gather")` makes
    the kernel reachable from every `kernel=` seam in the serving stack."""
    def deco(cls: Type[GroupedLoraKernel]) -> Type[GroupedLoraKernel]:
        assert issubclass(cls, GroupedLoraKernel), cls
        cls.name = name
        _GROUPED_REGISTRY[name] = cls
        return cls
    return deco


def registered_grouped_kernels() -> Tuple[str, ...]:
    return tuple(sorted(_GROUPED_REGISTRY))


GroupedKernelLike = Union[str, GroupedLoraKernel]


def resolve_grouped_kernel(obj: Optional[GroupedKernelLike]
                           ) -> GroupedLoraKernel:
    """Kernel name or instance -> instance; None -> the backend default
    (`grouped_pallas` on TPU, `grouped_gather` everywhere else — the
    interpreter's per-block cost makes the jnp gather path the faster CPU
    production path, mirroring the selector dispatch rules)."""
    if obj is None:
        obj = ("grouped_pallas" if jax.default_backend() == "tpu"
               else "grouped_gather")
    if isinstance(obj, GroupedLoraKernel):
        return obj
    if isinstance(obj, str):
        if obj not in _GROUPED_REGISTRY:
            raise KeyError(f"no grouped kernel registered for {obj!r}; "
                           f"known: {registered_grouped_kernels()}")
        if obj not in _GROUPED_DEFAULTS:
            _GROUPED_DEFAULTS[obj] = _GROUPED_REGISTRY[obj]()
        return _GROUPED_DEFAULTS[obj]
    raise TypeError(f"cannot resolve {obj!r} to a GroupedLoraKernel")


def grouped_lora_delta(x, a, b, gidx, scale,
                       kernel: Optional[GroupedKernelLike] = None):
    """Dispatch helper: x (..., K) with gidx broadcastable to the leading
    dims (one adapter per row; a (B,)-shaped gidx serves a (B, S, K)
    prefill batch with one adapter per sequence).  Returns (..., N) in
    a.dtype (callers cast back, like the single-adapter path in
    `models.layers.linear`)."""
    kern = resolve_grouped_kernel(kernel)
    lead = x.shape[:-1]
    gidx = jnp.asarray(gidx, jnp.int32)
    g = jnp.broadcast_to(
        gidx.reshape(gidx.shape + (1,) * (len(lead) - gidx.ndim)), lead)
    x2 = x.reshape(-1, x.shape[-1]).astype(a.dtype)
    out = kern.delta(x2, a, b, g.reshape(-1), scale)
    return out.reshape(lead + (b.shape[-1],))


@register_grouped_kernel("grouped_ref")
class RefGroupedKernel(GroupedLoraKernel):
    """Per-row reference loop — the bit-exact semantics.  One `lax.map`
    step per row: xa = x_m @ a[g_m] (f32), delta = scale * (xa @ b[g_m])."""

    def delta(self, x, a, b, gidx, scale):
        scale = jnp.asarray(scale, jnp.float32)

        def row(args):
            xr, g = args
            xa = jnp.dot(xr[None], a[g], preferred_element_type=jnp.float32)
            y = scale * jnp.dot(xa, b[g], preferred_element_type=jnp.float32)
            return y[0].astype(x.dtype)

        return jax.lax.map(row, (x, gidx))


@register_grouped_kernel("grouped_gather")
class GatherGroupedKernel(GroupedLoraKernel):
    """Batched gather + einsum, pure jnp.  XLA turns the per-row matmuls
    into one batched contraction; the (M, K, R) gather is materialized,
    which is fine at serving batch sizes (M = lanes)."""

    def delta(self, x, a, b, gidx, scale):
        ag = jnp.take(a, gidx, axis=0)              # (M, K, R)
        bg = jnp.take(b, gidx, axis=0)              # (M, R, N)
        xa = jnp.einsum("mk,mkr->mr", x, ag,
                        preferred_element_type=jnp.float32)
        y = jnp.asarray(scale, jnp.float32) * jnp.einsum(
            "mr,mrn->mn", xa, bg, preferred_element_type=jnp.float32)
        return y.astype(x.dtype)


def _grouped_kernel(gidx_ref, x_ref, a_ref, b_ref, scale_ref, o_ref):
    # one (tm, K) tile of rows; each row gathers its own (K, R) / (R, bn)
    # page out of the VMEM-resident pools by its prefetched page index
    i = pl.program_id(0)
    tm = x_ref.shape[0]
    for m in range(tm):                     # static: tm is 8 or 16
        g = gidx_ref[i * tm + m]
        xa = jnp.dot(x_ref[m:m + 1, :], a_ref[g],
                     preferred_element_type=jnp.float32)
        o_ref[m:m + 1, :] = (scale_ref[0, 0] * jnp.dot(
            xa, b_ref[g], preferred_element_type=jnp.float32)
        ).astype(o_ref.dtype)


@register_grouped_kernel("grouped_pallas")
class PallasGroupedKernel(GroupedLoraKernel):
    """Scalar-prefetch fused gather+matmul (the TPU production path).

    The page indices ride `pltpu.PrefetchScalarGridSpec`
    (num_scalar_prefetch=1) into SMEM.  Grid (M/tm, N/bn): each program
    takes a (tm, K) tile of rows (tm = 8 rows of 32-bit, 16 of 16-bit
    data, the sublane tiling) and one bn-wide output tile; the (G, K, R)
    page pool and the (G, R, bn) slice of the b pool sit in VMEM, and
    each row multiplies the pages its prefetched index names — the
    gather never round-trips through HBM.  M and N are zero-padded to
    the tile multiples internally and sliced off again; padding cannot
    perturb the surviving rows or columns, each output row tile is an
    independent per-row (1, K) x (K, R) x (R, bn) product.
    `interpret=None` auto-detects: native on TPU, Pallas interpret mode
    everywhere else — results are bit-identical to ``grouped_ref``
    either way.
    """

    def __init__(self, bn: int = 128, interpret: Optional[bool] = None):
        self.bn = bn
        self.interpret = interpret

    def _interpret(self) -> bool:
        if self.interpret is None:
            return jax.default_backend() != "tpu"
        return bool(self.interpret)

    def delta(self, x, a, b, gidx, scale):
        M, K = x.shape
        G, R, N = b.shape
        tm = 8 * max(1, 4 // x.dtype.itemsize)
        bn = min(self.bn, N)
        pad_n = -N % bn
        if pad_n:
            b = jnp.pad(b, ((0, 0), (0, 0), (0, pad_n)))
        pad_m = -M % tm
        if pad_m:
            x = jnp.pad(x, ((0, pad_m), (0, 0)))
            gidx = jnp.pad(gidx, (0, pad_m))
        m_pad, n_pad = M + pad_m, N + pad_n
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(m_pad // tm, n_pad // bn),
            in_specs=[
                pl.BlockSpec((tm, K), lambda i, j, g: (i, 0)),        # x rows
                pl.BlockSpec((G, K, R), lambda i, j, g: (0, 0, 0)),   # a pool
                pl.BlockSpec((G, R, bn), lambda i, j, g: (0, 0, j)),  # b pool
                pl.BlockSpec(memory_space=pltpu.SMEM),              # scale
            ],
            out_specs=pl.BlockSpec((tm, bn), lambda i, j, g: (i, j)),
        )
        out = pl.pallas_call(
            _grouped_kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((m_pad, n_pad), x.dtype),
            interpret=self._interpret(),
        )(gidx, x, a, b, jnp.full((1, 1), scale, jnp.float32))
        return out[:M, :N]

    def __repr__(self):
        return f"PallasGroupedKernel(bn={self.bn}, interpret={self.interpret})"

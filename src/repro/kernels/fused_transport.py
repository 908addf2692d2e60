"""Pallas one-pass transport kernels: fused Top-K -> quantize -> pack.

The transport hot path used to pay ~26 streaming HBM passes per upload:
24 bisection count passes (`threshold_count_pallas` per iteration of
`sparsity.threshold_histogram_count`), one mask pass, and a separate
quantize pass on top.  This module collapses the whole client-side wire
path to **three** streaming passes over the flat delta:

  pass 1  `absmax_pallas`      — per-block max |x|, reduced to `hi0`
                                 (the bisection's initial upper bound
                                 *and* the quantizer scale numerator).
  pass 2  `bin_counts_pallas`  — every element replays the `levels`-step
                                 bisection *path* it would take through
                                 the canonical lo/hi recurrence and emits
                                 a `levels`-bit bin index; the kernel
                                 bincounts the indices per block.  A tiny
                                 suffix-sum replay over the 2^levels-bin
                                 histogram (`threshold_from_bins`) then
                                 yields the threshold — **bit-identical**
                                 to `threshold_histogram_count(iters=
                                 levels)`, because every probe count the
                                 canonical loop would compute is a suffix
                                 sum of the bins, and the lo/hi float
                                 math is replayed op-for-op.
  pass 3  `fused_mask_quantize_pallas` / `..._pack_pallas`
                               — mask at the threshold, quantize the
                                 survivors (same float ops as
                                 `quantization.quantize`), count the
                                 nnz, and (pack variant) write the
                                 coded wire form — ascending indices +
                                 values — into a static-capacity buffer,
                                 all in one kernel.

Why the path replay is exact: the canonical bisection from `(lo, hi) =
(0, max|x|)` visits nodes of a binary tree whose midpoints are fully
determined by the float recurrence `mid = 0.5 * (lo + hi)`.  An element
running the *same* recurrence against its own |x| takes one root-to-leaf
path; its leaf index orders elements by magnitude interval, so the count
`#{|x| >= mid}` at any tree node `(prefix p, depth d)` is exactly the
suffix sum of the histogram from bin `(2p + 1) << (levels - 1 - d)`.
Padding zeros land in bin 0 (never counted by any probe — every probe
index is >= 1) except in the all-zero-vector case, where every probe's
`mid == 0` and the threshold is 0 on every path anyway.

The AsyncEngine's server phase closes the loop without densifying:
`sparse_accumulate` gather-accumulates packed (indices, values) client
rows straight into the (p_len,) pseudo-gradient sum — the CSR-style
scatter-add shape (the synchronous round, whose uploads are already a
dense stack on the device, folds the rows to the same bits) — and
`pack_values` / `unpack_values` are the jnp reference codec the
differential tests pin the kernels against (and the engines' bulk
host-transfer coding).

Backend notes: like `kernels/topk_mask.py`, these kernels run natively on
TPU and under Pallas interpret mode everywhere else (the selector layer
owns that dispatch).  The TPU compiler takes no scatter and no block
below the (8, 128) tiling, so every stream is viewed as (rows, 128)
tiles, scalars (threshold, scale, hi0, the running pack offset) sit in
SMEM, and per-block reductions leave as (1, 128) partials.  The bin
histogram is one MXU product per `COUNT_ROWS` tile rows, laid end to
end along the lanes: one-hots of their high and low bin bits, exact
(0/1 in bf16, at most `COUNT_ROWS * 128` per product in f32, products
summed in int32).  The pack compacts each tile row with a lane-shift
network and writes the rows at the running offset (`_pack_tile`).
tests/test_tpu_compile.py compiles every kernel for a described v5e
chip at GPT-2 Small's sizes.  The single-vector pack variant accumulates
its packed outputs across the sequential grid via `pl.program_id(0)`,
so it must not be vmapped; batched callers (the engines' cohort pack
step) use `pack_values_batch`, whose 2-D-grid kernel gives every batch
row its *own* accumulator block — per-row init at the row's first grid
step — and is bit-identical to `jax.vmap(pack_values)` by construction
(pinned in tests/test_fused_transport.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.topk_mask import (BLOCK, LANES, SMEM, check_stream,
                                     partial_spec, scalars, stream_spec,
                                     tiles)

LEVELS = 12         # default bisection depth: 2^12 magnitude bins (16 KiB)
COUNT_ROWS = 32     # tile rows whose bins one MXU product counts


# ---------------------------------------------------------------------------
# pass 1: absmax
# ---------------------------------------------------------------------------

def _absmax_kernel(x_ref, out_ref):
    out_ref[...] = jnp.max(jnp.abs(x_ref[...]), axis=0, keepdims=True)


def absmax_pallas(x: jax.Array, *, block: int = BLOCK,
                  interpret: bool = False) -> jax.Array:
    """max |x| of a (n,) vector, n % block == 0 (pad upstream).  Bitwise
    equal to `jnp.max(jnp.abs(x))`: max-of-block-maxes is order-free."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    part = pl.pallas_call(
        _absmax_kernel,
        grid=grid,
        in_specs=[stream_spec(block)],
        out_specs=partial_spec(),
        out_shape=jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.float32),
        interpret=interpret,
    )(tiles(x.astype(jnp.float32)))
    return jnp.max(part)


# ---------------------------------------------------------------------------
# pass 2: bisection-path bin counts + the threshold replay
# ---------------------------------------------------------------------------

def _bin_kernel(levels, s_ref, x_ref, hist_ref, idx_ref):
    a = jnp.abs(x_ref[...])
    lo = jnp.zeros_like(a)
    hi = jnp.full_like(a, s_ref[0, 0])
    idx = jnp.zeros(a.shape, jnp.int32)
    for _ in range(levels):                 # static unroll: `levels` is small
        mid = 0.5 * (lo + hi)               # the canonical recurrence,
        up = a >= mid                       # replayed per element
        idx = idx * 2 + up.astype(jnp.int32)
        lo = jnp.where(up, mid, lo)
        hi = jnp.where(up, hi, mid)
    idx_ref[...] = idx
    # bincount without a scatter: bin = (h, l) splits into its high and
    # low bits, and hist[h, l] = sum over elements of onehot_h * onehot_l.
    # `r` tile rows are laid end to end along the lanes, and one
    # (nh, r*128) x (nl, r*128)^T MXU product counts them all; the
    # products do not wait on each other, only their int32 sum is carried.
    # Exact: the one-hots are 0/1 in bf16, a product's counts (<= r*128)
    # sit below 2^24 in f32, and the products add up in int32.
    nh, nl = hist_ref.shape
    lo_bits = nl.bit_length() - 1
    rows = a.shape[0]
    r = math.gcd(rows, COUNT_ROWS)      # interpret blocks: any row count
    hs = jax.lax.broadcasted_iota(jnp.int32, (nh, r * LANES), 0)
    ls = jax.lax.broadcasted_iota(jnp.int32, (nl, r * LANES), 0)

    def count(i, acc):
        v = idx_ref[pl.ds(pl.multiple_of(i * r, r), r), :].reshape(1, -1)
        oh = jnp.where((v >> lo_bits) == hs, 1.0, 0.0).astype(jnp.bfloat16)
        ol = jnp.where((v & (nl - 1)) == ls, 1.0, 0.0).astype(jnp.bfloat16)
        return acc + jax.lax.dot_general(
            oh, ol, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)

    hist_ref[...] = jax.lax.fori_loop(0, rows // r, count,
                                      jnp.zeros((nh, nl), jnp.int32))


def bin_counts_pallas(x: jax.Array, hi0: jax.Array, levels: int = LEVELS,
                      *, block: int = BLOCK,
                      interpret: bool = False) -> jax.Array:
    """(2^levels,) int32 histogram of bisection-path bin indices for a
    (n,) vector with n % block == 0.  `hi0` is the absmax from pass 1."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    nl = 1 << (levels // 2)
    nh = 1 << (levels - levels // 2)
    hist = pl.pallas_call(
        functools.partial(_bin_kernel, levels),
        grid=grid,
        in_specs=[SMEM, stream_spec(block)],
        out_specs=pl.BlockSpec((None, nh, nl), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((grid[0], nh, nl), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block // LANES, LANES), jnp.int32)],
        interpret=interpret,
    )(scalars(hi0), tiles(x.astype(jnp.float32)))
    return jnp.sum(hist, axis=0).reshape(nh * nl)


def threshold_from_bins(hist: jax.Array, hi0: jax.Array, k,
                        levels: int = LEVELS) -> jax.Array:
    """Replay the canonical bisection over the bin histogram.

    Carries (lo, hi, node prefix); each step's probe count is the suffix
    sum of bins >= `(2p + 1) << (levels - 1 - d)` — exactly the count
    `sparsity.threshold_histogram_count` would get from a streaming pass —
    and the lo/hi updates are the same float ops, so the returned
    threshold is bit-identical to `threshold_histogram_count(|x|, k,
    iters=levels)`.  `k` must already honor `clamp_count`.
    """
    assert hist.shape[-1] == 1 << levels, (hist.shape, levels)
    # suffix[i] = #{elements with bin index >= i}
    suffix = jnp.cumsum(hist[::-1])[::-1]
    k = jnp.asarray(k, jnp.int32)
    hi0 = hi0.astype(jnp.float32)

    def body(d, carry):
        lo, hi, p = carry
        mid = 0.5 * (lo + hi)
        probe = (2 * p + 1) << (levels - 1 - d)
        cnt = suffix[probe]
        up = cnt > k                        # too many kept -> raise threshold
        lo = jnp.where(up, mid, lo)
        hi = jnp.where(up, hi, mid)
        return lo, hi, 2 * p + up.astype(jnp.int32)

    lo, _, _ = jax.lax.fori_loop(
        0, levels, body,
        (jnp.zeros_like(hi0), hi0, jnp.zeros((), jnp.int32)))
    return lo


# ---------------------------------------------------------------------------
# pass 3: fused mask + quantize (+ pack)
# ---------------------------------------------------------------------------

def _quantized(x, u, bits: int, stochastic: bool, scale):
    """The same float ops as `quantization.quantize` on the survivors:
    y = x / scale, stochastic floor(y + u) or round(y), clip, rescale."""
    qmax = float(2 ** (bits - 1) - 1)
    y = x / scale
    y = jnp.floor(y + u) if stochastic else jnp.round(y)
    return jnp.clip(y, -qmax - 1.0, qmax) * scale


def _mask_quantize(bits, stochastic, s_ref, x_ref, u_ref):
    """(kept mask, masked+quantized tile) at threshold s[0], scale s[1]."""
    x = x_ref[...]
    keep = jnp.abs(x) >= s_ref[0, 0]
    q = _quantized(x, u_ref[...], bits, stochastic, s_ref[0, 1]) \
        if bits else x
    return keep, jnp.where(keep, q, jnp.zeros_like(q))


def _fuse_kernel(bits, stochastic, s_ref, x_ref, u_ref, out_ref, cnt_ref):
    keep, out = _mask_quantize(bits, stochastic, s_ref, x_ref, u_ref)
    out_ref[...] = out
    cnt_ref[...] = jnp.sum(keep.astype(jnp.int32), axis=0, keepdims=True)


def fused_mask_quantize_pallas(x: jax.Array, threshold: jax.Array,
                               scale: jax.Array, u, bits: int, *,
                               block: int = BLOCK, interpret: bool = False):
    """Mask at `threshold`, quantize survivors at `scale`, count — one
    streaming pass.  x (n,), n % block == 0.  `u` is the (n,)-shaped
    stochastic-rounding uniform draw (None = round-to-nearest); drawn by
    the caller at the *unpadded* shape so the randomness matches
    `quantization.quantize` bit-for-bit, then padded.  bits == 0 skips
    quantization (plain mask + count).  vmap-safe."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    stochastic = u is not None
    uu = x if u is None else u              # placeholder keeps specs static
    masked, counts = pl.pallas_call(
        functools.partial(_fuse_kernel, bits, stochastic),
        grid=grid,
        in_specs=[SMEM, stream_spec(block), stream_spec(block)],
        out_specs=[stream_spec(block), partial_spec()],
        out_shape=[
            jax.ShapeDtypeStruct((n // LANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
        ],
        interpret=interpret,
    )(scalars(threshold, scale), tiles(x.astype(jnp.float32)),
      tiles(uu.astype(jnp.float32)))
    return masked.reshape(n), jnp.sum(counts)


# ---------------------------------------------------------------------------
# in-kernel packing without a scatter
# ---------------------------------------------------------------------------

def _lane_shift_left(x, s):
    """x[:, j + s] at lane j (lanes past the end wrap; callers mask)."""
    return pltpu.roll(x, LANES - s, 1)


def _pack_tile(keep, vals, base, off, cval_ref, cidx_ref, ccnt_ref,
               idx_acc, val_acc):
    """Append the kept entries of one (rows, LANES) tile, in row-major
    order, to the packed accumulators at slot `off`; returns the new
    offset.  Mosaic has no scatter, so the pack runs in two steps:

    1. every row is compacted in place by a shift network: a kept entry
       moves left by the number of dropped entries before it in its row,
       one power of two per stage.  Shift distances never decrease
       along a row, so applying their bits from the lowest up never
       lands two entries on one lane;
    2. each compacted row is rotated to the running slot offset and
       written into (at most) two accumulator rows under a lane mask.

    Entries and indices only move and are selected, so values are
    bit-exact.  Slots at or past the accumulator's last full row are
    dropped; the caller slices the `cap` slots it owns."""
    rows = keep.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, keep.shape, 1)
    src = base + jax.lax.broadcasted_iota(jnp.int32, keep.shape, 0) * LANES \
        + lane
    alive = keep.astype(jnp.int32)
    pre = alive                             # inclusive prefix count per row
    for k in range(LANES.bit_length() - 1):
        s = 1 << k
        pre = pre + jnp.where(lane >= s, pltpu.roll(pre, s, 1), 0)
    dist = jnp.where(keep, lane + 1 - pre, 0)
    for k in range(LANES.bit_length() - 1):
        s = 1 << k
        move = alive * ((dist >> k) & 1)
        came = _lane_shift_left(move, s) == 1
        alive = jnp.where(came, 1, alive - move)
        vals = jnp.where(came, _lane_shift_left(vals, s), vals)
        src = jnp.where(came, _lane_shift_left(src, s), src)
        dist = jnp.where(came, _lane_shift_left(dist, s), dist)
    cval_ref[...] = vals
    cidx_ref[...] = src
    ccnt_ref[...] = pre
    full_rows = idx_acc.shape[0] - 1
    lane1 = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def place(r, off):
        c = jnp.max(ccnt_ref[pl.ds(r, 1), :])
        sh = off % LANES
        q = off // LANES
        head = (lane1 >= sh) & (lane1 < sh + c)
        tail = lane1 < sh + c - LANES

        @pl.when(q < full_rows)
        def _():
            for ref, row in ((val_acc, cval_ref), (idx_acc, cidx_ref)):
                v = pltpu.roll(row[pl.ds(r, 1), :], sh, 1)
                ref[pl.ds(q, 1), :] = jnp.where(head, v, ref[pl.ds(q, 1), :])
                ref[pl.ds(q + 1, 1), :] = jnp.where(
                    tail, v, ref[pl.ds(q + 1, 1), :])
        return off + c

    return jax.lax.fori_loop(0, rows, place, off)


def _acc_rows(cap: int) -> int:
    """Accumulator rows for `cap` slots: whole rows plus one spill row."""
    return -(-cap // LANES) + 1


def _pack_scratch(block: int):
    rows = block // LANES
    return [pltpu.VMEM((rows, LANES), jnp.float32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.VMEM((rows, LANES), jnp.int32),
            pltpu.SMEM((1,), jnp.int32)]


def _pack_params(block: int, cap: int, semantics):
    """VMEM budget: the resident accumulators (double-buffered) plus the
    streamed tiles and the compaction scratch, with headroom."""
    acc = 2 * 2 * _acc_rows(cap) * LANES * 4
    tiles_b = 8 * block * 4
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=max(32 << 20, int(1.25 * (acc + tiles_b))))


def _fuse_pack_kernel(bits, stochastic, sentinel,
                      s_ref, x_ref, u_ref,
                      out_ref, idx_ref, val_ref, tot_ref,
                      cval_ref, cidx_ref, ccnt_ref, off_ref):
    i = pl.program_id(0)
    keep, out = _mask_quantize(bits, stochastic, s_ref, x_ref, u_ref)
    out_ref[...] = out

    @pl.when(i == 0)
    def _init():
        idx_ref[...] = jnp.full(idx_ref.shape, sentinel, jnp.int32)
        val_ref[...] = jnp.zeros(val_ref.shape, jnp.float32)
        off_ref[0] = 0

    off = _pack_tile(keep, out, i * keep.size, off_ref[0], cval_ref,
                     cidx_ref, ccnt_ref, idx_ref, val_ref)
    off_ref[0] = off
    tot_ref[...] = jnp.full(tot_ref.shape, off, jnp.int32)


def fused_mask_quantize_pack_pallas(x: jax.Array, threshold: jax.Array,
                                    scale: jax.Array, u, bits: int,
                                    cap: int, sentinel: int, *,
                                    block: int = BLOCK,
                                    interpret: bool = False):
    """`fused_mask_quantize_pallas` that additionally packs the coded wire
    form in the same kernel: ascending survivor indices + their (possibly
    quantized) values in a static (cap,) buffer, empty slots at index
    `sentinel` (callers pass the unpadded length, so `sparse_accumulate`
    / `unpack_values` scatter-drop them).  Returns (masked dense, idx,
    val, total kept); total > cap means overflow — the packed buffer
    holds the first `cap` survivors and the caller must fall back to the
    dense form.  Accumulates across the sequential grid (pl.program_id),
    so NOT vmap-safe — batch callers use the non-pack variant +
    `pack_values_batch`."""
    n = x.shape[0]
    check_stream(n, block)
    grid = (n // block,)
    stochastic = u is not None
    uu = x if u is None else u
    rows = _acc_rows(cap)
    acc = pl.BlockSpec((rows, LANES), lambda i: (0, 0))
    masked, idx, val, tot = pl.pallas_call(
        functools.partial(_fuse_pack_kernel, bits, stochastic, sentinel),
        grid=grid,
        in_specs=[SMEM, stream_spec(block), stream_spec(block)],
        out_specs=[stream_spec(block), acc, acc,
                   pl.BlockSpec((1, LANES), lambda i: (0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((n // LANES, LANES), jnp.float32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((1, LANES), jnp.int32),
        ],
        scratch_shapes=_pack_scratch(block),
        compiler_params=_pack_params(block, cap, ("arbitrary",)),
        interpret=interpret,
    )(scalars(threshold, scale), tiles(x.astype(jnp.float32)),
      tiles(uu.astype(jnp.float32)))
    return (masked.reshape(n), idx.reshape(-1)[:cap], val.reshape(-1)[:cap],
            tot[0, 0])


# ---------------------------------------------------------------------------
# the jnp reference codec + the server-side sparse accumulate
# ---------------------------------------------------------------------------

def pack_values(values: jax.Array, cap: int, mask=None):
    """Reference pack: (n,) dense-embedded sparse vector -> (idx (cap,)
    int32 ascending, val (cap,), nnz ()).  `mask` defaults to
    `values != 0`; empty slots carry index n (out of range, so unpack /
    accumulate scatter-drop them).  Entries past `cap` are dropped from
    the buffer but still counted in nnz — nnz > cap flags overflow."""
    n = values.shape[-1]
    keep = values != 0 if mask is None else mask
    kept = keep.astype(jnp.int32)
    pos = jnp.where(keep, jnp.cumsum(kept) - 1, cap)
    idx = jnp.full((cap,), n, jnp.int32).at[pos].set(
        jnp.arange(n, dtype=jnp.int32), mode="drop")
    val = jnp.zeros((cap,), jnp.float32).at[pos].set(
        values.astype(jnp.float32), mode="drop")
    return idx, val, jnp.sum(kept)


def _pack_batch_kernel(sentinel, x_ref, idx_ref, val_ref, tot_ref,
                       cval_ref, cidx_ref, ccnt_ref, off_ref):
    """Batched pack: grid (B, nblocks); the per-row accumulator blocks are
    indexed by the *batch* grid axis, so rows never share state and the
    row offset re-initializes at each row's first block."""
    j = pl.program_id(1)
    x = x_ref[...]

    @pl.when(j == 0)
    def _init():
        idx_ref[...] = jnp.full(idx_ref.shape, sentinel, jnp.int32)
        val_ref[...] = jnp.zeros(val_ref.shape, jnp.float32)
        off_ref[0] = 0

    off = _pack_tile(x != 0, x, j * x.size, off_ref[0], cval_ref, cidx_ref,
                     ccnt_ref, idx_ref, val_ref)
    off_ref[0] = off
    tot_ref[...] = jnp.full(tot_ref.shape, off, jnp.int32)


def pack_values_batched_pallas(values: jax.Array, cap: int, *,
                               block: int = BLOCK, interpret: bool = False):
    """In-kernel batched pack of (B, n) dense-embedded sparse rows ->
    (idx (B, cap), val (B, cap), nnz (B,)), n % block == 0 (pad
    upstream; zero padding is never kept).  Empty slots carry sentinel
    index n — the *padded* length when the caller padded, which
    `pack_values_batch` clamps back to the unpadded length.  Otherwise
    bit-identical to `jax.vmap(lambda v: pack_values(v, cap))(values)`:
    same keep mask, same ascending positions, same overflow semantics."""
    B, n = values.shape
    check_stream(n, block)
    grid = (B, n // block)
    rows = _acc_rows(cap)
    acc = pl.BlockSpec((None, rows, LANES), lambda b, j: (b, 0, 0))
    idx, val, tot = pl.pallas_call(
        functools.partial(_pack_batch_kernel, n),
        grid=grid,
        in_specs=[pl.BlockSpec((None, block // LANES, LANES),
                               lambda b, j: (b, j, 0))],
        out_specs=[acc, acc,
                   pl.BlockSpec((None, 1, LANES), lambda b, j: (b, 0, 0))],
        out_shape=[
            jax.ShapeDtypeStruct((B, rows, LANES), jnp.int32),
            jax.ShapeDtypeStruct((B, rows, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, 1, LANES), jnp.int32),
        ],
        scratch_shapes=_pack_scratch(block),
        compiler_params=_pack_params(block, cap, ("parallel", "arbitrary")),
        interpret=interpret,
    )(tiles(values.astype(jnp.float32)))
    return (idx.reshape(B, -1)[:, :cap], val.reshape(B, -1)[:, :cap],
            tot[:, 0, 0])


def pack_values_batch(values: jax.Array, cap: int, *,
                      interpret=None):
    """The engines' batched cohort pack step: in-kernel packing via
    `pack_values_batched_pallas` (native on TPU; interpret mode with one
    whole-row block everywhere else, the selector layer's dispatch
    idiom), padding the rows up to the block multiple internally.  The
    sentinel stays the unpadded length `n`, matching `pack_values`
    exactly — padded tail zeros are never kept, so the result is
    bit-identical to `jax.vmap(lambda v: pack_values(v, cap))`."""
    n = values.shape[-1]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # one lane-aligned whole-row block under interpret (per-block cost
    # dominates there); the VMEM-sized tile on TPU
    block = -(-n // 128) * 128 if interpret else BLOCK
    pad = -n % block
    x = values.astype(jnp.float32)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    idx, val, tot = pack_values_batched_pallas(
        x, cap, block=block, interpret=interpret)
    # padded source positions can never be kept (zeros), but their slot
    # indices would exceed n; clamp the sentinel back to n for bit-parity
    idx = jnp.minimum(idx, n)
    return idx, val, tot


def unpack_values(idx: jax.Array, val: jax.Array, n: int) -> jax.Array:
    """Densify one packed message; sentinel slots (index >= n) drop."""
    return jnp.zeros((n,), val.dtype).at[idx].set(val, mode="drop")


def sparse_accumulate(idx: jax.Array, val: jax.Array, n: int) -> jax.Array:
    """Sum packed client messages into a dense (n,) vector without ever
    densifying the messages: one scatter-add over all (cap,) rows.  `idx`
    / `val` are (..., cap); sentinel slots (index >= n) drop.  This is the
    AsyncEngine's server-side aggregation of packed jobs — O(total nnz)
    gather-accumulate, vs O(clients * p_len) for the dense mean — and is
    bit-equal to the row-ordered sum of the dense rows, which is what
    the synchronous round computes."""
    return jnp.zeros((n,), val.dtype).at[idx.reshape(-1)].add(
        val.reshape(-1), mode="drop")


def hierarchical_accumulate(idx: jax.Array, val: jax.Array, n: int,
                            edges: int) -> jax.Array:
    """Two-level edge -> server reduction of packed client messages,
    bit-equal to the flat `sparse_accumulate` (docs/scale.md).

    Edges are *parameter-sharded* (reduce-scatter style): edge `e` owns
    the contiguous index range [e*n//edges, (e+1)*n//edges) and
    scatter-adds only the pairs that land in its range (everything else
    is redirected to that edge's local sentinel and dropped — sparse
    uploads never densify at the edge); the server then concatenates the
    disjoint dense partials with *no* cross-edge additions.  Because
    every coordinate's additions happen at exactly one edge, in the same
    flattened row-major order the flat scatter-add applies them, the
    f32 sums associate identically and the result is bitwise equal —
    unlike client-sharded edge partials, whose server-side re-addition
    would re-associate the per-coordinate sums.  Each edge's work is
    O(total nnz) masking + O(nnz in range) scatter, so the server-side
    combine stays O(n) concatenation regardless of cohort or population
    size."""
    assert edges >= 1, edges
    parts = []
    for e in range(edges):
        lo, hi = e * n // edges, (e + 1) * n // edges
        in_range = (idx >= lo) & (idx < hi)
        # out-of-range pairs -> this edge's sentinel (hi - lo), dropped
        eidx = jnp.where(in_range, idx - lo, hi - lo)
        parts.append(jnp.zeros((hi - lo,), val.dtype).at[
            eidx.reshape(-1)].add(val.reshape(-1), mode="drop"))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]

"""First-class federated strategies: a `Strategy` protocol + registry.

A strategy answers three orthogonal questions about one FL round over the
flat global vector `P` (Algorithm 1): which entries move *down*, which
gradients *train*, and which entries move *up*.  Each answer is expressed
through four hooks on the `Strategy` base class:

  init_state(p_len)                  -> persistent server-side pytree
  download_mask(flatP, sstate, r)    -> global (p_len,) bool download mask
  client_plan(m_down, slot, ctx)     -> per-client `RoundPlan`
  post_round(sstate, flatP, ...)     -> end-of-round state transition

plus `download_base(flatP, sstate)` for strategies that correct the
downloaded weights before masking (error feedback).  `core.fedround` is
strategy-agnostic: it only ever calls these hooks, stacks the returned
`RoundPlan`s onto the vmapped client axis, and routes messages through the
`core.transport` pipeline.

Register a new strategy with `@register_strategy("name")`; it is then
reachable from `StrategySpec(kind="name")`, the `Experiment` builder, and
every benchmark.  See `docs/strategies.md` for the per-strategy mask table
(formerly in this docstring) and a how-to-add-a-strategy recipe.
"""
from __future__ import annotations

import dataclasses
import inspect
import warnings
from typing import Any, ClassVar, Dict, Optional, Tuple, Type, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import selectors as sel
from repro.core import sparsity as sp

KINDS = ("lora", "flasc", "flasc_ef", "sparse_adapter", "fedselect",
         "adapter_lth", "ffa", "hetlora")


@dataclasses.dataclass(frozen=True)
class StrategySpec:
    """Declarative strategy config; resolved to a `Strategy` via `resolve`."""
    kind: str = "flasc"
    density_down: float = 0.25
    density_up: float = 0.25
    # Top-K selection policy for every mask/upload in the round
    # (`core.selectors` registry: "exact" | "histogram" | "pallas").
    # "" means unset; __post_init__ resolves it to "exact" (or to the
    # exact_topk mapping), so a constructed spec always carries a real name.
    selector: str = ""
    # deprecated alias for `selector`: True -> "exact", False -> "histogram"
    exact_topk: Optional[bool] = None
    # Adapter-LTH schedule
    lth_prune_every: int = 1
    lth_keep: float = 0.98
    # heterogeneity: per-client-slot density (flasc-het) or rank (hetlora)
    client_densities: Tuple[float, ...] = ()
    hetlora_ranks: Tuple[int, ...] = ()
    # hetlora: rank-coverage-weighted aggregation instead of plain averaging
    hetlora_weighted: bool = False
    # message quantization (0 = off); composes with Top-K: mask -> quantize
    quant_bits_down: int = 0
    quant_bits_up: int = 0
    # FLoCoRA-style low-rank *message* compression (transport.LowRankCompress,
    # docs/baselines.md): factor rank per direction (0 = off — except under
    # kind="flocora", whose whole point is both-direction compression, so
    # there each 0 means "default to 8"; use any other kind for
    # single-direction compression).  "random" transmits only the
    # seeded-projection coefficients; "learned" transmits both SVD factors.
    # Quantization bits for a compressed direction apply to the transmitted
    # factors.
    lowrank_down: int = 0
    lowrank_up: int = 0
    lowrank_mode: str = "random"
    lowrank_seed: int = 0
    # server-side sparse aggregation (docs/kernels.md): the uploads'
    # nonzeros are summed per coordinate in client order.  The
    # AsyncEngine carries its jobs in packed coded form (indices +
    # values) and scatter-adds them
    # (`kernels.fused_transport.sparse_accumulate`); the synchronous
    # round already holds the dense (n_clients, p_len) stack on the
    # device and folds its rows, which gives the same bits, so its cost
    # is O(C * p_len) reads there.  Opt-in; only sound for uniform-
    # averaging strategies with topk uploads (see
    # `supports_sparse_aggregate`), and the engines fall back to the
    # dense mean whenever a message overflows its static pack capacity,
    # so results are never silently truncated.
    sparse_aggregate: bool = False
    # hierarchical two-level aggregation (docs/scale.md): > 0 splits the
    # flat vector into that many contiguous index ranges, each pre-reduced
    # by an "edge" scatter-add over only its range (sparse uploads never
    # densify at the edge) before the server concatenates the disjoint
    # partials.  Parameter-sharded (reduce-scatter style), so the per-
    # coordinate addition order matches the flat reduction exactly and the
    # result is bit-equal; 0 = flat single-level reduction.  Only takes
    # effect where uploads are aggregated packed, the AsyncEngine's
    # sparse-aggregation path (`sparse_aggregate=True`); the synchronous
    # round's row fold gives the same bits either way.
    edge_shards: int = 0
    # two_stage_ortho phase length: each A/B communication phase spans
    # this many consecutive rounds (1 = the paper's strict alternation).
    # The QR re-orthogonalization folds once per A phase, on its last
    # round.  Ignored by every other kind.
    phase_len: int = 1

    def __post_init__(self):
        # user strategies enter the registry after import time, so accept
        # any registered kind, not just the eight built-ins
        if self.kind not in KINDS and self.kind not in _REGISTRY:
            raise ValueError(
                f"unknown strategy kind {self.kind!r}; known: "
                f"{tuple(sorted(set(KINDS) | set(_REGISTRY)))}")
        if self.exact_topk is not None:
            warnings.warn(
                "StrategySpec(exact_topk=...) is deprecated; use "
                "selector=\"exact\" / \"histogram\" instead",
                DeprecationWarning, stacklevel=3)
            mapped = "exact" if self.exact_topk else "histogram"
            if self.selector and self.selector != mapped:
                raise ValueError(
                    f"conflicting selection config: selector="
                    f"{self.selector!r} with exact_topk={self.exact_topk}")
            object.__setattr__(self, "selector", mapped)
            # the alias is consumed by the mapping: clearing it lets
            # dataclasses.replace(spec, selector=...) migrate a legacy
            # spec, and keeps checkpoints from persisting (and re-warning
            # about) the deprecated field on every resume
            object.__setattr__(self, "exact_topk", None)
        elif not self.selector:
            object.__setattr__(self, "selector", "exact")
        if not isinstance(self.selector, str) or \
                self.selector not in sel.registered_selectors():
            raise ValueError(
                f"unknown selector {self.selector!r}; known: "
                f"{sel.registered_selectors()} (custom Selector instances "
                "go through transport.TopKSparsify, not the spec)")
        if self.lowrank_mode not in ("random", "learned"):
            raise ValueError(
                f"unknown lowrank_mode {self.lowrank_mode!r}; "
                "known: ('random', 'learned')")
        if self.lowrank_down < 0 or self.lowrank_up < 0:
            raise ValueError("lowrank ranks must be >= 0 (0 = off); got "
                             f"{self.lowrank_down}/{self.lowrank_up}")
        if self.edge_shards < 0:
            raise ValueError(
                f"edge_shards must be >= 0 (0 = flat); got {self.edge_shards}")
        if self.phase_len < 1:
            raise ValueError(
                f"phase_len must be >= 1; got {self.phase_len}")


# ---------------------------------------------------------------------------
# per-client round plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class UploadRule:
    """How one client turns its dense local delta into the upload message.

    mode "topk":  Top-K of the delta at `density` (FLASC — the only rule
                  compatible with dense local training).
    mode "fixed": multiply by `mask`; nnz accounting counts actual nonzero
                  values (the mask may cover entries the delta never touched).
    """
    mode: str                                   # "topk" | "fixed"
    density: float = 1.0
    mask: Optional[jax.Array] = None

    def __post_init__(self):
        assert self.mode in ("topk", "fixed"), self.mode

    @classmethod
    def topk(cls, density: float) -> "UploadRule":
        return cls(mode="topk", density=float(density))

    @classmethod
    def fixed(cls, mask) -> "UploadRule":
        return cls(mode="fixed", mask=mask)


@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """One client's plan for one round, in flat-vector space.

    m_down  — (p_len,) bool: entries downloaded to this client
    m_train — (p_len,) bool mask on local gradients, or None = dense local
              finetuning (FLASC's distinguishing feature)
    upload  — `UploadRule` for the delta upload
    """
    m_down: jax.Array
    m_train: Optional[jax.Array]
    upload: UploadRule


@dataclasses.dataclass(frozen=True)
class PlanContext:
    """Per-round facts available to `client_plan` / `aggregate` /
    `post_round`.  A fresh context is built per round trace, so strategies
    may key caches on its identity (see `FFALoRA`/`TwoStageOrtho`)."""
    p_len: int
    n_clients: int
    rank_idx: Optional[np.ndarray] = None       # per-entry LoRA rank component
    is_b: Optional[np.ndarray] = None           # per-entry "is a B-matrix entry"
    # traced scalar: the server round counter (schedule-dependent
    # strategies branch on it with jnp.where, never python `if`)
    round_idx: Any = None
    # the `fedround.FlatMeta` of the trainable tree — gives structure-aware
    # strategies (per-leaf QR in `two_stage_ortho`) flatten/unflatten
    meta: Any = None
    # which client *slots* actually contributed the rows being aggregated
    # (None = the full 0..n_clients-1 cohort, the sync-round default).
    # AsyncEngine sets this to the buffer's job slots so coverage-weighted
    # strategies (hetlora_weighted) scale by the slices actually present
    # in a partial/repeated buffer instead of assuming the full cohort.
    cohort_slots: Optional[Tuple[int, ...]] = None


# ---------------------------------------------------------------------------
# the protocol + registry
# ---------------------------------------------------------------------------

class Strategy:
    """Base strategy: dense download, dense training, upload = download mask.

    Subclasses override any subset of the hooks.  Instances are lightweight,
    stateless wrappers around a `StrategySpec`; all persistent state lives in
    the `sstate` pytree threaded through the round function (so strategies
    stay jit/scan-compatible).
    """
    kind: ClassVar[str] = "base"

    def __init__(self, spec: Optional[StrategySpec] = None):
        self.spec = spec if spec is not None else StrategySpec(kind=self.kind)
        assert self.spec.kind == self.kind, (self.spec.kind, self.kind)

    # --- hooks -------------------------------------------------------------
    def init_state(self, p_len: int) -> Dict[str, Any]:
        return {}

    def download_mask(self, flatP, sstate, round_idx) -> jax.Array:
        """Global (non-per-client) download mask. (p_len,) bool."""
        return jnp.ones_like(flatP, bool)

    def download_base(self, flatP, sstate) -> jax.Array:
        """Vector the download mask is applied to (default: the raw server
        weights; error-feedback strategies add their residual here)."""
        return flatP

    def client_plan(self, m_down, slot: int, ctx: PlanContext) -> RoundPlan:
        return RoundPlan(m_down, None, UploadRule.fixed(m_down))

    def aggregate(self, deltas, ctx: PlanContext) -> jax.Array:
        """Combine the (n_clients, p_len) upload messages into the server
        pseudo-gradient.  Default: uniform averaging (FedAvg)."""
        return jnp.mean(deltas, axis=0)

    def aggregate_sparse(self, idx, val, ctx: PlanContext) -> jax.Array:
        """`aggregate` over *packed* upload messages — (n_clients, cap)
        index/value rows, sentinel index >= p_len in empty slots — without
        ever densifying them: one scatter-add (`fused_transport.
        sparse_accumulate`) then the uniform 1/C scaling.  Only called
        when `supports_sparse_aggregate` holds, i.e. for strategies whose
        `aggregate` is the base-class uniform mean, so the two paths
        compute the same sum up to float summation order.  The
        AsyncEngine's server phase runs this on its packed jobs; the
        synchronous round folds its dense upload rows in client order
        instead (`fedround._aggregate_uploads`), which gives these bits
        exactly.  With `spec.edge_shards > 0` the
        scatter-add runs as the hierarchical edge tree
        (`fused_transport.hierarchical_accumulate`), which is bit-equal
        to the flat reduction by construction (docs/scale.md)."""
        from repro.kernels import fused_transport as ft
        if self.spec.edge_shards > 0:
            acc = ft.hierarchical_accumulate(idx, val, ctx.p_len,
                                             self.spec.edge_shards)
        else:
            acc = ft.sparse_accumulate(idx, val, ctx.p_len)
        return acc / idx.shape[0]

    @property
    def uniform_aggregation(self) -> bool:
        """True when `aggregate` is plain averaging — the assumption DP
        noise calibration relies on.  Strategies with a weighted rule must
        return False so the round function can refuse dp_clip > 0."""
        return True

    def post_round(self, sstate, flatP, *, P_base, m_down, round_idx,
                   ctx: Optional[PlanContext] = None):
        """End-of-round transition; returns (sstate', flatP') — strategies
        may permanently zero pruned weights.  `ctx` is the round's
        `PlanContext` (None from legacy callers that predate it)."""
        return sstate, flatP

    def __repr__(self):
        return f"{type(self).__name__}({self.spec})"


def call_post_round(strat: "Strategy", sstate, flatP, *, P_base, m_down,
                    round_idx, ctx: Optional[PlanContext]):
    """Invoke `strat.post_round`, passing `ctx=` only when the override
    accepts it — out-of-tree strategies written against the pre-ctx hook
    signature keep working (the round loop calls through here)."""
    params = inspect.signature(type(strat).post_round).parameters
    if "ctx" in params or any(p.kind is inspect.Parameter.VAR_KEYWORD
                              for p in params.values()):
        return strat.post_round(sstate, flatP, P_base=P_base, m_down=m_down,
                                round_idx=round_idx, ctx=ctx)
    return strat.post_round(sstate, flatP, P_base=P_base, m_down=m_down,
                            round_idx=round_idx)


_REGISTRY: Dict[str, Type[Strategy]] = {}


def register_strategy(kind: str):
    """Class decorator: `@register_strategy("flasc")` makes the class
    constructible from `StrategySpec(kind="flasc")` / the string "flasc"."""
    def deco(cls: Type[Strategy]) -> Type[Strategy]:
        assert issubclass(cls, Strategy), cls
        cls.kind = kind
        _REGISTRY[kind] = cls
        return cls
    return deco


def registered_kinds() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def supports_sparse_aggregate(strat: "Strategy") -> bool:
    """True when `strat` may aggregate packed (index, value) upload
    messages via `Strategy.aggregate_sparse` instead of dense stacks.

    Requires `spec.sparse_aggregate` opt-in, the *base-class* uniform
    `aggregate` (a weighted override like hetlora_weighted's rank
    coverage reads the dense stack and must keep getting it), no
    per-client upload densities (one static pack capacity serves the
    whole cohort), and no low-rank upload compression (factor messages
    are dense matrices, not sparse supports).  DP clipping is checked at
    the call sites — `federated_round` only reaches the sparse branch
    with dp_clip == 0, and AsyncEngine refuses DP outright."""
    spec = strat.spec
    return bool(spec.sparse_aggregate
                and type(strat).aggregate is Strategy.aggregate
                and not spec.client_densities
                and spec.lowrank_up == 0)


def sparse_aggregate_capacity(strat: "Strategy", p_len: int) -> int:
    """Static packed-message slot count for the engines' sparse
    aggregation path: 0 when `strat` does not support it (the engines
    read 0 as "stay dense"), else `comm.pack_capacity` over the spec's
    expected Top-K upload support at `density_up`.  Quantization only
    ever zeroes kept values, so it never raises the support; threshold
    ties can, which is what the capacity slack (and the dense overflow
    fallback) absorbs."""
    if not supports_sparse_aggregate(strat):
        return 0
    from repro.core import comm
    return comm.pack_capacity(
        p_len, int(sp.density_count(p_len, strat.spec.density_up)))


StrategyLike = Union[Strategy, StrategySpec, str]


def resolve(obj: StrategyLike) -> Strategy:
    """StrategySpec / kind-string / Strategy instance -> Strategy instance."""
    if isinstance(obj, Strategy):
        return obj
    if isinstance(obj, StrategySpec):
        try:
            cls = _REGISTRY[obj.kind]
        except KeyError:
            raise KeyError(f"no strategy registered for kind={obj.kind!r}; "
                           f"known: {registered_kinds()}") from None
        return cls(obj)
    if isinstance(obj, str):
        return resolve(StrategySpec(kind=obj))
    raise TypeError(f"cannot resolve {obj!r} to a Strategy")


# ---------------------------------------------------------------------------
# static flat-view metadata (shared by ffa / hetlora)
# ---------------------------------------------------------------------------

def rank_index_map(lora_tree) -> Tuple[np.ndarray, np.ndarray]:
    """Static per-entry metadata for the flat view: (rank_idx, is_b).

    For a leaf 'a' (..., d_in, r): rank component = position % r.
    For a leaf 'b' (..., r, d_out): rank component = (position // d_out) % r.
    """
    leaves, _ = jax.tree_util.tree_flatten_with_path(lora_tree)
    rank_idx, is_b = [], []
    for path, leaf in leaves:
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        n = int(np.prod(leaf.shape))
        pos = np.arange(n, dtype=np.int32)
        if name == "a":
            r = leaf.shape[-1]
            rank_idx.append(pos % r)
            is_b.append(np.zeros(n, np.int8))
        elif name == "b":
            r, d_out = leaf.shape[-2], leaf.shape[-1]
            rank_idx.append((pos // d_out) % r)
            is_b.append(np.ones(n, np.int8))
        else:  # non-LoRA leaf (full_ft): no rank structure
            rank_idx.append(np.zeros(n, np.int32))
            is_b.append(np.ones(n, np.int8))
    return np.concatenate(rank_idx), np.concatenate(is_b)


# ---------------------------------------------------------------------------
# the eight paper strategies
# ---------------------------------------------------------------------------

@register_strategy("lora")
class DenseLoRA(Strategy):
    """Dense LoRA (FedIT): everything moves, everything trains.  `full_ft`
    reuses this over the backbone vector."""


@register_strategy("flasc")
class Flasc(Strategy):
    """FLASC: Top-K download of P, *dense* local training, independent Top-K
    upload of the delta — the paper's method."""

    def download_mask(self, flatP, sstate, round_idx):
        return sel.topk_mask(flatP, self.spec.density_down,
                             selector=self.spec.selector)

    def client_plan(self, m_down, slot, ctx):
        s = self.spec
        d_up = s.client_densities[slot] if s.client_densities else s.density_up
        return RoundPlan(m_down, None, UploadRule.topk(d_up))


@register_strategy("flasc_ef")
class FlascEF(Flasc):
    """FLASC + server-side error feedback for download sparsity (beyond-
    paper, EF14/EF21-style): the Top-K residual accumulates and is re-offered
    next round.  Upload-side EF is infeasible cross-device because clients
    are stateless across rounds."""

    def init_state(self, p_len):
        return {"e": jnp.zeros((p_len,), jnp.float32)}

    def download_mask(self, flatP, sstate, round_idx):
        return sel.topk_mask(flatP + sstate["e"], self.spec.density_down,
                             selector=self.spec.selector)

    def download_base(self, flatP, sstate):
        return flatP + sstate["e"]

    def post_round(self, sstate, flatP, *, P_base, m_down, round_idx,
                   ctx=None):
        return {"e": P_base * (1.0 - m_down)}, flatP     # unsent residual


@register_strategy("sparse_adapter")
class SparseAdapter(Strategy):
    """Fixed sparse adapter (paper Appx A): one dense round, then magnitude-
    prune once and freeze the mask for download, training, and upload."""

    def init_state(self, p_len):
        return {"mask": jnp.ones((p_len,), jnp.bool_),
                "initialized": jnp.zeros((), jnp.bool_)}

    def download_mask(self, flatP, sstate, round_idx):
        return sstate["mask"]

    def client_plan(self, m_down, slot, ctx):
        return RoundPlan(m_down, m_down, UploadRule.fixed(m_down))

    def post_round(self, sstate, flatP, *, P_base, m_down, round_idx,
                   ctx=None):
        spec = self.spec

        def first(_):
            return {"mask": sel.topk_mask(flatP, spec.density_down,
                                          selector=spec.selector),
                    "initialized": jnp.ones((), jnp.bool_)}

        def rest(_):
            return sstate

        return jax.lax.cond(sstate["initialized"], rest, first, None), flatP


@register_strategy("fedselect")
class FedSelect(Strategy):
    """Federated Select: a fresh Top-K mask of P each round, shared by
    download, training, and upload."""

    def download_mask(self, flatP, sstate, round_idx):
        return sel.topk_mask(flatP, self.spec.density_down,
                             selector=self.spec.selector)

    def client_plan(self, m_down, slot, ctx):
        return RoundPlan(m_down, m_down, UploadRule.fixed(m_down))


@register_strategy("adapter_lth")
class AdapterLTH(Strategy):
    """Lottery-ticket adapter: multiplicative density decay with permanent
    pruning every `lth_prune_every` rounds."""

    def init_state(self, p_len):
        return {"mask": jnp.ones((p_len,), jnp.bool_),
                "density": jnp.ones((), jnp.float32)}

    def download_mask(self, flatP, sstate, round_idx):
        return sstate["mask"]

    def client_plan(self, m_down, slot, ctx):
        return RoundPlan(m_down, m_down, UploadRule.fixed(m_down))

    def post_round(self, sstate, flatP, *, P_base, m_down, round_idx,
                   ctx=None):
        spec = self.spec
        n = flatP.shape[-1]

        def prune(_):
            dens = jnp.maximum(sstate["density"] * spec.lth_keep, 1e-4)
            masked = jnp.where(sstate["mask"], jnp.abs(flatP), 0.0)
            # traced keep-count through the selector layer (same k clip the
            # seed's threshold path used); `masked > 0` keeps the permanent-
            # pruning invariant under the exact selector, whose rank
            # selection would otherwise resurrect zeroed entries on ties
            # (the histogram family's TINY threshold floor already excludes
            # exact zeros, so there it is a no-op)
            k = jnp.clip(jnp.round(n * dens).astype(jnp.int32), 1, n - 1)
            mask = sel.topk_mask_by_count(masked, k,
                                          selector=spec.selector) & (masked > 0)
            return {"mask": mask, "density": dens}

        def keep(_):
            return sstate

        do = (round_idx % spec.lth_prune_every == 0) & (round_idx > 0)
        sstate2 = jax.lax.cond(do, prune, keep, None)
        return sstate2, flatP * sstate2["mask"]


@register_strategy("ffa")
class FFALoRA(Strategy):
    """FFA-LoRA: download everything, but train and upload only the B
    matrices (A frozen at init) — halves upload and fixes DP aggregation
    bias."""

    _mask_cache: Optional[Tuple[PlanContext, jax.Array]] = None

    def client_plan(self, m_down, slot, ctx):
        assert ctx.is_b is not None, "ffa needs FlatMeta rank metadata"
        # slot-independent within one round's PlanContext: hand every client
        # the same array so the round function broadcasts it over the client
        # axis instead of stacking copies.  Keyed on the context object, so
        # reusing the Strategy instance across models stays correct.
        if self._mask_cache is None or self._mask_cache[0] is not ctx:
            self._mask_cache = (ctx, jnp.asarray(ctx.is_b == 1))
        m_train = self._mask_cache[1]
        return RoundPlan(m_down, m_train, UploadRule.fixed(m_train))


@register_strategy("hetlora")
class HetLoRA(Strategy):
    """Heterogeneous LoRA: client c sees only the leading `hetlora_ranks[c]`
    rank components (structured nested masks) for download, training, and
    upload.

    With `hetlora_weighted=True` the aggregation divides each entry by the
    number of clients whose rank slice actually covers it, instead of the
    full cohort size: plain averaging dilutes the high-rank components
    (only the large-rank clients ever touch them) by n_clients, shrinking
    their effective server learning rate by n/coverage."""

    def client_plan(self, m_down, slot, ctx):
        assert ctx.rank_idx is not None, "hetlora needs FlatMeta rank metadata"
        r_c = self.spec.hetlora_ranks[slot]
        m = jnp.asarray(ctx.rank_idx < r_c)
        return RoundPlan(m, m, UploadRule.fixed(m))

    def coverage(self, ctx: PlanContext) -> np.ndarray:
        """(p_len,) count of aggregated rows whose rank mask covers each
        entry.  Defaults to the full 0..n_clients-1 cohort; when
        `ctx.cohort_slots` is set (AsyncEngine partial/repeated buffers),
        only the slices actually present in the buffer are counted — a
        slot appearing twice (version repeats) contributes twice, matching
        the two delta rows it stacked."""
        assert ctx.rank_idx is not None, "hetlora needs FlatMeta rank metadata"
        if ctx.cohort_slots is not None:
            ranks = np.asarray([self.spec.hetlora_ranks[s]
                                for s in ctx.cohort_slots])
        else:
            ranks = np.asarray(self.spec.hetlora_ranks[:ctx.n_clients])
            assert len(ranks) == ctx.n_clients, \
                (len(self.spec.hetlora_ranks), ctx.n_clients)
        return np.sum(ranks[:, None] > ctx.rank_idx[None, :], axis=0)

    def aggregate(self, deltas, ctx):
        if not self.spec.hetlora_weighted:
            return super().aggregate(deltas, ctx)
        cov = jnp.asarray(np.maximum(self.coverage(ctx), 1), jnp.float32)
        return jnp.sum(deltas, axis=0) / cov

    @property
    def uniform_aggregation(self) -> bool:
        return not self.spec.hetlora_weighted


# ---------------------------------------------------------------------------
# the named communication-efficiency baselines (docs/baselines.md)
# ---------------------------------------------------------------------------

@register_strategy("flocora")
class FloCoRA(DenseLoRA):
    """FLoCoRA (Grativol et al., arXiv:2406.14082): dense LoRA rounds whose
    *messages* are low-rank compressed by the `transport.LowRankCompress`
    stage in both directions — the whole method lives in the transport
    pipeline, so the strategy itself is dense LoRA.  The method
    compresses *both* directions, so each unset (zero) rank defaults to
    8 independently; mode "random" ships only the seeded-projection
    coefficients (the paper's shared-random-matrix trick), "learned"
    ships both SVD factors.  For single-direction compression use any
    other kind with the `lowrank_*` spec fields."""

    DEFAULT_RANK = 8

    def __init__(self, spec: Optional[StrategySpec] = None):
        spec = spec if spec is not None else StrategySpec(kind="flocora")
        spec = dataclasses.replace(
            spec, lowrank_down=spec.lowrank_down or self.DEFAULT_RANK,
            lowrank_up=spec.lowrank_up or self.DEFAULT_RANK)
        super().__init__(spec)


@register_strategy("two_stage_ortho")
class TwoStageOrtho(Strategy):
    """Two-stage sparsified-orthogonal updates (Kim & Choi,
    arXiv:2505.00333): the A and B factors of every adapter alternate
    communication phases — even rounds train and upload only the A
    entries, odd rounds only the B entries (non-LoRA leaves, e.g. a
    classification head, ride the B phase: `rank_index_map` marks them
    is_b) — so each upload moves roughly half the vector before
    sparsification.  Uploads are magnitude Top-K at `density_up` through
    the selector layer; the delta is zero off the phase mask, so Top-K
    selects within the active factor with no extra machinery.  After
    every A phase the server orthogonalizes each aggregated A factor
    (reduced QR) and folds the triangular factor into B, keeping the
    adapter product A·B bit-for-bit unchanged in exact arithmetic while
    renormalizing the basis the next B phase trains against.  Download
    stays dense (clients need both factors to run the model); compose
    with `lowrank_down` for download compression.  `StrategySpec(
    phase_len=L)` stretches each phase to L consecutive rounds — the QR
    fold then runs once per A phase, on its last round — with L=1
    reproducing the paper's strict alternation bit-for-bit."""

    _phase_cache: Optional[Tuple[PlanContext, jax.Array]] = None

    def _phase_mask(self, ctx: PlanContext) -> jax.Array:
        assert ctx.is_b is not None, \
            "two_stage_ortho needs FlatMeta rank metadata"
        assert ctx.round_idx is not None, \
            "two_stage_ortho needs PlanContext.round_idx"
        # one array per round trace (keyed on the fresh-per-round ctx), so
        # every client's plan shares it and the round function broadcasts
        # instead of stacking copies
        if self._phase_cache is None or self._phase_cache[0] is not ctx:
            is_b = jnp.asarray(ctx.is_b == 1)
            # phase_len consecutive rounds per phase (1 = strict A/B
            # alternation, the paper's schedule)
            phase_b = ((ctx.round_idx // self.spec.phase_len) % 2) == 1
            self._phase_cache = (ctx, jnp.where(phase_b, is_b, ~is_b))
        return self._phase_cache[1]

    def client_plan(self, m_down, slot, ctx):
        m_train = self._phase_mask(ctx)
        return RoundPlan(m_down, m_train,
                         UploadRule.topk(self.spec.density_up))

    def post_round(self, sstate, flatP, *, P_base, m_down, round_idx,
                   ctx=None):
        assert ctx is not None and ctx.meta is not None, \
            "two_stage_ortho.post_round needs PlanContext.meta"
        meta = ctx.meta

        def orthogonalize(flat):
            return meta.flatten(_ortho_lora_pairs(meta.unflatten(flat)))

        # fold QR once per A phase, on its last round (phase_len=1 reduces
        # to the original "after every even round" schedule)
        L = self.spec.phase_len
        a_phase_end = (((round_idx // L) % 2) == 0) & ((round_idx + 1) % L == 0)
        flatP = jax.lax.cond(a_phase_end, orthogonalize, lambda f: f, flatP)
        return sstate, flatP


def _ortho_lora_pairs(tree):
    """Reduced-QR every {'a', 'b'} LoRA pair in a mirrored tree:
    a -> Q, b -> R @ b (product-preserving reparameterization; batched
    over any leading stacked-layer dims)."""
    if isinstance(tree, dict) and {"a", "b"} <= set(tree) \
            and not isinstance(tree["a"], dict):
        a, b = tree["a"], tree["b"]
        if a.shape[-2] < a.shape[-1]:   # wide A: reduced QR would reshape it
            return tree
        q, r = jnp.linalg.qr(a.astype(jnp.float32))
        return {**tree, "a": q.astype(a.dtype),
                "b": (r @ b.astype(jnp.float32)).astype(b.dtype)}
    if isinstance(tree, dict):
        return {k: _ortho_lora_pairs(v) for k, v in tree.items()}
    return tree

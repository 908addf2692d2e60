"""The federated round — FLASC Algorithm 1 (and every baseline) as a single
jit-able, strategy-agnostic function.

One call = one FL round: ask the `Strategy` (resolved through the registry
in `core.strategies`) for a global download mask and one `RoundPlan` per
client, stack the plans onto the vmapped client axis (sharded over
`data`/`pod` in the production mesh), run every client's local SGD(
+momentum) epochs in parallel, route both message directions through the
`core.transport` pipeline (mask -> quantize), (optionally DP clip+noise),
aggregate, apply the FedAdam server update, and hand the round back to the
strategy's `post_round` hook.  All strategy logic lives behind the hook
protocol — this module contains no per-strategy branches.

Homogeneous and heterogeneous cohorts share one code path: per-client plan
fields that are identical objects collapse to broadcast operands
(`in_axes=None`), anything client-varying rides the vmapped axis — which
is also what guarantees heterogeneous runs get the same quantization
treatment as homogeneous ones.

This function *is* the object lowered by the multi-pod dry-run for the
`train_4k` shape.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import dp as dp_mod
from repro.core import sparsity as sp
from repro.core import strategies as st
from repro.core import transport as tp
from repro.kernels import fused_transport as ft
from repro.models.config import FederatedConfig
from repro.optim import adam_init, adam_update

LossFn = Callable[[Any, Any, Dict[str, jax.Array]], jax.Array]
# loss_of(params, trainable_tree, microbatch) -> scalar: the frozen backbone
# rides every step as an explicit (shardable, FSDP-able) argument, never as
# a closed-over replicated constant.

_BoundLoss = Callable[[Any, Dict[str, jax.Array]], jax.Array]
# a LossFn with its backbone bound: (trainable_tree, microbatch) -> scalar


@dataclasses.dataclass
class FlatMeta:
    """Static flatten metadata for the trainable tree."""
    treedef: Any
    shapes: Tuple
    p_len: int
    rank_idx: Optional[np.ndarray] = None
    is_b: Optional[np.ndarray] = None

    @classmethod
    def of(cls, tree, with_rank_map: bool = True):
        leaves, treedef = jax.tree.flatten(tree)
        shapes = tuple((l.shape, l.dtype) for l in leaves)
        p_len = int(sum(np.prod(s) for s, _ in shapes))
        rk = ib = None
        if with_rank_map:
            rk, ib = st.rank_index_map(tree)
        return cls(treedef, shapes, p_len, rk, ib)

    def flatten(self, tree) -> jax.Array:
        leaves = jax.tree.leaves(tree)
        return jnp.concatenate([l.reshape(-1).astype(jnp.float32) for l in leaves])

    def unflatten(self, flat: jax.Array):
        out, off = [], 0
        for shape, dtype in self.shapes:
            n = int(np.prod(shape))
            out.append(flat[off:off + n].reshape(shape).astype(dtype))
            off += n
        return jax.tree.unflatten(self.treedef, out)

    def plan_context(self, n_clients: int, round_idx=None,
                     cohort_slots=None) -> st.PlanContext:
        """Fresh per-round context; `round_idx` (traced scalar) lets
        schedule-dependent strategies branch on the round, `meta=self`
        gives structure-aware hooks flatten/unflatten, and `cohort_slots`
        (static tuple, AsyncEngine partial buffers) tells coverage-aware
        aggregation which slots actually contributed."""
        return st.PlanContext(p_len=self.p_len, n_clients=n_clients,
                              rank_idx=self.rank_idx, is_b=self.is_b,
                              round_idx=round_idx, meta=self,
                              cohort_slots=cohort_slots)


def init_server(flatP: jax.Array):
    return {"opt": adam_init(flatP), "round": jnp.zeros((), jnp.int32)}


def _client_update(flat0, cbatch, m_train, up_pipe: tp.Pipeline, *,
                   loss_of, meta: FlatMeta, fed: FederatedConfig, up_key=None,
                   mu0=None):
    """One client's local epoch(s). cbatch leaves: (local_steps, local_bs, ...).
    Returns (upload message values, up_nnz, mean loss, final momentum).
    `mu0` is the client's persistent momentum row (population runs,
    docs/scale.md); None starts from zeros — the stateless-cohort default,
    whose trace is unchanged (the final momentum is already a scan carry,
    so returning it costs nothing and is dead-code-eliminated when the
    caller drops it)."""

    def grad_step(carry, mb):
        flat, mu = carry
        loss, g = jax.value_and_grad(lambda f: loss_of(meta.unflatten(f), mb))(flat)
        if m_train is not None:
            g = g * m_train
        mu = fed.client_momentum * mu + g
        flat = flat - fed.client_lr * mu
        return (flat, mu), loss

    mu0 = jnp.zeros_like(flat0) if mu0 is None else mu0
    (flatT, muT), losses = jax.lax.scan(grad_step, (flat0, mu0), cbatch)
    delta = flat0 - flatT                                     # pseudo-gradient sign
    with jax.named_scope("fedround.upload"):
        msg = up_pipe(delta, key=up_key)
    return msg.values, msg.nnz, jnp.mean(losses), muT


def _share_or_stack(items):
    """(value, vmap in_axis): identical plan fields become a broadcast
    operand; client-varying fields are stacked on the vmapped axis."""
    if all(it is items[0] for it in items):
        return items[0], None
    return jnp.stack(items), 0


def _keep_count(p_len: int, density: float) -> int:
    return sp.density_count(p_len, density)


def _run_clients(P_base, plans, client_batches, s: st.StrategySpec, *,
                 loss_of: _BoundLoss, meta: FlatMeta, fed: FederatedConfig,
                 kdown=None, upkeys=None, ax_key=None, spmd_axis_name=None,
                 round_idx=None, client_mu=None):
    """Stack per-client `RoundPlan`s onto the vmapped client axis and run
    every client's local update through the transport pipelines.

    This is the client block of `federated_round`, shared verbatim with the
    async engine's `make_client_phase_fn` so both execution paths trace the
    exact same per-client computation (the basis of the AsyncEngine
    sync-equivalence guarantee).

    Returns ((deltas, up_nnzs, losses, down_nnzs), (m_down_cs, ax_down)) —
    the second pair is the stacked download mask and its vmap axis, which
    the caller needs for the shared-vs-per-client download accounting.

    `client_mu` (k, p_len) threads each client's persistent momentum row
    through the local update (population runs); the output tuple then
    grows a fifth element with the final rows.  None (default) keeps the
    stateless zeros-init trace byte-identical.
    """
    # --- stack the plans onto the client axis -----------------------------
    m_down_cs, ax_down = _share_or_stack([p.m_down for p in plans])
    trains = [p.m_train for p in plans]
    if all(t is None for t in trains):
        m_train_cs, ax_train = None, None
    else:
        trains = [jnp.ones((meta.p_len,), bool) if t is None else t
                  for t in trains]
        m_train_cs, ax_train = _share_or_stack(trains)

    up_modes = {p.upload.mode for p in plans}
    assert len(up_modes) == 1, f"mixed upload modes unsupported: {up_modes}"
    up_mode = up_modes.pop()
    up_counts = None
    if up_mode == "fixed":
        up_cs, ax_up = _share_or_stack([p.upload.mask for p in plans])
    else:
        densities = [p.upload.density for p in plans]
        if len(set(densities)) == 1:            # uniform density: static Top-K
            up_cs, ax_up = None, None
        else:                                   # per-client keep-counts
            up_counts = jnp.asarray(
                [_keep_count(meta.p_len, d) for d in densities], jnp.int32)
            up_cs, ax_up = up_counts, 0

    # the traced round index folds into random-mode projections so the
    # compressed subspace rotates across rounds (transport.lowrank_stage)
    lr_down = tp.lowrank_stage(s, "down", fold=round_idx)
    lr_up = tp.lowrank_stage(s, "up", fold=round_idx)

    def one_client(m_dn, m_tr, up_arg, cb, kup, mu):
        down = tp.download_pipeline(m_dn, s.quant_bits_down,
                                    lowrank=lr_down)(P_base, key=kdown)
        if up_mode == "fixed":
            rule = st.UploadRule.fixed(up_arg)
            pipe = tp.upload_pipeline(rule, s.quant_bits_up,
                                      selector=s.selector, lowrank=lr_up)
        elif up_counts is None:
            pipe = tp.upload_pipeline(plans[0].upload, s.quant_bits_up,
                                      selector=s.selector, lowrank=lr_up)
        else:
            pipe = tp.upload_pipeline(plans[0].upload, s.quant_bits_up,
                                      selector=s.selector, count=up_arg,
                                      lowrank=lr_up)
        values, nnz, loss, muT = _client_update(
            down.values, cb, m_tr, pipe, loss_of=loss_of, meta=meta, fed=fed,
            up_key=kup, mu0=mu)
        if client_mu is None:
            return values, nnz, loss, down.nnz
        return values, nnz, loss, down.nnz, muT

    ax_mu = None if client_mu is None else 0
    out = jax.vmap(
        one_client, in_axes=(ax_down, ax_train, ax_up, 0, ax_key, ax_mu),
        spmd_axis_name=spmd_axis_name)(
        m_down_cs, m_train_cs, up_cs, client_batches, upkeys, client_mu)
    return out, (m_down_cs, ax_down)


def _aggregate_uploads(strat: st.Strategy, deltas, ctx):
    """`Strategy.aggregate`, or the sparse aggregation's sum when the
    strategy opts in (`StrategySpec.sparse_aggregate`).

    The uploads arrive here as one dense-embedded (C, p_len) stack that
    is already on the device, so the sparse path does not pack them: it
    folds the rows left to right from +0.0 (one `fori_loop`, so the
    program's size does not grow with the cohort) and scales by 1/C.
    Per coordinate that adds the nonzero values in row order, and adding
    an exact zero changes no f32 sum, so the result is bit-equal to
    `Strategy.aggregate_sparse` over the packed rows — the flat
    scatter-add and the `edge_shards` tree alike — which the AsyncEngine
    runs on jobs it carries in packed form.  A row whose nonzero support
    exceeds the pack capacity (pathological threshold ties) flips the
    whole round to the dense rule via `jnp.where`, exactly as a packed
    row that overflows does.  Capacity gating is static
    (`strategies.sparse_aggregate_capacity`): unsupported specs compile
    the unmodified dense aggregation, byte for byte.
    """
    cap = st.sparse_aggregate_capacity(strat, ctx.p_len)
    if cap == 0:
        return strat.aggregate(deltas, ctx)
    n = deltas.shape[0]
    acc = jax.lax.fori_loop(0, n, lambda c, acc: acc + deltas[c],
                            jnp.zeros((ctx.p_len,), jnp.float32))
    overflow = jnp.any(jnp.sum(deltas != 0, axis=1) > cap)
    return jnp.where(overflow, strat.aggregate(deltas, ctx), acc / n)


def _bind_backbone(loss_of: LossFn, params) -> _BoundLoss:
    """`loss_of` with the frozen backbone bound, for the client block.

    ZeRO-3 semantics: the backbone is *stored* sharded between rounds (the
    jit's FSDP/TP in_shardings) and gathered to full replicas at use, so
    every client's forward/backward computes on local full weights — which
    also keeps the sharded program bit-identical to the single-device one
    (no-op without an activation_sharding context, i.e. on SimEngine and
    the AsyncEngine)."""
    from repro.launch.shardings import gather_replicated
    return functools.partial(loss_of, gather_replicated(params))


def _server_update(strat: st.Strategy, fed: FederatedConfig, flatP,
                   server_state, sstate, pseudo_grad, *, P_base, m_down,
                   round_idx, ctx):
    """The server tail of a round: FedAdam, or the FedAvg/FedSGD rule
    (paper Appendix A: W <- W - lr * mean(delta)), on the aggregated
    pseudo-gradient, then the strategy's `post_round` hook.  The
    synchronous round and the split round's server phase both end here,
    which is what keeps the sim == async anchor bit-exact.

    Returns (flatP', server_state', sstate')."""
    if fed.server_opt == "adam":
        flatP, opt = adam_update(flatP, pseudo_grad, server_state["opt"],
                                 fed.server_lr, fed.adam_b1, fed.adam_b2,
                                 fed.adam_eps)
    else:
        flatP = flatP - fed.server_lr * pseudo_grad
        opt = server_state["opt"]
    sstate, flatP = st.call_post_round(strat, sstate, flatP, P_base=P_base,
                                       m_down=m_down, round_idx=round_idx,
                                       ctx=ctx)
    return flatP, {"opt": opt, "round": round_idx + 1}, sstate


def federated_round(params, flatP, server_state, sstate, client_batches, rng,
                    *, loss_of: LossFn, meta: FlatMeta, fed: FederatedConfig,
                    strategy: st.StrategyLike, spmd_axis_name=None,
                    client_mu=None):
    """One round. client_batches leaves: (n_clients, local_steps, local_bs, ...).

    `params` is the frozen backbone pytree, which `loss_of(params, tree,
    mb)` reads.  It must be a *traced argument* of the enclosing jit, so
    FSDP/TP in_shardings apply to the backbone instead of baking it in as
    a replicated constant (docs/engines.md); a task with no backbone
    passes `{}`.  `strategy` accepts a `Strategy` instance, a
    `StrategySpec`, or a kind string.  `spmd_axis_name` (e.g. ('data',)
    or ('pod','data')) shards the vmapped client axis across the mesh in
    the production lowering.
    Returns (flatP', server_state', sstate', metrics).

    `client_mu` (n_clients, p_len) threads the cohort's persistent
    per-client momentum rows (population runs, docs/scale.md); the final
    rows come back as `metrics["client_mu"]` for the engine to scatter
    into the `federated.population` store.  None (the default) keeps the
    stateless zeros-init trace unchanged.
    """
    strat = st.resolve(strategy)
    loss = _bind_backbone(loss_of, params)
    s = strat.spec
    round_idx = server_state["round"]
    n_clients = jax.tree.leaves(client_batches)[0].shape[0]

    # the download (server mask, base, plans) and the vmapped client block
    # share one named scope; the device-trace readers attribute time by it
    with jax.named_scope("fedround.clients"):
        m_down_global = strat.download_mask(flatP, sstate, round_idx)
        P_base = strat.download_base(flatP, sstate)
        ctx = meta.plan_context(n_clients, round_idx=round_idx)
        plans = [strat.client_plan(m_down_global, c, ctx)
                 for c in range(n_clients)]

        # --- per-message quantization keys (stochastic rounding) ----------
        use_keys = rng is not None and (s.quant_bits_up or s.quant_bits_down)
        qkeys = jax.random.split(rng, n_clients + 1) if use_keys else None
        kdown = qkeys[-1] if use_keys else None  # shared: one broadcast message
        upkeys, ax_key = (qkeys[:-1], 0) if use_keys else (None, None)

        out, (m_down_cs, ax_down) = _run_clients(
            P_base, plans, client_batches, s, loss_of=loss, meta=meta,
            fed=fed, kdown=kdown, upkeys=upkeys, ax_key=ax_key,
            spmd_axis_name=spmd_axis_name, round_idx=round_idx,
            client_mu=client_mu)
        if client_mu is None:
            (deltas, nnzs, losses, down_nnzs), mu_out = out, None
        else:
            deltas, nnzs, losses, down_nnzs, mu_out = out

        lr_down = tp.lowrank_stage(s, "down")
        if lr_down is not None and lr_down.active(meta.p_len):
            # low-rank download: every message is the factor matrices, not
            # the masked support — bill what the transport transmitted
            down_nnz = jnp.mean(down_nnzs)
        elif ax_down is None:   # shared mask: bill the global mask support
            down_nnz = jnp.sum(jnp.asarray(m_down_cs).astype(jnp.float32))
        else:                   # per-client masks: average per-client size
            down_nnz = jnp.mean(down_nnzs)

    # --- aggregate + server update ----------------------------------------
    with jax.named_scope("fedround.aggregate"):
        if spmd_axis_name:
            # all-gather the (n_clients, p_len) deltas before aggregation so
            # the cross-client reduce runs replicated in program order — a
            # partitioner-chosen all-reduce over the sharded client axis
            # picks its own association, off the single-device result by
            # an ulp (no-op without an activation_sharding context)
            from repro.launch.shardings import gather_replicated
            deltas = gather_replicated(deltas)
        if fed.dp_clip > 0.0:
            # DP noise calibration assumes uniform averaging; refuse to
            # silently drop a strategy's weighted aggregation rather than
            # mis-account it
            if not strat.uniform_aggregation:
                raise NotImplementedError(
                    f"{strat.kind}: non-uniform Strategy.aggregate is "
                    "unsupported with DP clipping (dp_clip > 0)")
            # the fallback key must still rotate with the round: a bare
            # key(0) replays the identical noise draw every round, which is
            # not DP — it is a fixed bias the server optimizer learns around
            key = (rng if rng is not None
                   else jax.random.fold_in(jax.random.key(0), round_idx))
            pseudo_grad, _ = dp_mod.dp_aggregate(deltas, fed.dp_clip,
                                                 fed.dp_noise, key)
        else:
            pseudo_grad = _aggregate_uploads(strat, deltas, ctx)

    with jax.named_scope("fedround.server_update"):
        flatP, server_state, sstate = _server_update(
            strat, fed, flatP, server_state, sstate, pseudo_grad,
            P_base=P_base, m_down=m_down_global, round_idx=round_idx, ctx=ctx)

        metrics = {
            "loss": jnp.mean(losses),
            "down_nnz": down_nnz,
            "up_nnz": jnp.sum(nnzs),
            "grad_norm": jnp.linalg.norm(pseudo_grad),
            # per-message sizes for the ledger's per-message index/bitmap
            # coding
            "down_nnz_clients": down_nnzs,
            "up_nnz_clients": nnzs,
            # per-client losses: engines derive the *recorded* loss from
            # these on the host (fused device reductions are
            # association-dependent per program, so their scalars differ
            # across engine backends)
            "loss_clients": losses,
        }
    if mu_out is not None:
        metrics["client_mu"] = mu_out
    return flatP, server_state, sstate, metrics


def make_round_fn(loss_of: LossFn, meta: FlatMeta, fed: FederatedConfig,
                  strategy: st.StrategyLike, spmd_axis_name=None):
    """jit-ready closure over the static pieces; `strategy` may be a
    Strategy, StrategySpec, or kind string.  The returned function takes
    the frozen backbone as its leading argument —

        pfn(params, flatP, server_state, sstate, client_batches, rng,
            client_mu=None) -> (flatP', server_state', sstate', metrics)

    — so a jit over it can shard (FSDP/TP) and audit the backbone like
    any other operand instead of replicating it as a baked-in constant.

    `client_mu` is the population round's operand (docs/scale.md): the
    (cohort, p_len) gather of the sampled clients' persistent momentum
    rows, which the engine stages from the `federated.population` store;
    the post-round rows ride back in `metrics["client_mu"]` for the
    scatter commit.  Left out, the traced program is the plain
    stateless round; a cohort whose rows are all zeros computes the
    plain round's values bit for bit.
    """
    strat = st.resolve(strategy)

    def pfn(params, flatP, server_state, sstate, client_batches, rng,
            client_mu=None):
        return federated_round(params, flatP, server_state, sstate,
                               client_batches, rng, loss_of=loss_of,
                               meta=meta, fed=fed, strategy=strat,
                               spmd_axis_name=spmd_axis_name,
                               client_mu=client_mu)
    return pfn


def make_scanned_round_fn(round_fn):
    """Scan-chunked round driver: runs `round_fn` (a `make_round_fn`
    result) over a leading rounds axis in one device call, amortizing host
    dispatch (ShardedEngine's `rounds_per_call`).

    The returned function takes (params, flatP, server, sstate, batches,
    round_ids, base_key) where every `batches` leaf has an extra leading
    rounds axis, `round_ids` is the (k,) int32 vector of global round
    indices, and each round's rng is derived as fold_in(base_key,
    round_id) — bit-identical to the per-round driver's key schedule.
    Metrics come back stacked along the rounds axis.  The backbone is
    scan-invariant — it enters the loop as a constant carry-free operand,
    so the k chunked rounds reuse one sharded copy instead of
    re-transferring it per round (the dispatch-savings scan
    `benchmarks/sharded_bench.py` measures).
    """

    def scan_rounds(params, flatP, server_state, sstate, batches, round_ids,
                    base_key):
        def body(carry, xs):
            flatP, server_state, sstate = carry
            cb, rid = xs
            key = jax.random.fold_in(base_key, rid)
            flatP, server_state, sstate, m = round_fn(
                params, flatP, server_state, sstate, cb, key)
            return (flatP, server_state, sstate), m

        (flatP, server_state, sstate), metrics = jax.lax.scan(
            body, (flatP, server_state, sstate), (batches, round_ids))
        return flatP, server_state, sstate, metrics

    return scan_rounds


# ---------------------------------------------------------------------------
# split-phase round (AsyncEngine): client compute and the server update are
# separate device calls, so clients can run against stale server snapshots
# and the server can aggregate a buffer of updates from mixed versions.
# ---------------------------------------------------------------------------

def make_client_phase_fn(loss_of: LossFn, meta: FlatMeta, fed: FederatedConfig,
                         strategy: st.StrategyLike, slots: Tuple[int, ...],
                         repeats: Optional[Tuple[int, ...]] = None,
                         pack_cap: Optional[int] = None):
    """Client side of the split round: run the cohort slots in `slots`
    (a static tuple of global client indices) against one server snapshot.

    The returned function takes the frozen backbone first, like
    `make_round_fn`'s —

        fn(params, flatP, sstate, round_idx, client_batches, rng)
            -> (deltas, up_nnzs, losses, down_nnzs)

    or, with `pack_cap` set (the AsyncEngine sparse-aggregation path),

        fn(...) -> (deltas, up_nnzs, losses, down_nnzs, idx, val, pnnz)

    where (idx, val, pnnz) are each delta row packed to `pack_cap` coded
    (index, value) slots by the in-kernel batched pack
    (`fused_transport.pack_values_batch`) — the engine
    bulk-transfers the packed pair (O(cap) per job instead of O(p_len))
    and pulls a dense row only for the rare message whose support
    overflows the capacity (pnnz > pack_cap).

    `client_batches` leaves are shaped (len(slots), local_steps,
    local_bs, ...).  It traces exactly the download-mask / plan-stacking /
    vmapped-client block of `federated_round` via `_run_clients`, and the
    quantization key schedule splits `rng` into the *full cohort's*
    n_clients+1 keys before selecting this call's rows — so with
    slots == (0..n_clients-1) the computation is bit-compatible with one
    synchronous round's client block (the AsyncEngine equivalence anchor).

    `repeats[i]` > 0 marks slot i's repeat-th job against the *same*
    server version (possible when the buffer is smaller than the
    concurrency); its quantization key is folded once more per repeat so
    stochastic rounding never reuses randomness.
    """
    strat = st.resolve(strategy)
    s = strat.spec
    repeats = tuple(repeats) if repeats is not None else (0,) * len(slots)
    assert len(repeats) == len(slots), (slots, repeats)

    def phase(params, flatP, sstate, round_idx, client_batches, rng):
        loss = _bind_backbone(loss_of, params)
        with jax.named_scope("fedround.clients"):
            m_down_global = strat.download_mask(flatP, sstate, round_idx)
            P_base = strat.download_base(flatP, sstate)
            ctx = meta.plan_context(fed.n_clients, round_idx=round_idx)
            plans = [strat.client_plan(m_down_global, c, ctx) for c in slots]

            use_keys = rng is not None and (s.quant_bits_up
                                            or s.quant_bits_down)
            if use_keys:
                qkeys = jax.random.split(rng, fed.n_clients + 1)
                kdown = qkeys[-1]
                ups = [qkeys[c] if rep == 0
                       else jax.random.fold_in(qkeys[c], rep)
                       for c, rep in zip(slots, repeats)]
                upkeys, ax_key = jnp.stack(ups), 0
            else:
                kdown, upkeys, ax_key = None, None, None

            (deltas, nnzs, losses, down_nnzs), _ = _run_clients(
                P_base, plans, client_batches, s, loss_of=loss, meta=meta,
                fed=fed, kdown=kdown, upkeys=upkeys, ax_key=ax_key,
                round_idx=round_idx)
        if pack_cap:
            # the packed jobs' pack; the synchronous round folds its
            # dense rows instead (`_aggregate_uploads`), to the same bits
            with jax.named_scope("fedround.aggregate"):
                idx, val, pnnz = ft.pack_values_batch(deltas, pack_cap)
            return deltas, nnzs, losses, down_nnzs, idx, val, pnnz
        return deltas, nnzs, losses, down_nnzs
    return phase


def make_server_phase_fn(meta: FlatMeta, fed: FederatedConfig,
                         strategy: st.StrategyLike, *, sparse: bool = False,
                         cohort_slots: Optional[Tuple[int, ...]] = None):
    """Server side of the split round: one buffered aggregation event (the
    aggregate / server-opt / `post_round` tail of `federated_round`).

    The returned function has signature

        fn(flatP, server_state, sstate, deltas, weights)
            -> (flatP', server_state', sstate')

    where `deltas` (k, p_len) are the buffered upload messages and
    `weights` (k,) their staleness discounts.  Each delta is scaled by its
    weight *before* `Strategy.aggregate`, so every registered strategy's
    aggregation rule runs unmodified — and since `x * 1.0` is an IEEE
    identity, all-ones weights reduce bit-exactly to the synchronous
    update.  `post_round` sees the download mask/base recomputed from the
    pre-update server snapshot, which is what the synchronous round hands
    it when the buffer is one full fresh cohort.

    With `sparse=True` (only valid when `strategies.
    supports_sparse_aggregate` holds) the delta operand is the packed
    pair the sparse-aggregation client phase produced —

        fn(flatP, server_state, sstate, idx, val, weights)

    with (k, cap) index/value rows — and the pseudo-gradient comes from
    `Strategy.aggregate_sparse` (one scatter-add, no densify).  Weights
    scale the packed values exactly like the dense path, so all-ones
    weights stay an IEEE identity and the synchronous sparse round is
    reproduced bit for bit.

    DP aggregation (fed.dp_clip > 0) is noise-calibrated for one uniform
    synchronous cohort and is refused by the AsyncEngine before this
    function is ever built.

    `cohort_slots` (static tuple) records which client slots the buffered
    rows came from, in row order — the AsyncEngine passes the buffer's
    job slots when the buffer is not one full fresh cohort, so
    coverage-weighted aggregation (hetlora_weighted) scales each entry by
    the rank slices actually present instead of assuming the full cohort.
    None (the sync-equivalence default) leaves the full-cohort context —
    and the compiled program — untouched.
    """
    strat = st.resolve(strategy)
    assert not sparse or st.supports_sparse_aggregate(strat), strat

    def fn(flatP, server_state, sstate, *rest):
        round_idx = server_state["round"]
        with jax.named_scope("fedround.server_update"):    # for post_round
            m_down = strat.download_mask(flatP, sstate, round_idx)
            P_base = strat.download_base(flatP, sstate)
        ctx = meta.plan_context(fed.n_clients, round_idx=round_idx,
                                cohort_slots=cohort_slots)
        with jax.named_scope("fedround.aggregate"):
            if sparse:
                idx, val, weights = rest
                pseudo_grad = strat.aggregate_sparse(
                    idx, val * weights[:, None], ctx)
            else:
                deltas, weights = rest
                pseudo_grad = strat.aggregate(deltas * weights[:, None], ctx)

        with jax.named_scope("fedround.server_update"):
            return _server_update(strat, fed, flatP, server_state, sstate,
                                  pseudo_grad, P_base=P_base, m_down=m_down,
                                  round_idx=round_idx, ctx=ctx)
    return fn

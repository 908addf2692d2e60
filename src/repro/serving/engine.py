"""The serving loop: batched prefill + grouped-adapter continuous decode.

One backbone, many adapters.  Each admitted request is prefilled alone
(B=1, exact prompt length — jit retraces once per prompt bucket) with
its client's adapter sliced out of the device pool via
`cache.page_lora`, and its KV cache is scattered into the lane slot of
the persistent batch cache.  Decode then runs all lanes as one batch:
per-lane positions go in as a `(B,)` pos vector and per-lane adapters as
a paged lora tree (`cache.paged_lora`), which `models.layers.linear`
routes through the grouped-kernel registry in `kernels.lora_matmul` —
one fused gather+matmul applying a different client's A/B factors to
every row.

Idle lanes keep decoding against page 0 with their stale position; their
outputs are discarded and their cache slots overwritten at the next
admission, so no masking or batch compaction is ever needed and the
decode computation stays a single fixed shape.

Sampling is greedy (argmax) — deterministic given the trace seed, which
is what the parity tests pin against the per-request single-adapter
reference path.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.spans import gc_spans
from repro.models import model as mdl
from repro.models.layers import spec_to_shape_dtype
from repro.serving.cache import PagedAdapterCache, page_lora, paged_lora
from repro.serving.scheduler import ContinuousBatchingScheduler
from repro.serving.trace import Request


@dataclasses.dataclass
class ServingReport:
    """What a serving run produced and what it cost."""
    completions: Dict[int, List[int]]   # rid -> generated token ids
    requests: int
    steps: int                          # decode steps executed
    prefills: int
    decode_tokens: int                  # tokens produced by decode steps
    generated_tokens: int               # decode_tokens + one per prefill
    wall_s: float
    tokens_per_s: float                 # generated_tokens / wall_s
    mean_occupancy: float               # active lanes per decode step
    stalls: int                         # admissions blocked on pinned cache
    cache: Dict[str, float]             # PagedAdapterCache.stats()


class ServingEngine:
    """Continuous-batching serving over a paged adapter cache.

    `run(trace)` drives the full loop: virtual arrivals -> FIFO admission
    (pinning adapter pages) -> per-request prefill into a lane slot ->
    batched multi-adapter decode -> retirement.  Host state is three
    small numpy arrays (current token, position, page index per lane);
    everything heavy stays on device.
    """

    def __init__(self, params, cfg, cache: PagedAdapterCache, *,
                 n_lanes: int = 4, lora_scale: float = 1.0,
                 max_len: int = 64, window: Optional[int] = None,
                 step_dt: float = 0.25):
        assert cfg.num_classes == 0 and not cfg.encoder_decoder \
            and not cfg.embed_inputs, \
            "serving requires a causal token LM architecture"
        assert n_lanes >= 1 and max_len >= 2, (n_lanes, max_len)
        self.params = params
        self.cfg = cfg
        self.cache = cache
        self.n_lanes = n_lanes
        self.lora_scale = lora_scale
        self.max_len = max_len
        self.window = window
        self.step_dt = step_dt
        shapes = spec_to_shape_dtype(
            mdl.cache_spec(cfg, n_lanes, max_len, window))
        self._zero_cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), shapes)
        # jit retraces _prefill once per prompt-length bucket; the trace
        # generator draws lengths from a small bucket set to bound that.
        # The backbone is an argument, not a closure capture: captured, it
        # would be baked into every executable as a constant (a second
        # copy on the device, and slower compiles).
        self._prefill = jax.jit(self._prefill_impl)
        self._decode = jax.jit(self._decode_impl)
        self._write_lane = jax.jit(self._write_lane_impl)

    # --- device closures ----------------------------------------------------
    def _prefill_impl(self, params, pool, page, tokens):
        lora = page_lora(pool, page)
        logits, row_cache = mdl.prefill(
            params, self.cfg, {"tokens": tokens}, lora=lora,
            lora_scale=self.lora_scale, window=self.window,
            max_len=self.max_len)
        return jnp.argmax(logits[0, -1]).astype(jnp.int32), row_cache

    def _write_lane_impl(self, batch_cache, row_cache, lane):
        # every cache leaf is (layers, B, ...): scatter row 0 into lane slot.
        return jax.tree.map(lambda bc, rc: bc.at[:, lane].set(rc[:, 0]),
                            batch_cache, row_cache)

    def _decode_impl(self, params, pool, batch_cache, tokens, pos, gidx):
        lora = paged_lora(pool, gidx)
        logits, new_cache = mdl.decode_step(
            params, self.cfg, tokens, pos, batch_cache, lora=lora,
            lora_scale=self.lora_scale, window=self.window)
        return jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32), new_cache

    # --- the loop -----------------------------------------------------------
    def run(self, trace: List[Request],
            max_steps: Optional[int] = None) -> ServingReport:
        for req in trace:
            assert req.prompt_len + req.gen_len <= self.max_len, (
                f"request {req.rid} needs {req.prompt_len + req.gen_len} "
                f"cache slots, engine has {self.max_len}")
        sched = ContinuousBatchingScheduler(trace, self.cache, self.n_lanes)
        batch_cache = self._zero_cache
        tokens = np.zeros(self.n_lanes, np.int32)
        pos = np.zeros(self.n_lanes, np.int32)
        gidx = np.zeros(self.n_lanes, np.int32)

        now = 0.0
        steps = prefills = decode_tokens = 0
        occupancy = 0
        t0 = time.perf_counter()
        with gc_spans():
            while not sched.done():
                if max_steps is not None and steps >= max_steps:
                    break
                with TraceAnnotation("serve.step") as step_span:
                    with TraceAnnotation("serve.admit"):
                        jump = sched.idle_jump()
                        if jump is not None:
                            now = max(now, jump)
                        sched.tick(now)
                        admitted = sched.admit()
                    for lane in admitted:
                        req = lane.request
                        with TraceAnnotation("serve.prefill"):
                            tok, row_cache = self._prefill(
                                self.params, self.cache.pool,
                                jnp.asarray(lane.page, jnp.int32),
                                jnp.asarray(
                                    np.asarray(req.prompt, np.int32)[None]))
                            batch_cache = self._write_lane(
                                batch_cache, row_cache,
                                jnp.asarray(lane.index, jnp.int32))
                        with TraceAnnotation("serve.sync"):
                            tok = int(tok)
                        li = lane.index
                        tokens[li] = tok
                        pos[li] = req.prompt_len
                        gidx[li] = lane.page
                        prefills += 1
                        # the prompt's last logits already yielded token #1.
                        sched.push_token(lane, tok)
                    active = [l for l in sched.lanes if l.active]
                    step_span.set_metadata(active=len(active),
                                           admitted=len(admitted))
                    if active:
                        with TraceAnnotation("serve.decode"):
                            out, batch_cache = self._decode(
                                self.params, self.cache.pool, batch_cache,
                                jnp.asarray(tokens), jnp.asarray(pos),
                                jnp.asarray(gidx))
                        with TraceAnnotation("serve.sync"):
                            out_host = np.asarray(out)
                        steps += 1
                        occupancy += len(active)
                        with TraceAnnotation("serve.push"):
                            for lane in active:
                                li = lane.index
                                tokens[li] = out_host[li]
                                pos[li] += 1
                                decode_tokens += 1
                                sched.push_token(lane, int(out_host[li]))
                    now += self.step_dt
        wall = time.perf_counter() - t0
        generated = decode_tokens + prefills
        return ServingReport(
            completions=dict(sched.completions), requests=len(trace),
            steps=steps, prefills=prefills, decode_tokens=decode_tokens,
            generated_tokens=generated, wall_s=wall,
            tokens_per_s=generated / wall if wall > 0 else 0.0,
            mean_occupancy=occupancy / steps if steps else 0.0,
            stalls=sched.stalls, cache=self.cache.stats())

"""Paged adapter cache: a fixed pool of device-resident adapter pages.

The training side produces one LoRA tree per client — far more clients
than fit on device.  `HostAdapterStore` is the spill tier (host numpy,
`checkpoint/io` npz snapshots on disk); `PagedAdapterCache` keeps a fixed
number of *pages* resident on device and admits/evicts whole adapters
LRU-keyed by client id, with pin counts protecting the adapters active
decode lanes are using.

Pool layout: every LoRA pair leaf gains a page axis at -3 —
'a' (lead..., G, d_in, r), 'b' (lead..., G, r, d_out) — so the leading
layer axis still scans and `paged_lora(pool, gidx)` turns the pool plus
per-lane page indices into the paged tree `models.layers.linear`
dispatches on.  Adapters whose rank is below the pool rank are zero-padded
(exact: the padded b rows are zero, so the extra rank components
contribute nothing to the delta).
"""
from __future__ import annotations

from collections import OrderedDict
from typing import Any, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.checkpoint import io as ckpt_io


def _is_pair(v) -> bool:
    return isinstance(v, dict) and {"a", "b"} <= set(v.keys())


def _map_pairs(tree, fn):
    """Apply fn to every {'a','b',...} pair node of a (nested-dict) lora
    tree, preserving the nesting."""
    out = {}
    for k, v in tree.items():
        out[k] = fn(v) if _is_pair(v) else _map_pairs(v, fn)
    return out


def paged_lora(pool, gidx):
    """Pool tree + per-row page indices (B,) -> the paged lora tree that
    `models.layers.linear` dispatches to the grouped-kernel registry.
    The gidx leaf is broadcast to each pair's leading (layer) dims so it
    scans alongside the stacked pool leaves."""
    gidx = jnp.asarray(gidx, jnp.int32)

    def pair(v):
        lead = v["a"].shape[:-3]
        return {"a": v["a"], "b": v["b"],
                "gidx": jnp.broadcast_to(gidx, lead + gidx.shape)}

    return _map_pairs(pool, pair)


def page_lora(pool, page):
    """Slice one page out of the pool -> a standard single-adapter lora
    tree (the per-request prefill path: prefill and decode read the SAME
    pool values, so a rank-padded adapter is served identically by both)."""
    return jax.tree.map(lambda leaf: leaf[..., page, :, :], pool)


def _pad_rank(pair: Dict[str, np.ndarray], rank: int) -> Dict[str, np.ndarray]:
    a, b = np.asarray(pair["a"]), np.asarray(pair["b"])
    r = a.shape[-1]
    if r > rank:
        raise ValueError(f"adapter rank {r} exceeds pool rank {rank}")
    if r < rank:
        a = np.concatenate(
            [a, np.zeros(a.shape[:-1] + (rank - r,), a.dtype)], axis=-1)
        b = np.concatenate(
            [b, np.zeros(b.shape[:-2] + (rank - r,) + b.shape[-1:], b.dtype)],
            axis=-2)
    return {"a": a, "b": b}


class HostAdapterStore:
    """Host-resident adapter library: client id -> LoRA tree (numpy
    leaves).  This is the spill target the device cache misses into, and
    the bridge to disk: snapshots round-trip through the same
    `checkpoint.io.save_pytree` npz format the training side writes."""

    def __init__(self):
        self._adapters: Dict[int, Any] = {}

    def put(self, client: int, lora) -> None:
        self._adapters[int(client)] = jax.tree.map(np.asarray, lora)

    def get(self, client: int):
        return self._adapters[int(client)]

    def __contains__(self, client) -> bool:
        return int(client) in self._adapters

    def __len__(self) -> int:
        return len(self._adapters)

    def clients(self):
        return sorted(self._adapters)

    # --- disk round-trip (training snapshot format) -------------------------
    def save(self, directory: str) -> None:
        import os
        os.makedirs(directory, exist_ok=True)
        for cid, lora in self._adapters.items():
            ckpt_io.save_pytree(lora,
                                os.path.join(directory, f"adapter_{cid}.npz"))

    @classmethod
    def load(cls, directory: str) -> "HostAdapterStore":
        import os
        import re
        store = cls()
        for name in sorted(os.listdir(directory)):
            m = re.fullmatch(r"adapter_(\d+)\.npz", name)
            if m:
                store._adapters[int(m.group(1))] = ckpt_io.load_pytree(
                    os.path.join(directory, name))
        return store


class PagedAdapterCache:
    """LRU admission/eviction of whole adapters over a fixed device pool.

    * `acquire(client)` — pin the client's page for an active lane,
      uploading from the host store on a miss (evicting the
      least-recently-used unpinned adapter when the pool is full).
      Returns the page index, or None when every page is pinned by other
      clients (admission blocks until a lane retires).
    * `release(client)` — drop one pin.
    * `stats()` — hits / misses / evictions / resident counters (the
      serving benchmark's cache-hit-rate column).

    The pool stays on device across uploads: a miss writes one page slot
    in place (`leaf.at[..., p, :, :].set`), it never re-uploads the pool.
    """

    def __init__(self, store: HostAdapterStore, template, pages: int,
                 rank: Optional[int] = None):
        """`template` is any adapter tree (or spec-shaped tree of arrays)
        defining the pool leaf shapes; `rank` overrides the pool rank
        (adapters of smaller rank are zero-padded on upload)."""
        assert pages >= 1, pages
        self.store = store
        self.pages = pages
        tmpl = jax.tree.map(np.asarray, template)

        def pool_pair(v):
            a, b = v["a"], v["b"]
            r = rank if rank is not None else a.shape[-1]
            return {
                "a": jnp.zeros(a.shape[:-2] + (pages, a.shape[-2], r), a.dtype),
                "b": jnp.zeros(b.shape[:-2] + (pages, r) + b.shape[-1:], b.dtype),
            }

        self.rank = rank if rank is not None else _first_pair_rank(tmpl)
        self.pool = _map_pairs(tmpl, pool_pair)
        self._lru: "OrderedDict[int, int]" = OrderedDict()   # client -> page
        self._pins: Dict[int, int] = {}                      # client -> count
        self._free = list(range(pages))
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    # --- internals ----------------------------------------------------------
    def _write_page(self, lora, page: int) -> None:
        def write(pool_leaf, new_leaf):
            return pool_leaf.at[..., page, :, :].set(
                jnp.asarray(new_leaf, pool_leaf.dtype))

        with TraceAnnotation("cache.write_page", page=page):
            padded = _map_pairs(lora, lambda v: _pad_rank(v, self.rank))
            self.pool = jax.tree.map(write, self.pool, padded)

    def _victim(self) -> Optional[int]:
        for cid in self._lru:                       # LRU order: oldest first
            if self._pins.get(cid, 0) == 0:
                return cid
        return None

    # --- the scheduler surface ----------------------------------------------
    def acquire(self, client: int) -> Optional[int]:
        client = int(client)
        if client in self._lru:
            self.hits += 1
            self._lru.move_to_end(client)
            self._pins[client] = self._pins.get(client, 0) + 1
            return self._lru[client]
        if self._free:
            page = self._free.pop()
        else:
            victim = self._victim()
            if victim is None:
                return None                          # every page is pinned
            page = self._lru.pop(victim)
            self._pins.pop(victim, None)
            self.evictions += 1
        self.misses += 1
        self._write_page(self.store.get(client), page)
        self._lru[client] = page
        self._pins[client] = 1
        return page

    def release(self, client: int) -> None:
        client = int(client)
        n = self._pins.get(client, 0)
        assert n > 0, f"release of unpinned client {client}"
        self._pins[client] = n - 1

    # --- introspection ------------------------------------------------------
    def resident(self) -> int:
        return len(self._lru)

    def page_of(self, client: int) -> Optional[int]:
        return self._lru.get(int(client))

    def stats(self) -> Dict[str, float]:
        total = self.hits + self.misses
        return {"pages": self.pages, "resident": self.resident(),
                "hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0}


def _first_pair_rank(tree) -> int:
    found = []

    def visit(t):
        for v in t.values():
            if _is_pair(v):
                found.append(np.asarray(v["a"]).shape[-1])
            elif isinstance(v, dict):
                visit(v)

    visit(tree)
    assert found, "template tree has no {'a','b'} LoRA pairs"
    return int(found[0])

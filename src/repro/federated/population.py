"""Million-client populations: host-resident client state, cohort
sampling, and double-buffered cohort prefetch.

The engines' per-client persistent state (today: the client momentum row
`client_mu`) used to be device-resident and sized to the cohort — which
caps the population at what fits on one device.  This module scales the
*population* three orders of magnitude past the *cohort*:

  * `PopulationStore` — chunked, lazily-materialized host numpy storage
    for one (row_len,) f32 row per client.  Chunks that were never
    written read back as zeros (a fresh client's momentum), so a 10^6
    client store costs O(touched clients), and its checkpoint payload —
    a `{"chunks": {str(chunk_idx): (chunk, row_len)}}` pytree — keeps
    every chunk a separate npz array, never one population-sized
    allocation (`checkpoint/io.save_pytree` '/'-joins nested keys).
  * `CohortSampler` registry (`uniform` / `fraction` / `availability`)
    — which clients form round r's cohort.  Samplers are stateless and
    deterministic per (config, seed, round): the same spec replays the
    same cohort sequence on a resumed run with no serialized state.
  * `CohortPrefetcher` — the double buffer: while round r computes on
    device, round r+1's cohort is sampled, gathered from the store, and
    staged host-to-device as ONE `jax.device_put` of the stacked rows
    (never a per-client transfer).  Staging ahead of round r's commit is
    only safe when the two cohorts are disjoint; an overlapping cohort
    stages its ids but defers the gather until after the commit (see
    `prefetch`), so prefetch-on is bit-identical to prefetch-off.
  * `Population` — the (store, sampler, prefetch) bundle a `RoundTask`
    carries; `Engine._run_population_rounds` drives it.

The cohort rides the existing round functions unchanged: sampled client
ids select *state rows* (and, in a deployment, the data shard); the
vmapped round still sees a (cohort, ...) batch, and
the `client_mu` operand of `fedround.make_round_fn` threads the gathered
rows through the client scan and returns the finals in
`metrics["client_mu"]` for the scatter-back.  See docs/scale.md.
"""
from __future__ import annotations

import dataclasses
import json
import time
from typing import Any, Dict, Optional, Tuple, Type, Union

import jax
import numpy as np

from repro.federated import async_clock as ac

# ---------------------------------------------------------------------------
# cohort samplers
# ---------------------------------------------------------------------------

_SAMPLERS: Dict[str, Type["CohortSampler"]] = {}


def register_sampler(name: str):
    """Class decorator: `@register_sampler("fraction")` makes the sampler
    reachable from `resolve_sampler("fraction", ...)`, Population specs,
    and the AsyncEngine `sampler=` kwarg."""
    def deco(cls: Type["CohortSampler"]) -> Type["CohortSampler"]:
        assert issubclass(cls, CohortSampler), cls
        cls.kind = name
        _SAMPLERS[name] = cls
        return cls
    return deco


def registered_samplers() -> Tuple[str, ...]:
    return tuple(sorted(_SAMPLERS))


class CohortSampler:
    """Deterministic cohort selection over a client population.

    `eligible(r)` -> (population,) bool mask of clients available in
    round r; `sample(r)` -> (cohort,) int64 ascending client ids drawn
    uniformly from the eligible set.  Both are pure functions of
    (config, seed, r) — there is no mutable state, so checkpoints carry
    only the config and a resumed run replays the identical sequence.

    Membership is decided by per-client random scores
    (`default_rng([seed, r])`), selected with `argpartition` — O(N) in
    the population, never a full sort — and returned in ascending id
    order, matching the slot order of a full synchronous cohort.
    """

    kind = "base"

    def __init__(self, population: int, cohort: Optional[int] = None,
                 seed: int = 0):
        assert population >= 1, population
        assert cohort is None or 1 <= cohort <= population, (cohort,
                                                             population)
        self.population = int(population)
        self.cohort = None if cohort is None else int(cohort)
        self.seed = int(seed)

    def eligible(self, round_idx: int) -> np.ndarray:
        return np.ones(self.population, bool)

    def sample(self, round_idx: int) -> np.ndarray:
        assert self.cohort is not None, \
            f"{self.kind}: construct with a cohort size to sample"
        elig = self.eligible(round_idx)
        n_elig = int(elig.sum())
        if n_elig < self.cohort:
            raise RuntimeError(
                f"{self.kind}: round {round_idx} has {n_elig} eligible "
                f"clients < cohort {self.cohort}")
        scores = np.random.default_rng(
            [self.seed, round_idx]).random(self.population)
        scores[~elig] = np.inf
        pick = np.argpartition(scores, self.cohort - 1)[:self.cohort]
        return np.sort(pick.astype(np.int64))

    def config(self) -> Dict[str, Any]:
        """JSON spec: `resolve_sampler(self.config(), population=N)`
        rebuilds an equivalent sampler (population comes from the
        context, not the spec)."""
        return {"kind": self.kind, "cohort": self.cohort, "seed": self.seed}


@register_sampler("uniform")
class UniformSampler(CohortSampler):
    """Every client eligible every round — uniform cohorts without
    replacement within a round (the classic FL sampling model)."""


@register_sampler("fraction")
class FractionSampler(CohortSampler):
    """Bernoulli participation: each round, client c is available with
    probability `participation`, independently per (seed, round, client).

    Availability uses its own rng stream (`[seed, round, 1]`), separate
    from the membership scores, so `participation=1.0` is bit-identical
    to `uniform` — the sync-equivalence anchor the engine tests pin."""

    def __init__(self, population: int, cohort: Optional[int] = None,
                 seed: int = 0, participation: float = 1.0):
        super().__init__(population, cohort, seed)
        assert 0.0 < participation <= 1.0, participation
        self.participation = float(participation)

    def eligible(self, round_idx: int) -> np.ndarray:
        if self.participation >= 1.0:
            return np.ones(self.population, bool)
        rng = np.random.default_rng([self.seed, round_idx, 1])
        return rng.random(self.population) < self.participation

    def config(self) -> Dict[str, Any]:
        return dict(super().config(), participation=self.participation)


@register_sampler("availability")
class AvailabilitySampler(CohortSampler):
    """Duty-cycle availability trace derived from a
    `ClientSystemProfile`: client c is on for a contiguous window of
    `w_c` rounds out of every `period`, phase-shifted by `c % period`.

    The window scales inversely with the client's speed factor —
    `w_c = clip(round(duty * period / speed_factor(c)), 1, period)` — so
    the slow devices of a heterogeneous profile (idle, plugged-in
    hardware) are available for more of the cycle while fast devices
    come and go, the diurnal pattern of real cross-device deployments.
    A uniform profile gives every client the same window and only the
    phases differ.

    Alternatively, `trace=<path>` replaces the synthetic duty-cycle model
    with a recorded on/off trace: an `.npz`/`.npy` (array under the key
    `"windows"`, or the file's first/only array) or a `.json` (a dict
    with a `"windows"` entry, or a bare nested list) holding an (N, T)
    0/1 matrix — N trace rows over a T-round cycle.  Client c follows
    row `c % N` and round r reads column `r % T`, so any population size
    replays the trace deterministically.  `config()` carries the *path*,
    not the matrix: checkpoints stay small, and a resumed run re-reads
    the file (moving/editing it between runs is on the operator, same as
    the dataset files)."""

    def __init__(self, population: int, cohort: Optional[int] = None,
                 seed: int = 0, period: int = 24, duty: float = 0.5,
                 profile: Any = None, trace: Optional[str] = None):
        super().__init__(population, cohort, seed)
        assert period >= 1, period
        assert 0.0 < duty <= 1.0, duty
        if isinstance(profile, dict):   # checkpoint meta round-trip
            profile = ac.ClientSystemProfile(
                **{k: tuple(v) if isinstance(v, list) else v
                   for k, v in profile.items()})
        self.period = int(period)
        self.duty = float(duty)
        self.profile = profile if profile is not None \
            else ac.ClientSystemProfile()
        self.trace = None if trace is None else str(trace)
        if self.trace is not None:
            windows = load_availability_trace(self.trace)
            rows = np.arange(self.population, dtype=np.int64) \
                % windows.shape[0]
            self._windows = windows[rows]           # (population, T)
            return
        self._windows = None
        factors = np.asarray(self.profile.speed_factors or (1.0,), float)
        f = factors[np.arange(self.population) % factors.size]
        self._window = np.clip(
            np.rint(self.duty * self.period / f).astype(np.int64),
            1, self.period)
        self._phase = np.arange(self.population, dtype=np.int64) \
            % self.period

    def eligible(self, round_idx: int) -> np.ndarray:
        if self._windows is not None:
            return self._windows[:, round_idx % self._windows.shape[1]]
        return ((round_idx - self._phase) % self.period) < self._window

    def config(self) -> Dict[str, Any]:
        return dict(super().config(), period=self.period, duty=self.duty,
                    profile=dataclasses.asdict(self.profile),
                    trace=self.trace)


def load_availability_trace(path: str) -> np.ndarray:
    """Read an (N, T) bool availability matrix from `path` (see
    `AvailabilitySampler`): npz (key `"windows"` preferred, else the
    first array in file order), npy, or json (`{"windows": [...]}` or a
    bare list of rows)."""
    if path.endswith((".npz", ".npy")):
        loaded = np.load(path)
        if isinstance(loaded, np.lib.npyio.NpzFile):
            with loaded:
                key = "windows" if "windows" in loaded.files \
                    else loaded.files[0]
                arr = loaded[key]
        else:
            arr = loaded
    else:
        with open(path) as f:
            obj = json.load(f)
        arr = np.asarray(obj["windows"] if isinstance(obj, dict) else obj)
    arr = np.asarray(arr)
    assert arr.ndim == 2 and arr.size, \
        f"availability trace {path}: need a non-empty (N, T) matrix, " \
        f"got shape {arr.shape}"
    return arr.astype(bool)


SamplerLike = Union["CohortSampler", str, Dict[str, Any],
                    Type["CohortSampler"]]


def resolve_sampler(obj: SamplerLike, *, population: int,
                    **kwargs) -> CohortSampler:
    """Sampler instance / registered name / config-dict spec / class ->
    instance.  A dict spec is a `config()` round-trip:
    `{"kind": "fraction", "participation": 0.3, ...}`."""
    if isinstance(obj, CohortSampler):
        assert not kwargs, "pass kwargs with a name/spec, not an instance"
        return obj
    if isinstance(obj, dict):
        spec = dict(obj)
        kind = spec.pop("kind")
        return resolve_sampler(kind, population=population,
                               **dict(spec, **kwargs))
    if isinstance(obj, str):
        try:
            cls = _SAMPLERS[obj]
        except KeyError:
            raise KeyError(f"no sampler registered as {obj!r}; known: "
                           f"{registered_samplers()}") from None
        return cls(population, **kwargs)
    if isinstance(obj, type) and issubclass(obj, CohortSampler):
        return obj(population, **kwargs)
    raise TypeError(f"cannot resolve {obj!r} to a CohortSampler")


# ---------------------------------------------------------------------------
# the host-resident store
# ---------------------------------------------------------------------------

class PopulationStore:
    """One (row_len,) f32 row of persistent state per client, chunked on
    the host.

    Rows live in fixed-size chunks (`chunk` clients each) that
    materialize on first write; a chunk never written reads back as
    zeros — exactly a fresh client's momentum — so memory and
    checkpoint size are O(clients ever in a cohort), not O(population).
    `gather`/`scatter` move whole cohorts with at most one allocation
    per touched chunk; nothing here touches a device (the engine's
    prefetcher owns the single H2D `device_put`)."""

    def __init__(self, population: int, row_len: int, chunk: int = 4096):
        assert population >= 1 and row_len >= 1 and chunk >= 1
        self.population = int(population)
        self.row_len = int(row_len)
        self.chunk = int(chunk)
        self._chunks: Dict[int, np.ndarray] = {}

    # --- cohort movement ---------------------------------------------------
    def gather(self, ids: np.ndarray) -> np.ndarray:
        """-> (len(ids), row_len) f32 copy of the rows for `ids`."""
        ids = self._check_ids(ids)
        out = np.zeros((ids.size, self.row_len), np.float32)
        cidx = ids // self.chunk
        for c in np.unique(cidx):
            buf = self._chunks.get(int(c))
            if buf is not None:
                sel = cidx == c
                out[sel] = buf[ids[sel] - c * self.chunk]
        return out

    def scatter(self, ids: np.ndarray, rows: np.ndarray) -> None:
        """Write `rows` back to `ids`, materializing chunks as needed."""
        ids = self._check_ids(ids)
        rows = np.asarray(rows, np.float32)
        assert rows.shape == (ids.size, self.row_len), (rows.shape,
                                                        (ids.size,
                                                         self.row_len))
        cidx = ids // self.chunk
        for c in np.unique(cidx):
            c = int(c)
            buf = self._chunks.get(c)
            if buf is None:
                rows_in_chunk = min(self.chunk,
                                    self.population - c * self.chunk)
                buf = np.zeros((rows_in_chunk, self.row_len), np.float32)
                self._chunks[c] = buf
            sel = cidx == c
            buf[ids[sel] - c * self.chunk] = rows[sel]

    # aliases matching the engine's vocabulary
    sample_cohort = gather
    commit_cohort = scatter

    def _check_ids(self, ids) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        assert ids.ndim == 1, ids.shape
        if ids.size:
            assert 0 <= ids.min() and ids.max() < self.population, \
                (int(ids.min()), int(ids.max()), self.population)
        return ids

    # --- sizing ------------------------------------------------------------
    @property
    def n_chunks(self) -> int:
        """Chunks materialized so far (ever-written clients / chunk)."""
        return len(self._chunks)

    @property
    def nbytes(self) -> int:
        return sum(  # reprolint: disable=host-reduction -- integer bytes
            b.nbytes for b in self._chunks.values())

    # --- checkpoint (de)serialization --------------------------------------
    def to_arrays(self) -> Dict[str, Any]:
        """Npz-ready pytree: each materialized chunk stays its own array
        (`save_pytree` '/'-joins the nested keys), so serializing a 10^6
        client store never builds a population-sized array.  Arrays are
        aliased, not copied — snapshot before the next `scatter`."""
        return {"chunks": {str(c): buf for c, buf in
                           sorted(self._chunks.items())}}

    def load_arrays(self, arrays: Dict[str, Any]) -> None:
        """Restore in place from a `to_arrays` pytree (checkpoint
        resume).  Missing "chunks" means an empty (all-fresh) store."""
        self._chunks = {}
        for key, buf in arrays.get("chunks", {}).items():
            buf = np.asarray(buf, np.float32)
            assert buf.shape[1] == self.row_len, (buf.shape, self.row_len)
            self._chunks[int(key)] = buf.copy()


class DevicePopulationStore:
    """Dense device-resident reference backend (one (population, row_len)
    jnp array) with the `PopulationStore` interface — the bit-equality
    anchor `tests/test_population.py` holds the chunked host store to.
    Only viable at test scale; the host store is the production path."""

    def __init__(self, population: int, row_len: int):
        import jax.numpy as jnp
        self.population = int(population)
        self.row_len = int(row_len)
        self._arr = jnp.zeros((population, row_len), jnp.float32)

    def gather(self, ids: np.ndarray) -> np.ndarray:
        ids = np.asarray(ids, np.int64)
        return np.asarray(self._arr[ids], np.float32)

    def scatter(self, ids: np.ndarray, rows: np.ndarray) -> None:
        ids = np.asarray(ids, np.int64)
        self._arr = self._arr.at[ids].set(
            np.asarray(rows, np.float32))

    sample_cohort = gather
    commit_cohort = scatter

    def to_arrays(self) -> Dict[str, Any]:
        return {"dense": np.asarray(self._arr)}

    def load_arrays(self, arrays: Dict[str, Any]) -> None:
        import jax.numpy as jnp
        self._arr = jnp.asarray(arrays["dense"], jnp.float32)


# ---------------------------------------------------------------------------
# double-buffered prefetch
# ---------------------------------------------------------------------------

class CohortPrefetcher:
    """Stages round r+1's cohort while round r computes on device.

    `prefetch(r, exclude=ids_r)` runs between the engine's async round-r
    dispatch and its blocking device pull: it samples round r+1's ids
    and — when they are disjoint from the still-uncommitted round-r
    cohort — gathers the host rows and issues the single H2D
    `jax.device_put` immediately, overlapping the transfer with device
    compute.  An overlapping cohort would read rows round r is about to
    rewrite, so only the ids are staged and `take(r+1)` (which the
    engine calls after `commit_cohort`) finishes the gather then:
    prefetch changes *when* rows move, never *which values* — the
    prefetch-on == prefetch-off anchor."""

    def __init__(self, store, sampler: CohortSampler):
        self.store = store
        self.sampler = sampler
        self._staged: Optional[Tuple[int, np.ndarray, Any]] = None
        # instrumentation (benchmarks/population_bench.py): seconds the
        # engine's round loop spent blocked in take() — the staging cost
        # left on the critical path — and bulk H2D transfer count (the
        # one-device_put-per-cohort contract)
        self.take_wait_s = 0.0
        self.h2d_puts = 0

    def _put(self, rows: np.ndarray) -> Any:
        self.h2d_puts += 1
        return jax.device_put(rows)

    def prefetch(self, round_idx: int, exclude: np.ndarray) -> None:
        ids = self.sampler.sample(round_idx)
        if np.intersect1d(ids, np.asarray(exclude, np.int64)).size:
            rows = None     # stale-read hazard: defer gather to take()
        else:
            rows = self._put(self.store.gather(ids))
        self._staged = (round_idx, ids, rows)

    def take(self, round_idx: int) -> Tuple[np.ndarray, Any]:
        """-> (ids, device rows) for `round_idx`, using the staged buffer
        when it matches (cold path: sample + gather + put now)."""
        t0 = time.perf_counter()
        staged, self._staged = self._staged, None
        if staged is not None and staged[0] == round_idx:
            _, ids, rows = staged
        else:
            ids, rows = self.sampler.sample(round_idx), None
        if rows is None:
            rows = self._put(self.store.gather(ids))
        self.take_wait_s += time.perf_counter() - t0
        return ids, rows


# ---------------------------------------------------------------------------
# the bundle a RoundTask carries
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Population:
    """Everything the engine needs to run cohorts out of a client
    population larger than the device batch: the host store, the cohort
    sampler (whose `cohort` must equal `fed.n_clients` — the vmapped
    batch is still cohort-sized), and the prefetch switch."""

    store: Any                          # PopulationStore-shaped backend
    sampler: CohortSampler
    prefetch: bool = True
    # runtime handle the engine fills in: the round loop's prefetcher,
    # whose wait/H2D counters the benchmarks read.  Not configuration.
    last_prefetcher: Optional[CohortPrefetcher] = dataclasses.field(
        default=None, compare=False, repr=False)

    @property
    def population(self) -> int:
        return self.store.population

    def config(self) -> Dict[str, Any]:
        """JSON facets for checkpoint metadata; `Population.build` plus
        the store payload in `RunState.aux` rebuilds the bundle."""
        chunk = getattr(self.store, "chunk", 0)
        return {"population": self.store.population,
                "row_len": self.store.row_len,
                "chunk": chunk,
                "sampler": self.sampler.config(),
                "prefetch": self.prefetch}

    @classmethod
    def build(cls, population: int, row_len: int, *,
              cohort: Optional[int] = None, sampler: SamplerLike = "uniform",
              seed: int = 0, chunk: int = 4096, prefetch: bool = True,
              **sampler_kw) -> "Population":
        """The one-call constructor (`Experiment.with_population` wires
        it): `chunk=0` selects the dense `DevicePopulationStore` test
        backend."""
        store = (PopulationStore(population, row_len, chunk) if chunk
                 else DevicePopulationStore(population, row_len))
        if isinstance(sampler, (CohortSampler, dict)):
            # an instance or a config() spec already carries cohort/seed;
            # the defaults here must not override them
            samp = resolve_sampler(sampler, population=population,
                                   **sampler_kw)
        else:
            samp = resolve_sampler(sampler, population=population,
                                   cohort=cohort, seed=seed, **sampler_kw)
        return cls(store=store, sampler=samp, prefetch=prefetch)

    @classmethod
    def from_config(cls, cfg: Dict[str, Any]) -> "Population":
        """Rebuild from `config()` (checkpoint meta round-trip); the
        caller restores the store payload via `store.load_arrays`."""
        spec = dict(cfg["sampler"])
        return cls.build(int(cfg["population"]), int(cfg["row_len"]),
                         sampler=spec, chunk=int(cfg["chunk"]),
                         prefetch=bool(cfg["prefetch"]))

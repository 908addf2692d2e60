"""Plain reference of one synchronous federated round over a flat adapter
vector: FLASC (sparse download and upload, dense local training) and
dense federated LoRA (FedIT), each followed by FedAdam on the server.

Written from the algorithm, not from the program, and imports nothing of
it.  Per round r, with P the server's flat adapter vector:

    download  = P * topk(|P|, k_down)          (FLASC; all of P for dense)
    per client c, local_steps steps of SGD with momentum from `download`:
        g = grad loss(w, batch);  mu = momentum * mu + g;  w = w - lr * mu
    delta_c   = download - w
    upload_c  = topk(|delta_c|, k_up) * delta_c  (FLASC; delta_c for dense)
                then b-bit symmetric stochastic rounding of the whole
                message: s = max|upload| / (2^(b-1) - 1),
                q = clip(floor(upload / s + u), -2^(b-1), 2^(b-1) - 1) * s
    pseudo-gradient = mean_c upload_c
    P         = Adam(P, pseudo-gradient)

Top-k keeps exactly k = max(round(n * density), 1) entries by magnitude.
The stochastic-rounding draws `u` follow the documented key schedule of
the system under test, so that both sides round with the same numbers:
round key = fold_in(key(seed + 2), r), split into clients + 1 keys, client
c draws uniform(keys[c], (n,)).

A message's size is its count of kept entries (topk) or of nonzero
entries (dense); its wire bytes are the cheaper of index coding
(value + 4 B index per entry) and bitmap coding (values + n/8 B).
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np



@dataclasses.dataclass(frozen=True)
class Round:
    """One round's algorithm settings."""
    kind: str                       # "flasc" | "lora"
    density_down: float = 1.0
    density_up: float = 1.0
    quant_bits_up: int = 0
    local_steps: int = 1
    client_lr: float = 1e-3
    client_momentum: float = 0.9
    server_lr: float = 1e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8

    @classmethod
    def from_traffic(cls, tr: dict) -> "Round":
        s, o = tr["strategy"], tr["optimizer"]
        flasc = s["kind"] == "flasc"
        return cls(kind=s["kind"],
                   density_down=s.get("density_down", 1.0) if flasc else 1.0,
                   density_up=s.get("density_up", 1.0) if flasc else 1.0,
                   quant_bits_up=int(s.get("quant_bits_up", 0)),
                   local_steps=int(tr["cohort"]["local_steps"]),
                   client_lr=o["client_lr"],
                   client_momentum=o["client_momentum"],
                   server_lr=o["server_lr"], b1=o["adam_b1"],
                   b2=o["adam_b2"], eps=o["adam_eps"])


def keep_count(n: int, density: float) -> int:
    return n if density >= 1.0 else max(int(round(n * density)), 1)


def topk(x, k: int):
    """x with all but its k largest magnitudes zeroed, and the mask."""
    n = x.shape[-1]
    if k >= n:
        return x, jnp.ones(x.shape, bool)
    idx = jax.lax.top_k(jnp.abs(x), k)[1]
    mask = jnp.zeros(x.shape, bool).at[idx].set(True)
    return x * mask, mask


def stochastic_round(x, bits: int, u):
    qmax = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x)) / qmax, 1e-12)
    return jnp.clip(jnp.floor(x / s + u), -qmax - 1, qmax) * s


def coded_bytes(values: int, n: int, value_bytes: float) -> int:
    return int(min(values * (value_bytes + 4), values * value_bytes + n // 8))


def client_update(loss_fn: Callable, w0, batches, cfg: Round):
    """batches: (local_steps, ...) leaves -> (delta, mean loss)."""
    def step(carry, mb):
        w, mu = carry
        lo, g = jax.value_and_grad(loss_fn)(w, mb)
        mu = cfg.client_momentum * mu + g
        return (w - cfg.client_lr * mu, mu), lo

    (w, _), losses = jax.lax.scan(step, (w0, jnp.zeros_like(w0)), batches)
    return w0 - w, jnp.mean(losses)


def run_rounds(loss_fn: Callable, weights, P0, batches_of: Callable,
               cfg: Round, rounds: int, seed: int, clients: int,
               use_clients: int = 0) -> Dict[str, List]:
    """Drive `rounds` rounds from P0.  `loss_fn(weights, w, batch)` is the
    model's loss; `batches_of(r, c)` gives client c's (local_steps, ...)
    batch of round r.  `use_clients` < clients leaves
    the rest of the cohort out and averages over the first `use_clients`
    (a planted fault: half of the batch left out).  Clients run one after
    another, so memory holds one client's activations.

    Returns per round: the mean and the per-client losses, the per-client
    message sizes, and the parameters after the round; plus Adam's first
    moment after round 0."""
    n = P0.shape[0]
    k_down = keep_count(n, cfg.density_down)
    k_up = keep_count(n, cfg.density_up)
    used = use_clients or clients
    upd = jax.jit(lambda wts, w, b: client_update(
        lambda w_, mb: loss_fn(wts, w_, mb), w, b, cfg))

    @jax.jit
    def transform(delta, key):
        if cfg.kind == "flasc":
            msg, mask = topk(delta, k_up)
            nnz = jnp.sum(mask)
        else:
            msg, nnz = delta, jnp.sum(delta != 0)
        if cfg.quant_bits_up:
            msg = stochastic_round(msg, cfg.quant_bits_up,
                                   jax.random.uniform(key, (n,)))
        return msg, nnz

    @jax.jit
    def server(P, m, v, t, pg):
        m = cfg.b1 * m + (1 - cfg.b1) * pg
        v = cfg.b2 * v + (1 - cfg.b2) * pg * pg
        mh = m / (1 - cfg.b1 ** t)
        vh = v / (1 - cfg.b2 ** t)
        return P - cfg.server_lr * mh / (jnp.sqrt(vh) + cfg.eps), m, v

    P = P0
    m = jnp.zeros_like(P0)
    v = jnp.zeros_like(P0)
    out: Dict[str, List] = {"loss": [], "loss_clients": [], "down_nnz": [],
                            "up_nnz": [], "params": [], "m0": None}
    base = jax.random.key(seed + 2)
    for r in range(rounds):
        if cfg.kind == "flasc":
            down, dmask = topk(P, k_down)
            down_nnz = int(jnp.sum(dmask))
        else:
            down, down_nnz = P, n
        keys = jax.random.split(jax.random.fold_in(base, r), clients + 1)
        acc = jnp.zeros_like(P)
        losses, ups = [], []
        for c in range(used):
            delta, lo = upd(weights, down, batches_of(r, c))
            msg, nnz = transform(delta, keys[c])
            acc = acc + msg
            losses.append(float(lo))
            ups.append(int(nnz))
        pg = acc / used
        P, m, v = server(P, m, v, jnp.float32(r + 1), pg)
        if r == 0:
            out["m0"] = np.asarray(m)
        out["loss"].append(float(np.mean(np.asarray(losses, np.float64))))
        out["loss_clients"].append(losses)
        out["down_nnz"].append([down_nnz] * used)
        out["up_nnz"].append(ups)
        out["params"].append(np.asarray(P))
    return out

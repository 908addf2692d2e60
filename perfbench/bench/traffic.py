"""The one general generator of serving traffic: a traffic file's
parameters and the seed in, requests out.

Its shape follows the system's own `serving/trace.py` `synth_trace`
(Zipf tenant popularity, prompt lengths from a bucket set, a uniform
generation budget, one seeded `numpy` generator), copied here so that the
yardstick does not move with the program.  Sizes are stratified: every
chunk holds each prompt bucket equally often and generation budgets evenly
spread over the range, in an order drawn from the seed, so every seed asks
for the same work; tenants and prompt tokens are drawn from the seed.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def zipf_probs(n: int, a: float) -> np.ndarray:
    """Normalized Zipf pmf over ranks: p(i) ∝ 1/(i+1)^a."""
    p = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** a
    return p / p.sum()


def chunk(tr: dict, seed: int, index: int, vocab: int,
          rid0: int = 0) -> List[Dict]:
    """Requests of chunk `index`: dicts with rid, client, arrival,
    prompt_len, gen_len, prompt.  `arrivals: offline` puts every arrival
    of a chunk at 0."""
    if tr["arrivals"] != "offline":
        raise ValueError(f"unknown arrivals {tr['arrivals']!r}")
    n = int(tr["chunk_requests"])
    rng = np.random.default_rng([seed, index])
    plens = rng.permutation(np.resize(np.asarray(tr["prompt_buckets"]), n))
    lo, hi = tr["gen_range"]
    glens = rng.permutation(np.rint(np.linspace(lo, hi, n)).astype(int))
    clients = rng.choice(int(tr["tenants"]), size=n,
                         p=zipf_probs(int(tr["tenants"]), float(tr["zipf_a"])))
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, size=int(plens[i]))
        out.append({"rid": rid0 + i, "client": int(clients[i]),
                    "arrival": 0.0, "prompt_len": int(plens[i]),
                    "gen_len": int(glens[i]),
                    "prompt": tuple(int(x) for x in prompt)})
    return out

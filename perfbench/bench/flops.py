"""Operations and bytes the benchmark's ratios divide by, from shapes alone.

Model FLOPs count the work the algorithm needs, not what a program happens
to execute: a recomputed forward (`jax.checkpoint`) is not counted, and a
causal attention counts the half of its score matrix it uses.  `d` is the
sizes dict of a reference's `dims`.
"""
from __future__ import annotations

from references import federated


def layer_matmul_params(d: dict) -> int:
    """Weights one layer multiplies each token by: q, k, v, o and the MLP."""
    D, F = d["d_model"], d["d_ff"]
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    mlp = (3 if d["activation"] == "swiglu" else 2) * D * F
    return D * q + 2 * D * kv + q * D + mlp


def matmul_params(d: dict) -> int:
    """All layers plus the output head (the token table is a lookup)."""
    return d["layers"] * layer_matmul_params(d) + d["d_model"] * d["vocab"]


def lora_params(d: dict) -> int:
    """Adapter entries: rank x (d_in + d_out) per target per layer."""
    from references import dense_lm
    return dense_lm.lora_size(d)


def attention_flops(d: dict, seq_len: int) -> float:
    """Forward score and value products of one causal sequence over all
    layers: 2 x (2 S^2 H hd), halved for the causal mask."""
    return d["layers"] * 2.0 * seq_len * seq_len * d["heads"] * d["head_dim"]


def sequence_train_flops(d: dict, seq_len: int) -> float:
    """Forward plus the backward that adapter training needs, for one
    sequence.  Frozen weights need only the input gradient (2 per weight
    and token, as the forward); adapter weights need both gradients
    (2 + 4); attention's backward is twice its forward.  The head scores
    the S - 1 positions that have a next token."""
    S = seq_len
    N_layers = d["layers"] * layer_matmul_params(d)
    N_head = d["d_model"] * d["vocab"]
    fwd_bwd_frozen = 4.0 * (N_layers * S + N_head * (S - 1))
    adapter = 6.0 * lora_params(d) * S
    return fwd_bwd_frozen + adapter + 3.0 * attention_flops(d, S)


def train_round_flops(d: dict, cohort: dict) -> float:
    """Model FLOPs of one federated round: every client's local steps."""
    seqs = cohort["clients"] * cohort["sequences_per_client"] \
        * cohort["local_steps"]
    return seqs * sequence_train_flops(d, cohort["seq_len"])


def serve_token_flops(d: dict) -> float:
    """2 x matmul parameters per generated token (attention over the cache
    and the adapters left out, so the share this gives is a floor)."""
    return 2.0 * matmul_params(d)


def transform_bytes(n: int, clients: int, density_up: float,
                    bits: int) -> float:
    """Least HBM traffic of one round's upload transform: every client's
    float32 delta read once, and its message written once in the
    cheapest form that carries it: index coding, bitmap coding, or every
    value in order, at `bits` per value."""
    k = federated.keep_count(n, density_up)
    value_bytes = bits / 8.0 if bits else 4.0
    message = min(federated.coded_bytes(k, n, value_bytes), n * value_bytes)
    return clients * (4.0 * n + message)

"""Plain reference of the dense decoder the configurations in this
benchmark run: GPT-2 Small's widths (`configs/gpt2s.json`) and Yi-9B's
(`configs/yi9b-8l.json`).

Straightforward `jax.numpy` in float32 with every matrix product at
`Precision.HIGHEST`; no kernels, no cache, no batching tricks.  It imports
nothing of the program.  The block, as both configuration files state it:

    x   = embed[tokens] (+ pos_table[0..S-1] where positions are learned)
    per layer:
      h = rmsnorm(x) * w_attn_norm
      q, k, v = h Wq, h Wk, h Wv   (+ LoRA: scale * (h A) B on each target)
      rotary on q and k (halves, not interleaved), positions 0..S-1
      o = softmax(q k^T / sqrt(hd), causal) v, grouped: head h reads kv head h // (H / KV)
      x = x + o Wo (+ LoRA)
      h = rmsnorm(x) * w_mlp_norm
      x = x + gelu_tanh(h W1) W2            (gpt2s)
      x = x + (silu(h W1) * (h W3)) W2      (yi9b-8l)
    logits = rmsnorm(x) * w_final_norm @ head   (head = embed^T where tied)

`precision="fp8"` is the control: every operand of every matrix product
is rounded to float8 e4m3 with a per-tensor scale (absmax / 448) before an
exact product, which is what an fp8 matmul with float32 accumulation
computes.

Weights come in the nested layout `bench/weights.py` makes from the seed;
the LoRA adapter comes as the flat vector whose layout `lora_layout`
states (targets in sorted order, `a` then `b`, row-major, layers leading).
"""
from __future__ import annotations

import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8_MAX = 448.0


def _first(cfg, *keys):
    for k in keys:
        if k in cfg and cfg[k] is not None:
            return cfg[k]
    raise KeyError(f"none of {keys} in the configuration")


def dims(cfg: dict) -> dict:
    """The sizes the block needs, from a configuration file in either
    naming: GPT-2's (`n_layer`, `n_embd`, ...) or Llama's."""
    d = int(_first(cfg, "n_embd", "hidden_size"))
    h = int(_first(cfg, "n_head", "num_attention_heads"))
    gpt2 = cfg.get("model_type") == "gpt2"
    out = {
        "layers": int(_first(cfg, "n_layer", "num_hidden_layers")),
        "d_model": d,
        "heads": h,
        "kv_heads": int(cfg.get("num_key_value_heads", h)),
        "head_dim": int(cfg.get("head_dim", d // h)),
        "d_ff": int(_first(cfg, "n_inner", "intermediate_size")),
        "vocab": int(cfg["vocab_size"]),
        "activation": "gelu" if gpt2 else "swiglu",
        "learned_pos": gpt2,
        "max_pos": int(_first(cfg, "n_positions", "max_position_embeddings")),
        "tied": bool(cfg.get("tie_word_embeddings", False)),
        "eps": float(_first(cfg, "layer_norm_epsilon", "rms_norm_eps")),
        "rope_theta": float(cfg.get("rope_theta",
                                    cfg.get("block", {}).get("rope_theta",
                                                             10000.0))),
    }
    lora = cfg["lora"]
    out["lora_rank"] = int(lora["rank"])
    out["lora_scale"] = float(lora["alpha"]) / float(lora["rank"])
    out["lora_targets"] = tuple(sorted(lora["targets"]))
    return out


def target_dims(d: dict, target: str) -> Tuple[int, int]:
    """(d_in, d_out) of an attention projection."""
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    return {"wq": (d["d_model"], q), "wk": (d["d_model"], kv),
            "wv": (d["d_model"], kv), "wo": (q, d["d_model"])}[target]


def backbone_shapes(d: dict) -> List[Tuple[Tuple[str, ...], Tuple[int, ...], str]]:
    """(path, shape, init) of every backbone leaf, in the nested layout the
    system under test takes: `groups/g0/...` with layers stacked first."""
    L, D, V, F = d["layers"], d["d_model"], d["vocab"], d["d_ff"]
    q = d["heads"] * d["head_dim"]
    kv = d["kv_heads"] * d["head_dim"]
    out = [(("embed",), (V, D), "table")]
    if d["learned_pos"]:
        out.append((("pos_embed",), (d["max_pos"], D), "table"))
    g = ("groups", "g0")
    out += [(g + ("attn_norm",), (L, D), "norm"),
            (g + ("attn", "wq"), (L, D, q), "matrix"),
            (g + ("attn", "wk"), (L, D, kv), "matrix"),
            (g + ("attn", "wv"), (L, D, kv), "matrix"),
            (g + ("attn", "wo"), (L, q, D), "matrix"),
            (g + ("mlp_norm",), (L, D), "norm"),
            (g + ("mlp", "w1"), (L, D, F), "matrix"),
            (g + ("mlp", "w2"), (L, F, D), "matrix")]
    if d["activation"] == "swiglu":
        out.append((g + ("mlp", "w3"), (L, D, F), "matrix"))
    out.append((("final_norm",), (D,), "norm"))
    if not d["tied"]:
        out.append((("lm_head",), (D, V), "matrix"))
    return out


def lora_layout(d: dict) -> List[Tuple[str, str, Tuple[int, ...]]]:
    """Leaves of the flat adapter vector, in order: (target, 'a'|'b', shape)."""
    L, r = d["layers"], d["lora_rank"]
    out = []
    for t in d["lora_targets"]:
        d_in, d_out = target_dims(d, t)
        out.append((t, "a", (L, d_in, r)))
        out.append((t, "b", (L, r, d_out)))
    return out


def lora_size(d: dict) -> int:
    return sum(math.prod(s) for _, _, s in lora_layout(d))


def split_leaves(flat, d: dict) -> List[jax.Array]:
    out, off = [], 0
    for _, _, s in lora_layout(d):
        n = math.prod(s)
        out.append(flat[off:off + n].reshape(s))
        off += n
    return out


def unflatten(flat, d: dict) -> Dict[str, Dict[str, jax.Array]]:
    tree: Dict[str, Dict[str, jax.Array]] = {}
    for (t, ab, _), leaf in zip(lora_layout(d), split_leaves(flat, d)):
        tree.setdefault(t, {})[ab] = leaf
    return tree


def lora_init(d: dict, key) -> jax.Array:
    """The adapter's initial state: one key per leaf, split from `key` in
    leaf order; `a` ~ N(0, 1/d_in), `b` = 0."""
    keys = jax.random.split(key, len(lora_layout(d)))
    parts = []
    for k, (_, ab, s) in zip(keys, lora_layout(d)):
        if ab == "a":
            parts.append((jax.random.normal(k, s, jnp.float32)
                          / math.sqrt(s[-2])).reshape(-1))
        else:
            parts.append(jnp.zeros((math.prod(s),), jnp.float32))
    return jnp.concatenate(parts)


# ---------------------------------------------------------------------------
# the block
# ---------------------------------------------------------------------------

def _round_fp8(x):
    s = jax.lax.stop_gradient(jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
                              / F8_MAX)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _fp8(x):
    """x rounded to scaled float8 e4m3; its cotangent is rounded alike, as
    an fp8 matmul's backward products take their operands in fp8 too."""
    return _round_fp8(x)


_fp8.defvjp(lambda x: (_round_fp8(x), None),
            lambda _, g: (_round_fp8(g),))


def mm(spec: str, a, b, precision: str):
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def rope(x, theta):
    """x (S, heads, hd), positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs     # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _proj(h, w, ab, scale, precision):
    y = mm("si,io->so", h, w, precision)
    if ab is not None:
        y = y + scale * mm("sr,ro->so", mm("si,ir->sr", h, ab[0], precision),
                           ab[1], precision)
    return y


def layer(x, lw, ll, d: dict, precision: str):
    """One block on one sequence x (S, D).  lw: this layer's weights;
    ll: target -> (a, b) for this layer, or {}."""
    S = x.shape[0]
    H, KV, hd = d["heads"], d["kv_heads"], d["head_dim"]
    sc = d["lora_scale"]
    h = rmsnorm(x, lw["attn_norm"], d["eps"])
    at = lw["attn"]
    q = _proj(h, at["wq"], ll.get("wq"), sc, precision).reshape(S, H, hd)
    k = _proj(h, at["wk"], ll.get("wk"), sc, precision).reshape(S, KV, hd)
    v = _proj(h, at["wv"], ll.get("wv"), sc, precision).reshape(S, KV, hd)
    q, k = rope(q, d["rope_theta"]), rope(k, d["rope_theta"])
    G = H // KV
    qg = q.reshape(S, KV, G, hd)
    s = mm("skgh,tkh->kgst", qg, k, precision) / math.sqrt(hd)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm("kgst,tkh->skgh", p, v, precision).reshape(S, H * hd)
    x = x + _proj(o, at["wo"], ll.get("wo"), sc, precision)
    h = rmsnorm(x, lw["mlp_norm"], d["eps"])
    ml = lw["mlp"]
    if d["activation"] == "gelu":
        u = jax.nn.gelu(mm("sd,df->sf", h, ml["w1"], precision),
                        approximate=True)
    else:
        u = jax.nn.silu(mm("sd,df->sf", h, ml["w1"], precision)) \
            * mm("sd,df->sf", h, ml["w3"], precision)
    return x + mm("sf,fd->sd", u, ml["w2"], precision)


def hidden(weights, lora_flat, tokens, d: dict, precision: str = "f32"):
    """Final normed hidden states (S, D) of one sequence."""
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    if d["learned_pos"]:
        x = x + weights["pos_embed"][:tokens.shape[0]].astype(jnp.float32)
    g = weights["groups"]["g0"]
    lora = unflatten(lora_flat, d) if lora_flat is not None else {}

    @jax.checkpoint
    def body(x, xs):
        lw, ll = xs
        return layer(x, lw, {t: (ab["a"], ab["b"]) for t, ab in ll.items()},
                     d, precision), None

    # layer by layer, recomputing each layer's activations in the backward
    # pass, so memory holds one layer's float32 work at a time
    x, _ = jax.lax.scan(body, x, (g, lora))
    return rmsnorm(x, weights["final_norm"], d["eps"])


def head(weights, d: dict):
    return weights["embed"].T if d["tied"] else weights["lm_head"]


def logits(weights, lora_flat, tokens, d: dict, precision: str = "f32"):
    """(S, V) float32 logits of one sequence."""
    return mm("sd,dv->sv", hidden(weights, lora_flat, tokens, d, precision),
              head(weights, d), precision)


def loss(weights, lora_flat, tokens, d: dict, precision: str = "f32"):
    """Mean next-token cross-entropy of a batch of sequences (B, S)."""
    def one(t):
        lg = logits(weights, lora_flat, t, d, precision)[:-1]
        lz = jax.nn.logsumexp(lg, axis=-1)
        gold = jnp.take_along_axis(lg, t[1:, None], axis=-1)[:, 0]
        return jnp.mean(lz - gold)
    return jnp.mean(jax.vmap(one)(tokens))

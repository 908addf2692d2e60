"""Tiny cells for the CPU tests: the real traffic files with a small cohort
or small chunks, over a tiny model in either block style."""
import argparse
import json
import os

from bench import spec
from bench.compile_clock import CompileClock

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def config(model_type: str = "llama") -> dict:
    return {"name": "tiny", "reference": "dense_lm", "model_type": model_type,
            "n_layer": 2, "n_embd": 64, "n_head": 4, "num_key_value_heads": 2,
            "n_inner": 128, "n_positions": 256, "vocab_size": 256,
            "layer_norm_epsilon": 1e-5, "tie_word_embeddings": True,
            "rope_theta": 10000.0,
            "lora": {"rank": 4, "alpha": 8.0,
                     "targets": ["wq", "wk", "wv", "wo"], "dtype": "float32"}}


def cell(traffic: str, limits=None, model_type: str = "llama") -> spec.Cell:
    with open(os.path.join(BENCH, "traffic", f"{traffic}.json")) as f:
        tr = json.load(f)
    if tr["kind"] == "federated":
        tr["cohort"] = {"clients": 4, "sequences_per_client": 2,
                        "seq_len": 16, "local_steps": 1}
        tr["warmup_rounds"] = 3
    else:
        tr.update(tenants=6, pages=3, lanes=4, max_len=48,
                  prompt_buckets=[8, 16], gen_range=[4, 12],
                  chunk_requests=8, check_requests=3)
    return spec.Cell(name=f"tiny.{traffic}", chips=1,
                     config=config(model_type), traffic=tr,
                     traffic_name=traffic, limits=dict(limits or {}),
                     end_to_end=[], per_layer=[], bench_dir=BENCH)


def Context(seed: int = 2**31 + 11, seconds: float = 0.3):
    """The harness's own context, on the CPU, without the profiler."""
    import run as harness
    return harness.Context(
        argparse.Namespace(seed=seed, seconds=seconds, trace=0),
        {"platform": "cpu", "kind": "cpu", "count": 1},
        {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        CompileClock(), 1)


def run(c: spec.Cell, **kw):
    return c.runner().run(c, Context(**kw))

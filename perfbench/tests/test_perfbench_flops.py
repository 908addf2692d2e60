"""The operation and byte counts the ratios divide by: hand counts of both
configurations, a cross-check against the trip-count-aware HLO profiler
on a tiny model, and the transform's least bytes against any
implementation that reads its input once."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import perfbench_tiny as tiny
from bench import flops, weights
from bench.system import lora_config, model_config
from references import dense_lm

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return dense_lm.dims(json.load(f))


def test_gpt2s_hand_count():
    d = dims("gpt2s")
    layer = 768 * 768 * 4 + 2 * 768 * 3072                 # q k v o, fc, proj
    assert flops.layer_matmul_params(d) == layer == 7_077_888
    assert flops.matmul_params(d) == 12 * layer + 768 * 50257 == 123_532_032
    assert flops.lora_params(d) == 12 * 4 * 16 * (768 + 768) == 1_179_648
    S = 128
    seq = (4 * (12 * layer * S + 768 * 50257 * (S - 1))
           + 6 * 1_179_648 * S + 3 * 12 * 2 * S * S * 768)
    assert flops.sequence_train_flops(d, S) == seq == 64_905_950_208
    cohort = {"clients": 32, "sequences_per_client": 2, "seq_len": S,
              "local_steps": 1}
    assert flops.train_round_flops(d, cohort) == 64 * seq   # ~4.15 TFLOP
    assert flops.serve_token_flops(d) == 2 * 123_532_032


def test_yi9b_8l_hand_count():
    d = dims("yi9b-8l")
    layer = 4096 * 4096 * 2 + 2 * 4096 * 512 + 3 * 4096 * 11008
    assert flops.layer_matmul_params(d) == layer == 173_015_040
    assert flops.matmul_params(d) == 8 * layer + 4096 * 64000 \
        == 1_646_264_320
    per_layer_lora = 16 * ((4096 + 4096) * 2 + (4096 + 512) * 2)
    assert per_layer_lora == 409_600
    assert flops.lora_params(d) == 8 * per_layer_lora == 3_276_800
    S = 512
    seq = (4 * (8 * layer * S + 4096 * 64000 * (S - 1))
           + 6 * 3_276_800 * S + 3 * 8 * 2 * S * S * 32 * 128)
    assert flops.sequence_train_flops(d, S) == seq


@pytest.mark.parametrize("model_type", ["llama", "gpt2"])
def test_train_flops_against_hlo_profile(model_type):
    """The compiled loss-and-gradient of the system's model executes the
    model FLOPs plus the forward it recomputes under `jax.checkpoint`
    (and the masked half of its full attention), never less."""
    from repro.launch import hloprof
    from repro.models import lora as lora_mod
    from repro.models import model as mdl
    cfg = tiny.config(model_type)
    d = dense_lm.dims(cfg)
    mcfg = model_config("tiny", d)
    params = weights.backbone(dense_lm.backbone_shapes(d), jax.random.key(0))
    lcfg = lora_config(cfg)
    lora = lora_mod.init_lora(mcfg, lcfg, jax.random.key(1))
    B, S = 2, 64
    toks = jnp.zeros((B, S), jnp.int32)
    grad = jax.jit(jax.value_and_grad(lambda lo: mdl.loss_fn(
        params, mcfg, {"tokens": toks}, lora=lo, lora_scale=lcfg.scale)))
    hlo = hloprof.profile(grad.lower(lora).compile().as_text(), 1)
    model = B * flops.sequence_train_flops(d, S)
    recompute = B * (2.0 * d["layers"] * flops.layer_matmul_params(d) * S
                     + 2.0 * flops.lora_params(d) * S
                     + 2.0 * flops.attention_flops(d, S))
    masked_half = B * 4.0 * flops.attention_flops(d, S)
    assert model < hlo["dot_flops"] <= model + recompute + masked_half
    assert hlo["dot_flops"] >= 0.95 * (model + recompute)


@pytest.mark.parametrize("model,clients,density,bits", [
    ("gpt2s", 32, 0.25, 4), ("yi9b-8l", 8, 0.25, 4), ("tiny", 4, 0.25, 4),
    ("gpt2s", 32, 1.0, 0), ("tiny", 3, 0.01, 8), ("tiny", 3, 0.9, 0)])
def test_transform_roofline_cannot_pass_one(model, clients, density, bits):
    """Any implementation reads each client's float32 delta at least once
    and writes its message at least once in some form; at the chip's
    bandwidth its time is never below the least time the roofline divides
    by, so `metrics/transform_roofline.py` reads at most 100%."""
    from references import federated
    d = dense_lm.dims(tiny.config()) if model == "tiny" else dims(model)
    n = dense_lm.lora_size(d)
    least = flops.transform_bytes(n, clients, density, bits)
    k = federated.keep_count(n, density)
    vb = bits / 8.0 if bits else 4.0
    forms = [k * (vb + 4), k * vb + n // 8, n * vb,      # index, bitmap, dense
             k * (4 + 4)]                                # packed f32 + int32
    for written in forms:
        moved = clients * (4.0 * n + written)
        assert least <= moved
        share = roofline_of(d, clients, density, bits, moved / 819e9)
        assert 0 < share <= 100.0 * (1 + 1e-12)


def roofline_of(d, clients, density, bits, kernel_s):
    """`metrics/transform_roofline.py` on a run whose transform kernels
    took `kernel_s` for its one traced round."""
    import types
    from bench import spec
    read = spec.metric_reader("transform_roofline", BENCH)
    trace = types.SimpleNamespace(op_seconds=lambda match: (
        kernel_s if match("jit_step:_fuse_kernel") else 0.0, 1))
    cell = types.SimpleNamespace(traffic={
        "strategy": {"density_up": density, "quant_bits_up": bits},
        "cohort": {"clients": clients}})
    run = types.SimpleNamespace(kind="federated", trace=trace,
                                traced={"units": 1.0}, cell=cell, dims=d,
                                peaks={"hbm_bytes_per_s": 819e9})
    return read(run)

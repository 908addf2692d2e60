"""Per-architecture smoke tests: reduced variant (<=2 layers per group,
d_model<=512, <=4 experts), one forward/train step on CPU, asserting output
shapes and no NaNs.  The prefill+decode step for every arch is in
test_smoke_decode.py."""
import jax
import jax.numpy as jnp
import pytest

from _archs import built, make_batch  # noqa: F401  (built: module fixture)
from repro.configs.registry import ARCH_IDS, get_config
from repro.models import lora as lora_mod
from repro.models import model as mdl
from repro.models.config import LoRAConfig


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_config_is_reduced(arch):
    cfg = get_config(arch, smoke=True)
    assert cfg.d_model <= 512
    assert cfg.num_layers <= 4
    assert cfg.num_experts <= 4
    full = get_config(arch)
    assert full.family == cfg.family
    assert full.name.split("-")[0] == cfg.name.split("-")[0]


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_forward_and_train_step(built, arch):
    cfg, params = built(arch)
    lcfg = LoRAConfig(rank=4)
    lora = lora_mod.init_lora(cfg, lcfg, jax.random.key(1))
    assert jax.tree.leaves(lora), f"{arch}: LoRA attached nowhere"
    batch = make_batch(cfg)
    out = mdl.forward(params, cfg, batch, lora=lora, lora_scale=lcfg.scale)
    assert out["logits"].shape == (2, 16, cfg.vocab_size)
    assert not bool(jnp.isnan(out["logits"]).any())

    loss, grads = jax.value_and_grad(
        lambda lo: mdl.loss_fn(params, cfg, batch, lora=lo,
                               lora_scale=lcfg.scale))(lora)
    assert jnp.isfinite(loss)
    g1 = sum(float(jnp.sum(jnp.abs(g))) for g in jax.tree.leaves(grads))
    assert g1 > 0.0, f"{arch}: zero LoRA gradient"
    # one SGD step moves the loss
    lora2 = jax.tree.map(lambda p, g: p - 0.1 * g, lora, grads)
    loss2 = mdl.loss_fn(params, cfg, batch, lora=lora2, lora_scale=lcfg.scale)
    assert jnp.isfinite(loss2)


def test_param_counts_match_assignment():
    import repro.models.model as M
    # full configs should land near their nameplate sizes
    approx = {"minitron-8b": (7e9, 10.5e9), "gemma-7b": (7.5e9, 10e9),
              "yi-9b": (8e9, 10e9), "qwen3-32b": (30e9, 36e9),
              "deepseek-v2-236b": (200e9, 260e9),
              "deepseek-v3-671b": (600e9, 720e9),
              "internvl2-76b": (68e9, 82e9),
              "xlstm-1.3b": (1.0e9, 2.6e9), "hymba-1.5b": (1.2e9, 2.2e9),
              "whisper-large-v3": (1.2e9, 2.2e9)}
    for arch, (lo, hi) in approx.items():
        n = M.count_params(get_config(arch))
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B outside [{lo/1e9},{hi/1e9}]"

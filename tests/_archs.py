"""The reduced architecture variants the smoke suites share
(test_smoke_archs.py, test_smoke_decode.py): a batch for any arch and its
initialized params, built once per module."""
import jax
import pytest

from repro.configs.registry import get_config
from repro.models import model as mdl
from repro.models.layers import init_params


def make_batch(cfg, B=2, S=16, key=None):
    key = key or jax.random.key(7)
    b = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.encoder_decoder:
        b["frames"] = jax.random.normal(key, (B, cfg.encoder_seq, cfg.d_model)) * 0.1
    if cfg.num_image_tokens > 0:
        b["image_embeds"] = jax.random.normal(
            key, (B, cfg.num_image_tokens, cfg.vision_embed_dim)) * 0.1
    return b


@pytest.fixture(scope="module")
def built():
    cache = {}

    def get(arch):
        if arch not in cache:
            cfg = get_config(arch, smoke=True)
            params = init_params(mdl.model_spec(cfg), jax.random.key(0))
            cache[arch] = (cfg, params)
        return cache[arch]
    return get

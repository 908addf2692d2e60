"""Quantized communication (beyond-paper §2 composition): unbiasedness,
error bounds, round integration, ledger byte widths."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypcompat import given, settings, hst

from repro.core import quantization as qz
from repro.core.comm import CommLedger


@pytest.mark.fast
@settings(deadline=None, max_examples=20)
@given(hst.integers(2, 8), hst.integers(0, 2 ** 31 - 1))
def test_quantize_error_bound(bits, seed):
    x = jax.random.normal(jax.random.key(seed), (512,))
    y = qz.quantize_roundtrip(x, bits)
    step = float(jnp.max(jnp.abs(x))) / (2 ** (bits - 1) - 1)
    assert float(jnp.max(jnp.abs(y - x))) <= step * 0.5 + 1e-6


def test_stochastic_rounding_unbiased():
    x = jax.random.normal(jax.random.key(0), (1024,))
    rts = jnp.stack([qz.quantize_roundtrip(x, 4, jax.random.key(i))
                     for i in range(400)])
    step = float(jnp.max(jnp.abs(x))) / 7
    bias = float(jnp.max(jnp.abs(jnp.mean(rts, 0) - x)))
    assert bias < 0.15 * step        # ~sqrt(400) shrinkage of a U(step) err


@pytest.mark.fast
def test_quantize_preserves_zeros():
    x = jnp.asarray([0.0, 1.0, -1.0, 0.0])
    y = qz.quantize_roundtrip(x, 8)
    assert float(y[0]) == 0.0 and float(y[3]) == 0.0


@pytest.mark.fast
def test_ledger_quantized_widths():
    led = CommLedger(total_params=1000, down_value_bytes=1.0, up_value_bytes=0.5)
    led.record_round(n_clients=4, down_nnz=250, up_nnz_total=400)
    assert led.down_bytes == 4 * 250 * 1
    assert led.up_bytes == 200


def test_round_with_quantization_converges():
    from repro.core import fedround, strategies as st
    from repro.models.config import FederatedConfig
    trainable = {"w": {"a": jnp.ones((16, 4)), "b": jnp.ones((4, 16)) * 0.3}}
    meta = fedround.FlatMeta.of(trainable)
    fed = FederatedConfig(n_clients=4, local_batch=2, client_lr=0.1,
                          server_lr=0.05)
    spec = st.StrategySpec(kind="flasc", density_down=0.5, density_up=0.5,
                           quant_bits_down=8, quant_bits_up=8)
    target = jax.random.normal(jax.random.key(1), (16, 4))

    def loss_of(bb, tree, mb):
        return jnp.mean((tree["w"]["a"] - bb) ** 2)

    flatP = meta.flatten(trainable)
    server = fedround.init_server(flatP)
    sstate = st.resolve(spec).init_state(meta.p_len)
    fn = jax.jit(fedround.make_round_fn(loss_of, meta, fed, spec))
    batch = {"x": jnp.zeros((4, 1, 2, 1))}
    losses = []
    for r in range(30):
        flatP, server, sstate, m = fn(target, flatP, server, sstate, batch,
                                      jax.random.key(r))
        losses.append(float(m["loss"]))
    assert losses[-1] < 0.5 * losses[0]

"""Host spans and device scopes on the profiler's clock: the round loop's
`engine.*` spans, the serving loop's `serve.*` and `cache.*` spans, the
`python.gc` hook, and the `fedround.*` named scopes of the compiled round.
The spans are read back from the `.xplane.pb` a `jax.profiler.trace`
writes, as a profile reader would."""
import gc
import glob
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.core import strategies as st
from repro.core.spans import gc_spans
from repro.data.datasets import make_synth_image
from repro.federated.api import Experiment
from repro.federated.engine import Callback
from repro.models import lora as lora_mod
from repro.models import model as mdl
from repro.models.config import LoRAConfig, ModelConfig
from repro.models.layers import init_params
from repro.serving import (HostAdapterStore, PagedAdapterCache, ServingEngine,
                           synth_trace)

PREFIXES = ("engine.", "serve.", "cache.", "python.")
ROUNDS = 3
SCOPES = ("fedround.clients", "fedround.upload", "fedround.aggregate",
          "fedround.server_update")


def _spans(trace_dir):
    """[(name, start, end, args)] of the program's host spans, by start."""
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)[0]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    a = int(e.start_ns)
                    out.append((e.name, a, a + int(e.duration_ns),
                                dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


class _Noop(Callback):
    pass


def _experiment(strategy: st.StrategySpec):
    task = make_synth_image(n_examples=64, n_clients=4, n_patches=4, dim=16,
                            seed=0, n_eval=32)
    return (Experiment(task, strategy=strategy)
            .with_federation(n_clients=4, local_batch=4)
            .with_model(d_model=16, num_layers=1, num_heads=2, d_ff=32)
            .with_lora(rank=4)
            .with_training(rounds=ROUNDS, eval_every=0, pretrain_steps=0,
                           verbose=False))


FLASC = st.StrategySpec(kind="flasc", density_down=0.25, density_up=0.25,
                        quant_bits_up=4, sparse_aggregate=True)


def test_round_loop_spans_once_per_round_and_nested(tmp_path):
    exp = _experiment(FLASC).with_engine("sim").with_callbacks(_Noop())
    with jax.profiler.trace(str(tmp_path)):
        res = exp.run()
    spans = _spans(tmp_path)
    rounds = [s for s in spans if s[0] == "engine.round"]
    assert [s[3]["round"] for s in rounds] == list(range(ROUNDS))
    assert all(s[3]["rounds"] == 1 for s in rounds)
    for name in ("engine.data", "engine.dispatch", "engine.finish",
                 "engine.sync"):
        found = [s for s in spans if s[0] == name]
        assert len(found) == ROUNDS, name
        for s, r in zip(found, rounds):
            assert _inside(s, r), name
    # one span per hook call: on_round_end every round, on_eval on the
    # rounds that evaluated
    evaluated = sum("acc" in rec for rec in res.history)
    finishes = [s for s in spans if s[0] == "engine.finish"]
    for name in ("engine.callback.LedgerCallback", "engine.callback._Noop"):
        found = [s for s in spans if s[0] == name]
        assert len(found) == ROUNDS + evaluated, name
        for f in finishes:
            assert any(_inside(s, f) for s in found), name
    for s, f in zip((s for s in spans if s[0] == "engine.sync"), finishes):
        assert _inside(s, f)


def test_gc_spans_name_each_collection_and_uninstall(tmp_path):
    before = list(gc.callbacks)
    with jax.profiler.trace(str(tmp_path)):
        with gc_spans():
            gc.collect(1)
    assert gc.callbacks == before
    found = [s for s in _spans(tmp_path) if s[0] == "python.gc"]
    assert [s[3]["generation"] for s in found] == [1]


def test_serving_loop_spans_match_the_report(tmp_path):
    cfg = ModelConfig(name="t", family="dense", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=64,
                      param_dtype="float32", compute_dtype="float32")
    params = init_params(mdl.model_spec(cfg), jax.random.key(0))
    lcfg = LoRAConfig(rank=2, alpha=4, dtype="float32")
    store = HostAdapterStore()
    for c in range(4):
        store.put(c, lora_mod.init_lora(cfg, lcfg, jax.random.key(c)))
    cache = PagedAdapterCache(store, store.get(0), pages=2)
    trace = synth_trace(6, 4, cfg.vocab_size, seed=3, prompt_buckets=(4,),
                        gen_range=(1, 4))
    eng = ServingEngine(params, cfg, cache, n_lanes=2,
                        lora_scale=lcfg.scale, max_len=12)
    with jax.profiler.trace(str(tmp_path)):
        rep = eng.run(trace)
    spans = _spans(tmp_path)

    def named(name):
        return [s for s in spans if s[0] == name]

    assert len(named("cache.write_page")) == rep.cache["misses"] > 0
    assert all(0 <= s[3]["page"] < 2 for s in named("cache.write_page"))
    assert len(named("serve.decode")) == rep.steps
    assert len(named("serve.prefill")) == rep.prefills == len(trace)
    # one pull per prefill and one per decode step
    assert len(named("serve.sync")) == rep.prefills + rep.steps
    steps = named("serve.step")
    assert sum(s[3]["admitted"] for s in steps) == len(trace)
    assert sum(s[3]["active"] for s in steps) == rep.decode_tokens
    for name in ("serve.admit", "serve.prefill", "serve.decode",
                 "serve.push"):
        assert all(any(_inside(s, t) for t in steps) for s in named(name))
    admits = named("serve.admit")
    assert all(any(_inside(w, a) for a in admits)
               for w in named("cache.write_page"))


def _scope_of(op_name: str):
    found = re.findall(r"fedround\.[a-z_]+", op_name)
    return found[-1] if found else None


@pytest.fixture(scope="module")
def round_hlo():
    """The compiled HLO of one tiny FLASC round (sparse aggregation)."""
    from repro.core import fedround
    from repro.models.config import FederatedConfig
    p_len = 512
    meta = fedround.FlatMeta.of({"a": jnp.zeros((p_len,), jnp.float32)},
                                with_rank_map=False)
    w = jnp.linspace(-1.0, 1.0, 16 * p_len).reshape(16, p_len)

    def loss_of(bb, tree, mb):
        return jnp.mean((mb["x"] @ (bb * tree["a"])) ** 2)

    fed = FederatedConfig(n_clients=4, local_batch=2, local_steps=1)
    fn = fedround.make_round_fn(loss_of, meta, fed, FLASC)
    flatP = jnp.zeros((p_len,), jnp.float32)
    batches = {"x": jnp.ones((4, 1, 2, 16), jnp.float32)}
    args = (w, flatP, fedround.init_server(flatP),
            st.resolve(FLASC).init_state(p_len), batches, jax.random.key(0))
    return jax.jit(fn).lower(*args).compile().as_text()


def test_round_ops_carry_fedround_scopes(round_hlo):
    # `while` too: the synchronous aggregation is a row fold, a loop with
    # no matmul, scatter or kernel in it
    ops = re.compile(r" = .*?\b(dot|scatter|custom-call|while)\(")
    seen = set()
    for line in round_hlo.splitlines():
        if not ops.search(line):
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        assert m, line
        scope = _scope_of(m.group(1))
        assert scope in SCOPES, line
        seen.add(scope)
    assert {"fedround.clients", "fedround.aggregate"} <= seen


def test_round_aggregates_without_the_pack_or_a_scatter(round_hlo):
    """The synchronous round sums its dense upload stack in place: the
    batched pack kernel (a `tpu_custom_call` on the chip, interpreted ops
    that carry its name here) and the packed scatter-add belong to the
    AsyncEngine's packed jobs alone.  The client phase may scatter."""
    assert "_pack_batch_kernel" not in round_hlo
    for line in round_hlo.splitlines():
        if re.search(r" = .*?\bscatter\(", line):
            m = re.search(r'op_name="([^"]*)"', line)
            assert m and _scope_of(m.group(1)) != "fedround.aggregate", line

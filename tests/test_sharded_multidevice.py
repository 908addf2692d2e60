"""Forced-multi-device differential suite for the sharded-params engine
path (docs/engines.md "Sharded backbone params").

Everything runs in ONE subprocess with
`XLA_FLAGS=--xla_force_host_platform_device_count=8` (the device count must
be fixed before jax initializes — the same discipline as
tests/test_dryrun_small.py), on a real 2-D client-axis × model-axis mesh
`(data=4, model=2)`.  The subprocess prints a RESULT json; the pytest cases
here each assert one facet of it:

  * ShardedEngine == SimEngine bit-equality (final weights + full history)
    for the strategy matrix {flasc, hetlora_weighted, flocora,
    fused selector + 8-bit quant};
  * scan-chunked dispatch (`rounds_per_call=2`) stays bit-equal on the mesh;
  * FSDP/TP param sharding actually applied: the compiled round's recorded
    in_shardings place backbone leaves over "data" (ZeRO-3) and "model"
    (TP), and a device_put through them spreads a leaf over > 1 device;
  * donation safety: the backbone step argument is never donated — the
    donated set is exactly {flatP, server, sstate} shifted to (1, 2, 3);
  * checkpoint/resume on the mesh reproduces the uninterrupted history.
"""
import pytest

from _subproc import run_script

MATRIX = ["flasc", "hetlora_weighted", "flocora", "fused_quant"]

# Single-threaded Eigen contractions: XLA:CPU's multi-threaded Eigen splits
# a dot's work by its operand shapes, so one client per device (sharded)
# and four clients batched on one device (sim) would sum the same products
# in different orders.  Pinning it keeps the comparison about the program.
SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           "--xla_cpu_multi_thread_eigen=false")
import json
import sys
import tempfile
import jax
import numpy as np

from repro.core import strategies as st
from repro.data import datasets as ds
from repro.federated import engine as eng
from repro.federated.api import Experiment

assert len(jax.devices()) == 8, jax.devices()

task = ds.make_synth_image(n_examples=128, n_clients=8, n_patches=4,
                           dim=16, seed=0, n_eval=64)

KINDS = {
    "flasc": {},
    "hetlora_weighted": dict(kind="hetlora", hetlora_ranks=(1, 2, 3, 4),
                             hetlora_weighted=True),
    "flocora": dict(kind="flocora", lowrank_down=4, lowrank_up=4),
    "fused_quant": dict(selector="fused", quant_bits_up=8),
}


def base(spec="flasc", rounds=3):
    return (Experiment(task, strategy=spec)
            .with_federation(n_clients=4, local_batch=4)
            .with_model(d_model=16, num_layers=1, num_heads=2, d_ff=32)
            .with_lora(rank=4)
            .with_training(rounds=rounds, eval_every=2, pretrain_steps=2))


# pretrained once: every run starts from this backbone
BACKBONE = base().build_backbone()


def build(kind_kw, rounds=3):
    kw = dict(kind_kw)
    kind = kw.pop("kind", "flasc")
    spec = st.StrategySpec(kind=kind, density_down=0.5, density_up=0.5, **kw)
    return base(spec, rounds).with_params(*BACKBONE)


class Capture(eng.Callback):
    def on_round_end(self, ev):
        self.flatP = np.asarray(ev.state.flatP)
        self.sstate = [np.asarray(x) for x in jax.tree.leaves(ev.state.sstate)]


out = {}
for name in KINDS:
    print("phase:", name, file=sys.stderr, flush=True)
    cap_sim, cap_sh = Capture(), Capture()
    sim = build(KINDS[name]).with_callbacks(cap_sim).run()
    exp = build(KINDS[name]).with_mesh((4, 2), fsdp=True) \
                            .with_callbacks(cap_sh)
    sh = exp.run()
    step = exp.engine.last_step
    out[name] = {
        "bit_equal": bool(np.array_equal(cap_sim.flatP, cap_sh.flatP)),
        "sstate_equal": all(np.array_equal(a, b) for a, b in
                            zip(cap_sim.sstate, cap_sh.sstate)),
        "hist_equal": sim.history == sh.history,
        "acc_equal": sim.final_acc == sh.final_acc,
        "donate_argnums": list(step.donate_argnums),
        "max_abs_diff": float(np.max(np.abs(cap_sim.flatP - cap_sh.flatP))),
    }
    if name == "flasc":
        # --- sharding inspection on the compiled round ------------------
        # in_shardings is exactly what the jit was built with; leaf specs
        # referencing "data" are the ZeRO-3 overlay, "model" is TP
        pshard = step.in_shardings[0]
        specs = [s.spec for s in jax.tree.leaves(pshard)]
        out["fsdp_param_leaves"] = sum("data" in str(s) for s in specs)
        out["tp_param_leaves"] = sum("model" in str(s) for s in specs)
        bspecs = [s.spec for s in jax.tree.leaves(step.in_shardings[4])]
        out["batch_data_sharded"] = all("data" in str(s) for s in bspecs)
        # and the live storage layout: the placed backbone the run
        # actually fed to every step must spread over > 1 of the 8 devices
        placed = exp.engine._placed_params[1]
        ndev = [len(x.sharding.device_set) for x in jax.tree.leaves(placed)]
        out["max_param_devices"] = int(max(ndev))

        # --- scan-chunked dispatch stays bit-equal on the mesh ----------
        print("phase: scan", file=sys.stderr, flush=True)
        cap_scan = Capture()
        scan = build(KINDS[name]).with_mesh((4, 2), fsdp=True,
                                            rounds_per_call=2) \
                                 .with_callbacks(cap_scan).run()
        out["scan_bit_equal"] = bool(np.array_equal(cap_sim.flatP,
                                                    cap_scan.flatP))
        out["scan_hist_equal"] = sim.history == scan.history

# checkpoint mid-run on the mesh, resume, re-apply the mesh (resume
# restores engine name+config; the mesh itself is not serializable)
print("phase: resume", file=sys.stderr, flush=True)
full = build(KINDS["flasc"], rounds=4).with_mesh((4, 2), fsdp=True).run()


class StopAfterCheckpoint(eng.Callback):
    def on_checkpoint(self, ev):
        raise eng.StopRun


ckpt = tempfile.mkdtemp(prefix="shmd_ckpt_")
part = (build(KINDS["flasc"], rounds=4).with_mesh((4, 2), fsdp=True)
        .with_checkpoint(ckpt, every=2)
        .with_callbacks(StopAfterCheckpoint()).run())
exp_r = Experiment.resume(ckpt)
exp_r.with_mesh((4, 2), fsdp=True)
resumed = exp_r.run()
out["resume"] = {
    "stopped_at": len(part.history),
    "hist_equal": resumed.history == full.history,
    "acc_equal": resumed.final_acc == full.final_acc,
}

print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    return run_script(SCRIPT)


@pytest.mark.parametrize("kind", MATRIX)
def test_sharded_bit_equal_to_sim_on_2d_mesh(results, kind):
    r = results[kind]
    assert r["bit_equal"], (kind, r["max_abs_diff"])
    assert r["sstate_equal"], kind
    assert r["hist_equal"], kind
    assert r["acc_equal"], kind


def test_fsdp_and_tp_param_sharding_applied(results):
    # ZeRO-3 leaves sharded over the client ("data") axis, TP over "model",
    # and an actual placement spanning multiple of the 8 forced devices
    assert results["fsdp_param_leaves"] > 0
    assert results["tp_param_leaves"] > 0
    assert results["batch_data_sharded"]
    assert results["max_param_devices"] > 1


def test_backbone_never_donated(results):
    # donated set is exactly {flatP, server, sstate}, shifted past the
    # backbone argument: position 0 (params) must never be donated — the
    # same buffers feed every round
    for kind in MATRIX:
        assert results[kind]["donate_argnums"] == [1, 2, 3], kind


def test_scan_chunked_dispatch_bit_equal(results):
    assert results["scan_bit_equal"]
    assert results["scan_hist_equal"]


def test_checkpoint_resume_on_mesh(results):
    r = results["resume"]
    assert r["stopped_at"] == 2          # stopped at the round-2 save
    assert r["hist_equal"]
    assert r["acc_equal"]


@pytest.mark.fast
def test_sharded_multidevice_fast_subset(results):
    """ci_fast subset: one strategy's bit-equality and the sharding
    inspection on the real 8-device 2-D mesh, read from the module's one
    child run."""
    r = results
    assert r["flasc"]["bit_equal"], r["flasc"]["max_abs_diff"]
    assert r["flasc"]["donate_argnums"] == [1, 2, 3]
    assert r["fsdp_param_leaves"] > 0 and r["tp_param_leaves"] > 0
    assert r["max_param_devices"] > 1
    assert r["scan_bit_equal"]

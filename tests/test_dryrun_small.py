"""In-test multi-device dry-run: lower + compile the three step kinds on a
small forced-host-device mesh, in a subprocess (device count must be fixed
before jax initializes — exactly the discipline dryrun.py follows)."""
import pytest

from _subproc import run_script

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import sys
import jax, numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from repro.configs.registry import get_config
from repro.core import strategies as st
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_debug_mesh
from repro.launch.shardings import activation_sharding, spec_tree_shardings
from repro.models.config import InputShape, LoRAConfig
from repro.models.layers import spec_to_shape_dtype

mesh = make_debug_mesh(2, 2, pods=2)   # (pod, data, model) = (2, 2, 2)
cfg = get_config(os.environ["ARCH"], smoke=True)
lcfg = LoRAConfig(rank=4)
out = {}

# --- train (one FLASC round) ---
print("phase: train", file=sys.stderr, flush=True)
shape = InputShape("t", 32, 8, "train")
fed = steps_mod.fed_for_mesh(mesh, shape)
spec = st.StrategySpec(kind="flasc", density_down=0.25, density_up=0.25)
meta = steps_mod.abstract_flat_meta(cfg, lcfg)
fn = steps_mod.build_train_step(cfg, lcfg, fed, spec, meta,
                                spmd_axis_name=steps_mod.train_spmd_axes(mesh))
ins = steps_mod.train_inputs(cfg, lcfg, fed, shape)
sh = lambda t: spec_tree_shardings(t, mesh, steps_mod.TRAIN_RULES)
args = (spec_to_shape_dtype(ins["params"]), spec_to_shape_dtype(ins["flatP"]),
        spec_to_shape_dtype(ins["server"]), {},
        spec_to_shape_dtype(ins["batches"]),
        jax.ShapeDtypeStruct((2,), np.dtype("uint32")))
shardings = (sh(ins["params"]), sh(ins["flatP"]), sh(ins["server"]), {},
             sh(ins["batches"]), NamedSharding(mesh, PartitionSpec(None)))
with activation_sharding(mesh, steps_mod.TRAIN_RULES):
    compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
ca = compiled.cost_analysis()
if isinstance(ca, (list, tuple)):       # older jax: list of per-device dicts
    ca = ca[0] if ca else {}
out["train_flops"] = ca.get("flops", 0.0)

# --- decode ---
print("phase: decode", file=sys.stderr, flush=True)
shape = InputShape("d", 64, 8, "decode")
fn = steps_mod.build_decode_step(cfg, lcfg)
ins = steps_mod.decode_inputs(cfg, lcfg, shape)
sh2 = lambda t: spec_tree_shardings(t, mesh)
args = tuple(spec_to_shape_dtype(ins[k]) for k in ("params","lora","token","pos","cache"))
shardings = tuple(sh2(ins[k]) for k in ("params","lora","token","pos","cache"))
with activation_sharding(mesh):
    compiled = jax.jit(fn, in_shardings=shardings).lower(*args).compile()
out["decode_ok"] = True
print("RESULT " + json.dumps(out))
"""


@pytest.mark.parametrize("arch", ["minitron-8b", "deepseek-v2-236b", "hymba-1.5b"])
def test_small_mesh_dryrun(arch):
    result = run_script(SCRIPT, ARCH=arch)
    assert result["decode_ok"]
    assert result["train_flops"] >= 0

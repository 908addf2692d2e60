"""The benchmark's data layer: every entry of BENCHMARK.json resolves to
files of its own, a cell is added by adding files, the contract's limits
on names and bounds hold, and a run without a TPU fails before it
measures."""
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench import spec

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark(ROOT)


def test_every_cell_resolves_to_its_files(bench):
    for w in bench["workloads"]:
        cell = spec.resolve(w["name"], bench, ROOT)
        assert cell.traffic["kind"] in ("federated", "serving")
        assert os.path.exists(os.path.join(BENCH, "bench", "runners",
                                           f"{cell.kind}.py"))
        ref = cell.reference()
        d = ref.dims(cell.config)
        assert d["layers"] >= 1 and d["d_model"] > 0
        assert cell.limits, f"no limits/{w['name']}.json"
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2, names
        assert cell.per_layer, w["name"]


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.metric_reader(m["name"], BENCH)), m["name"]


def test_configs_name_their_files(bench):
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(bench["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["source"] == c["source"]
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not key.endswith(("_dim", "_rank", "_size")), key


def test_contract_shapes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    cells = {w["name"] for w in bench["workloads"]}
    pairs = {(w["config"], w["traffic"]) for w in bench["workloads"]}
    assert len(pairs) == len(cells) == len(bench["workloads"])
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        for w in m.get("workloads", []):
            reported = [x["name"] for x in bench["end_to_end"]
                        if w in x.get("workloads", cells)]
            assert m["moves"] in reported, (m["name"], w)
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


def test_a_cell_is_added_by_adding_files(tmp_path, bench):
    """A new traffic file and a new workload entry make a new cell: no file
    that is there is edited."""
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(BENCH, "traffic", "flasc-fused.json")) as f:
        tr = json.load(f)
    tr["cohort"]["clients"] = 16
    with open(tmp_path / "perfbench" / "traffic" / "flasc-fused-c16.json",
              "w") as f:
        json.dump(tr, f)
    with open(tmp_path / "perfbench" / "limits" / "gpt2s.flasc-fused-c16.json",
              "w") as f:
        json.dump({"loss_gap": 1.0}, f)
    new = dict(bench)
    new["workloads"] = bench["workloads"] + [{
        "name": "gpt2s.flasc-fused-c16", "config": "gpt2s",
        "traffic": "flasc-fused-c16", "chips": 1, "why": "test"}]
    with open(tmp_path / "BENCHMARK.json", "w") as f:
        json.dump(new, f)
    cell = spec.resolve("gpt2s.flasc-fused-c16", root=str(tmp_path))
    assert cell.traffic["cohort"]["clients"] == 16
    assert cell.limits == {"loss_gap": 1.0}
    assert {m["name"] for m in cell.end_to_end} == {"setup_s"}
    for w in bench["workloads"]:
        assert spec.resolve(w["name"], root=str(tmp_path)).traffic == \
            spec.resolve(w["name"], bench, ROOT).traffic


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "gpt2s.flasc-fused", "--seed", "3", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_run_without_a_tpu_fails_before_measuring():
    p = _run(ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_run_from_the_benchmark_files_alone_fails(tmp_path):
    shutil.copytree(BENCH, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "repro" in p.stderr          # the system under test is not there

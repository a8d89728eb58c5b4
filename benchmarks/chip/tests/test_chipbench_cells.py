"""Cells are found by name; the benchmark file keeps to its schema; the
result line has the contract's keys; the run refuses a machine with no
TPU and a checkout with no program."""

import json
import os
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

import harness

BENCH = pathlib.Path(harness.__file__).resolve().parent
ROOT = BENCH.parents[1]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_file_keeps_to_its_schema():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["benchmarks/chip"]
    assert 1 <= SPEC["run_seconds"] <= 51
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert 0.01 <= e2e["setup_s"]["bound"] <= 0.25
    assert all(0.01 <= m["bound"] <= 0.25 for m in e2e.values())
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", cells):
            assert w in cells
            assert w in e2e[m["moves"]].get("workloads", cells)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert json.loads((ROOT / c["file"]).read_text())["name"] == \
            c["name"]


@pytest.mark.parametrize("name", [w["name"] for w in SPEC["workloads"]])
def test_every_cell_is_found_by_name(name):
    cell = harness.load_cell(name)
    assert (BENCH / "paths" / f"{cell.path}.py").is_file()
    assert cell.limits, f"{name} has no limits file"
    e2e = [m["name"] for m in cell.end_to_end()]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = cell.per_layer()
    assert layer
    for m in layer:
        assert hasattr(harness.load_module("metrics", m["name"]), "read")


def _fixture_checkout(tmp_path):
    """A benchmark tree with one cell of its own, made of new files only."""
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "paths", "metrics", "limits"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "fx-model.json").write_text(
        json.dumps({"name": "fx-model", "width": 3}))
    (root / "traffic" / "fx-mix.json").write_text(
        json.dumps({"path": "fx-path", "items": 7}))
    (root / "limits" / "fx.cell.json").write_text(json.dumps({"err": 0}))
    (root / "paths" / "fx-path.py").write_text(
        "import harness\n"
        "def run(ctx):\n"
        "    n = ctx.cell.traffic['items'] * ctx.cell.config['width']\n"
        "    return harness.Run(end_to_end={'fx_per_s': n}, "
        "counters={'n': n}, checks={'err': {'value': 0, 'limit': 0}}, "
        "attempted=n, failed=0, device={}, correct=True)\n")
    (root / "metrics" / "fx_layer.py").write_text(
        "def read(run, cell, peaks):\n    return run.counters['n'] / 2\n")
    spec = {"workloads": [{"name": "fx.cell", "config": "fx-model",
                           "traffic": "fx-mix", "chips": 1, "why": "x"}],
            "end_to_end": [{"name": "fx_per_s", "unit": "1/s",
                            "better": "higher", "bound": 0.1,
                            "source": "host_clock"}],
            "per_layer": [{"name": "fx_layer", "unit": "1", "better": "lower",
                           "source": "program_counter", "layer": "x",
                           "moves": "fx_per_s"}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def test_a_new_cell_is_new_files_and_entries(tmp_path):
    root = _fixture_checkout(tmp_path)
    cell = harness.load_cell("fx.cell", root=root,
                             bench_file=tmp_path / "BENCHMARK.json")
    assert cell.path == "fx-path" and cell.limits == {"err": 0}
    run = harness.load_module("paths", cell.path, root).run(
        type("Ctx", (), {"cell": cell})())
    assert run.end_to_end == {"fx_per_s": 21}
    assert harness.read_per_layer(cell, run, {}) == {
        "fx_layer": {"value": 10.5, "unit": "1"}}
    with pytest.raises(KeyError):
        harness.load_cell("no.such.cell", root=root,
                          bench_file=tmp_path / "BENCHMARK.json")


def test_the_last_line_has_the_contract_keys():
    run = harness.Run(end_to_end={}, counters={},
                      checks={"logit_gap": {"value": 0.01, "limit": 0.1}},
                      attempted=400, failed=0, correct=True,
                      device={"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 1})
    line = harness.result_line(
        run, {"ttft_p95_ms": {"value": 212.4, "unit": "ms"}},
        {"device_ops": [["a", 1.0]], "idle_gaps": []})
    out = json.loads(line)
    assert list(out) == ["correct", "attempted", "failed", "metrics",
                         "device", "breakdown", "checks"]
    assert out["correct"] is True and out["checks"]["logit_gap"]["limit"]
    assert "\n" not in line


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         SPEC["workloads"][0]["name"], "--seed", "3000000001",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_the_run_refuses_a_machine_with_no_tpu():
    p = _run(ROOT, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_the_run_refuses_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, {})
    assert p.returncode != 0
    assert p.stdout.strip() == ""

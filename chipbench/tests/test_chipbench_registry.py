"""The harness finds everything by name, and a new cell or per-layer metric
is added by adding files only."""

import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from chipbench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def test_every_cell_resolves(bench):
    for w in bench["workloads"]:
        cell = harness.resolve(ROOT, w["name"])
        for fn in ("setup", "window", "release", "check", "min_bytes",
                   "cache_counters", "control"):
            assert callable(getattr(cell.driver, fn)), (w["name"], fn)
        e2e = {m["name"] for m in cell.end_to_end}
        assert harness.SETUP_METRIC in e2e and len(e2e) >= 2, w["name"]
        assert cell.per_layer, w["name"]


def test_every_metric_has_a_reader(bench):
    for m in bench["per_layer"]:
        path = os.path.join(ROOT, "chipbench", "metrics", m["name"] + ".py")
        reader = harness.load_module(path, "m_" + m["name"].replace(".", "_"))
        assert callable(reader.read), m["name"]


def test_benchmark_file_keeps_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert os.path.isfile(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    moves = {m["name"] for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in moves and UNIT.match(m["unit"])
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_new_cell_and_metric_are_added_files(tmp_path, bench):
    """A copy of the benchmark gains a cell and a metric by new files and
    new entries only; no file that was there changes."""
    root = tmp_path / "repo"
    shutil.copytree(os.path.join(ROOT, "chipbench"), root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in (root / "chipbench").rglob("*")
              if p.is_file()}
    traffic = json.loads(
        (root / "chipbench" / "traffic" / "whatif-s64.json").read_text())
    traffic["scenarios"] = 32
    (root / "chipbench" / "traffic" / "whatif-s32.json").write_text(
        json.dumps(traffic))
    (root / "chipbench" / "metrics" / "batches.whatif.py").write_text(
        "def read(run):\n    return float(run.window['batches'])\n")
    new = json.loads(json.dumps(bench))
    new["workloads"].append({"name": "surf22-whatif-s32",
                             "config": "surf22-lisa",
                             "traffic": "whatif-s32", "chips": 1,
                             "why": "half the batch"})
    new["per_layer"].append({"name": "batches.whatif", "unit": "batches",
                             "better": "higher", "source": "host_clock",
                             "layer": "host shell", "moves": "whatif_rate",
                             "workloads": ["surf22-whatif-s32"]})
    for m in new["end_to_end"] + new["per_layer"]:
        if "surf22-whatif-s64" in m.get("workloads", []):
            m["workloads"].append("surf22-whatif-s32")
    cell = harness.resolve(str(root), "surf22-whatif-s32", new)
    assert cell.traffic["scenarios"] == 32
    assert cell.config["name"] == "surf22-lisa"
    names = [m["name"] for m in cell.per_layer]
    assert "batches.whatif" in names and "host_ms.whatif" in names
    reader = harness.load_module(
        str(root / "chipbench" / "metrics" / "batches.whatif.py"), "m_new")
    run = harness.Run(cell=cell, device_kind="TPU v5 lite",
                      window={"batches": 3}, spans={}, counters={},
                      program_spans={})
    assert reader.read(run) == 3.0
    after = {p: p.read_bytes() for p in before}
    assert after == before


def test_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "surf22-whatif-s64", "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

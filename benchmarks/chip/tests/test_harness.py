"""Configurations, traffic mixes and metric readers are found by name, and
a file added beside them is picked up with no edit to any other file."""

from __future__ import annotations

import json
import shutil
import subprocess
import sys
from types import SimpleNamespace

import pytest

import cell

BENCH = json.loads((cell.ROOT / "BENCHMARK.json").read_text())


def metric_names(base=cell.HERE):
    return sorted(p.stem for p in (base / "metrics").glob("*.py")
                  if not p.stem.startswith("_"))


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_every_cell_loads(w):
    c = cell.load_cell(w["name"])
    assert c.config_name == w["config"] and c.traffic_name == w["traffic"]
    assert any(m["name"] == "setup_s" for m in c.end_to_end)
    assert len(c.end_to_end) >= 2 and c.per_layer
    dep = cell.deployment(dict(c.config, minutes=1), 5)
    parts = [dep.session_index(i) for i in range(dep.sessions)]
    every = sorted(i for p in parts for i in p)
    assert every == list(range(len(dep.stream["time"])))


def test_every_metric_has_a_reader():
    names = {m["name"] for m in BENCH["per_layer"]}
    assert names <= set(metric_names())
    for n in names:
        assert callable(cell.load_module("metrics", n).read)


def test_metric_with_nothing_to_read_is_left_out():
    ctx = SimpleNamespace(events=0.0, stats={"plan_cache_hits": 0,
                                             "plan_cache_misses": 0},
                          counters={}, submit_wait_ms=[], trace=None,
                          traced_events=None)
    for n in metric_names():
        assert cell.load_module("metrics", n).read(ctx) is None, n


def test_added_files_are_picked_up(tmp_path):
    base = tmp_path / "chip"
    shutil.copytree(cell.HERE, base, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    (base / "traffic" / "trickle.json").write_text(json.dumps(
        {"loop": "closed", "note": "a test mix"}))
    (base / "metrics" / "zz_new_metric.py").write_text(
        "def read(ctx):\n    return 42.0\n")
    bench = dict(BENCH)
    bench["workloads"] = BENCH["workloads"] + [
        {"name": "smarthome_q25.trickle", "config": "smarthome_q25",
         "traffic": "trickle", "chips": 1, "why": "a test cell"}]
    bench["per_layer"] = BENCH["per_layer"] + [
        {"name": "zz_new_metric", "unit": "ms", "better": "lower",
         "source": "program_counter", "layer": "serve", "moves": "events_per_s",
         "workloads": ["smarthome_q25.trickle"]}]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    c = cell.load_cell("smarthome_q25.trickle", root=tmp_path, base=base)
    assert c.traffic["note"] == "a test mix"
    assert [m["name"] for m in c.per_layer] == ["zz_new_metric"]
    assert "zz_new_metric" in metric_names(base)
    assert cell.load_module("metrics", "zz_new_metric", base).read(None) \
        == 42.0


def test_command_fails_without_a_tpu():
    p = subprocess.run(
        [sys.executable, str(cell.HERE / "run.py"), "--workload",
         "smarthome_q25.closed", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
        cwd=str(cell.ROOT))
    assert p.returncode != 0
    assert p.stdout == ""
    assert "TPU" in p.stderr

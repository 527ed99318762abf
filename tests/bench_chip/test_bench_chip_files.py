"""BENCHMARK.json and the files it names: every workload file names an
existing configuration and traffic, every metric has its module, and the
harness without a TPU exits non-zero, printing no result."""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmarks.chip import spec, traffic  # noqa: E402

BENCH = spec.benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("cell", CELLS)
def test_workload_files_name_existing_configs(cell):
    c = spec.load_cell(cell, ROOT)
    conf = {x["name"]: x for x in BENCH["configs"]}
    entry = {w["name"]: w for w in BENCH["workloads"]}[cell]
    assert entry["config"] in conf
    assert os.path.isfile(os.path.join(ROOT, conf[entry["config"]]["file"]))
    assert set(c.limits) == {"loss_gap", "grad_gap", "change_gap"}
    assert c.checked_steps >= 1
    assert "setup_s" in c.end_to_end and len(c.end_to_end) >= 2
    assert c.per_layer


@pytest.mark.parametrize("cell", CELLS)
def test_traffic_is_the_same_work_for_every_seed(cell):
    c = spec.load_cell(cell, ROOT)
    a = traffic.TokenFeed(c.traffic, c.model.vocab, 2**31 + 11).batch(0)
    b = traffic.TokenFeed(c.traffic, c.model.vocab, 7).batch(0)
    assert a[0].shape == b[0].shape == (c.traffic["batch"],
                                        c.traffic["seq_len"])
    assert (a[0] != b[0]).any()
    again = traffic.TokenFeed(c.traffic, c.model.vocab, 2**31 + 11).batch(0)
    assert (a[0] == again[0]).all() and (a[1] == again[1]).all()
    assert a[0].min() >= 1 and a[0].max() < c.model.vocab
    assert (a[1][:, :-1] == a[0][:, 1:]).all()


@pytest.mark.parametrize(
    "metric", [m["name"] for m in BENCH["per_layer"]])
def test_every_per_layer_metric_has_its_reader(metric):
    mod = importlib.import_module(f"benchmarks.chip.metrics.{metric}")
    assert callable(mod.read)


def test_benchmark_keys_and_names():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(names)) == len(names)
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    for c in BENCH["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["reduced"] == c["reduced"]
        for k in c["reduced"]:
            assert cfg["published"][k] != cfg[k]


def test_without_a_tpu_the_harness_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "chip", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert "correct" not in proc.stdout

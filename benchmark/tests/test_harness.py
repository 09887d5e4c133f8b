"""The entry point from outside: no TPU, no result; the CPU rehearsal
names its platform and prints no device metric."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run(*extra, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "criteo-lr.fit",
         "--seed", "2147483659", "--seconds", "0.2", "--trace", "1", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)


def test_no_tpu_no_result():
    done = run()
    assert done.returncode != 0
    assert done.stdout.strip() == ""
    assert "no TPU" in done.stderr


def test_rehearsal_names_the_cpu():
    done = run("--rehearse", "1")
    assert done.returncode == 0, done.stderr[-2000:]
    result = json.loads(done.stdout.strip().splitlines()[-1])
    assert result["device"]["platform"] == "cpu"
    assert result["metrics"] == {}
    assert "not measured" in result["metrics_note"]
    assert result["compared"]["window_compiles"] == [0, 0]
    assert "busy_s" not in result["device"]


def test_every_named_file_is_there():
    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for w in bench["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        assert os.path.exists(os.path.join(
            harness.BENCH_DIR, "runners", cell.traffic["runner"] + ".py"))
        assert cell.limits, w["name"]
        assert set(cell.config["reduced"]) == set(next(
            c for c in bench["configs"]
            if c["name"] == w["config"])["reduced"])
    for m in bench["end_to_end"] + bench["per_layer"]:
        reader = harness.load_module(os.path.join(
            harness.BENCH_DIR, "metrics", m["name"] + ".py"))
        assert callable(reader.read)

"""The harness's arithmetic, its result line, and that every name in
BENCHMARK.json resolves to a file."""

import json
import math
import os
import re
import subprocess
import sys

import pytest

from bench import common, run
from bench.tests import tiny

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_percentile_counts_misses_as_the_longest_wait():
    ok = [0.1] * 95
    misses = [9.0] * 5  # failed: counted with the wait until the drain
    assert common.percentile(ok + misses, 95) == 0.1
    assert common.percentile(ok + misses + [9.0], 95) == 9.0
    assert math.isnan(common.percentile([], 95))
    assert common.percentile([3.0, 1.0, 2.0], 50) == 2.0


def test_gap_worst_uses_the_median_leaf_as_floor():
    ref = {"a": 1.0, "b": 1.0, "c": 1e-9}
    prog = {"a": 1.1, "b": 1.0, "c": 2e-9}
    g, at = common.gap_worst(prog, ref)
    assert at == "a" and g == pytest.approx(0.1)
    assert common.gap_worst({"a": float("nan"), "b": 1, "c": 0}, ref)[0] \
        == math.inf


def test_result_line_schema_and_checks_last():
    line = common.result_line(
        correct=True, attempted=3, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                "memory_peak_bytes": 1},
        checks={"logit_gap_mean": {"value": 0.1, "limit": 0.5}},
        breakdown={"device_ops": [["x", 0.1]], "idle_gaps": []})
    d = json.loads(line)
    assert list(d)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(d)[-1] == "checks"
    assert d["checks"]["logit_gap_mean"] == {"value": 0.1, "limit": 0.5}


def test_checks_report_prints_each_number_beside_its_limit(capsys):
    ok = common.checks_report({"a": {"value": 1.0, "limit": 2.0},
                               "b": {"value": 3.0, "limit": 2.0}})
    err = capsys.readouterr().err.strip().splitlines()
    assert not ok
    assert err[-2].startswith("check a 1.0 limit 2.0")
    assert err[-1].endswith("FAILED")


def test_benchmark_json_follows_the_contract():
    spec = run.load_spec()
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["paths"] == ["bench"]
    assert isinstance(spec["run_seconds"], int)
    assert 1 <= spec["run_seconds"] <= 51
    names = [m["name"] for m in spec["end_to_end"] + spec["per_layer"]]
    names += [w["name"] for w in spec["workloads"]]
    names += [c["name"] for c in spec["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in spec["workloads"]}
    assert used == {c["name"] for c in spec["configs"]}
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    assert "setup_s" in {m["name"] for m in spec["end_to_end"]}
    layers = {m["name"]: m["layer"] for m in spec["per_layer"]}
    e2e = {m["name"] for m in spec["end_to_end"]}
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "\n" not in m["layer"]
        assert all(w in {c["name"] for c in spec["workloads"]}
                   for w in m["workloads"])
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert layers


def test_every_name_resolves_to_a_file():
    spec = run.load_spec()
    for w in spec["workloads"]:
        r = run.resolve(spec, w["name"])
        assert r["e2e"] and any(m["name"] == "setup_s" for m in r["e2e"])
        assert r["per_layer"], w["name"]
        for path in r["metric_files"].values():
            assert callable(run.load_reader(path))
        assert r["limits"]


def test_cells_configs_and_metrics_are_added_by_files_alone(tmp_path):
    spec = tiny.make_root(str(tmp_path))
    metric = tmp_path / "bench" / "metrics" / "window_tokens.py"
    metric.write_text("def read(obs):\n    return obs.get('steps_traced')\n")
    spec["per_layer"].append({
        "name": "window_tokens.tinytrain", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "train step",
        "moves": "train_tok_s", "workloads": ["tiny-train"]})
    r = run.resolve(spec, "tiny-train", str(tmp_path))
    assert r["cfg"]["name"] == "tiny"
    assert "window_tokens.tinytrain" in r["metric_files"]
    read = run.load_reader(r["metric_files"]["window_tokens.tinytrain"])
    assert read({"steps_traced": 4}) == 4
    assert {m["name"] for m in r["e2e"]} == {"train_tok_s", "setup_s"}
    with pytest.raises(FileNotFoundError):
        spec["per_layer"].append(dict(spec["per_layer"][-1],
                                      name="missing.tinytrain"))
        run.resolve(spec, "tiny-train", str(tmp_path))


def _run_bench(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "serve-decode-long",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_a_run_without_a_tpu_fails_and_prints_no_result():
    p = _run_bench(tiny.ROOT, {})
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "no TPU" in p.stderr


def test_a_run_from_the_benchmark_files_alone_fails(tmp_path):
    import shutil

    shutil.copytree(tiny.BENCH, tmp_path / "bench")
    shutil.copy(os.path.join(tiny.ROOT, "BENCHMARK.json"), tmp_path)
    p = _run_bench(str(tmp_path), {})
    assert p.returncode != 0 and "{" not in p.stdout

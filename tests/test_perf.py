"""Perf-observability suite: bench history, regression gate, roofline,
profiler capture (DESIGN.md §15).

The gate semantics are the load-bearing part: identical runs must pass,
a planted 2x slowdown must fail, and the ``max_rel`` cap must keep a
junk-IQR row fail-able — exactly the three behaviours CI's perf-smoke
job scripts against.
"""

import json
import subprocess
import sys

import pytest

from repro.obs import BenchHistory, env_fingerprint, read_bench
from repro.obs.perf import (
    device_peak,
    profile_capture,
    roofline_utilization,
    validate_bench_record,
)
from repro.obs.perfcheck import compare_rows, compare_runs
from repro.obs.perfcheck import main as perfcheck_main


def _write_run(path, rows, *, env=None, run_id=None):
    h = BenchHistory(path, env=env, run_id=run_id)
    for name, value, kw in rows:
        h.bench_row(name, value, **kw)
    return h


ROWS = [
    ("kernels/hla2_fwd/n1024", 5000.0,
     dict(unit="us", direction="lower", dispersion=50.0, n=9)),
    ("ops/gla/decode_tok_per_s", 2000.0,
     dict(unit="tok/s", direction="higher", dispersion=20.0, n=9)),
]


# --------------------------------------------------------------------------
# history round-trip + schema
# --------------------------------------------------------------------------


def test_history_roundtrip(tmp_path):
    path = tmp_path / "h.jsonl"
    h = _write_run(path, ROWS)
    assert h.rows_written == 2
    runs = read_bench(path)
    assert len(runs) == 1
    run = runs[0]
    assert run["run_id"] == h.run_id
    for key in ("git_sha", "jax_version", "backend", "device_count"):
        assert key in run["env"]
    row = run["rows"]["kernels/hla2_fwd/n1024"]
    assert row["value"] == 5000.0
    assert row["dispersion"] == 50.0
    assert row["direction"] == "lower"


def test_history_appends_runs_oldest_first(tmp_path):
    path = tmp_path / "h.jsonl"
    _write_run(path, ROWS, run_id="aaa")
    _write_run(path, ROWS, run_id="bbb")
    assert [r["run_id"] for r in read_bench(path)] == ["aaa", "bbb"]


def test_history_header_is_lazy(tmp_path):
    path = tmp_path / "h.jsonl"
    BenchHistory(path)  # no rows -> no file
    assert not path.exists()


def test_history_rejects_bad_direction(tmp_path):
    h = BenchHistory(tmp_path / "h.jsonl")
    with pytest.raises(ValueError, match="direction"):
        h.bench_row("a/b", 1.0, unit="us", direction="sideways")


def test_read_bench_rejects_garbage(tmp_path):
    path = tmp_path / "h.jsonl"
    path.write_text("not json\n")
    with pytest.raises(ValueError, match="not JSON"):
        read_bench(path)


def test_read_bench_rejects_orphan_row(tmp_path):
    path = tmp_path / "h.jsonl"
    path.write_text(json.dumps({
        "kind": "row", "run_id": "nope", "name": "a/b", "value": 1.0,
        "unit": "us", "direction": "lower", "dispersion": 0.0, "n": 1,
    }) + "\n")
    with pytest.raises(ValueError, match="unknown run_id"):
        read_bench(path)


@pytest.mark.parametrize("mutate,frag", [
    (lambda r: r.pop("schema"), "schema"),
    (lambda r: r.update(env="x"), "env"),
    (lambda r: r["env"].pop("git_sha"), "git_sha"),
])
def test_validate_run_record_errors(mutate, frag):
    rec = {"kind": "run", "schema": "repro.obs.bench/v1", "run_id": "r1",
           "ts": 0.0, "env": {"git_sha": "x", "jax_version": "x",
                              "backend": "cpu", "device_count": 1}}
    mutate(rec)
    assert frag in validate_bench_record(rec)


@pytest.mark.parametrize("mutate,frag", [
    (lambda r: r.pop("name"), "name"),
    (lambda r: r.update(value="fast"), "value"),
    (lambda r: r.update(value=True), "value"),  # bools are not numbers
    (lambda r: r.update(direction="up"), "direction"),
    (lambda r: r.update(n=1.5), "n"),
])
def test_validate_row_record_errors(mutate, frag):
    rec = {"kind": "row", "run_id": "r1", "name": "a/b", "value": 1.0,
           "unit": "us", "direction": "lower", "dispersion": 0.0, "n": 1}
    mutate(rec)
    assert frag in validate_bench_record(rec)


def test_validate_accepts_good_records():
    assert validate_bench_record({
        "kind": "run", "schema": "repro.obs.bench/v1", "run_id": "r",
        "ts": 1.0, "env": {"git_sha": "x", "jax_version": "x",
                           "backend": "cpu", "device_count": 1}
    }) is None
    assert validate_bench_record({
        "kind": "row", "run_id": "r", "name": "a/b", "value": 2,
        "unit": "us", "direction": "higher", "dispersion": 0, "n": 3,
    }) is None


def test_env_fingerprint_keys():
    fp = env_fingerprint()
    assert set(fp) >= {"git_sha", "jax_version", "backend",
                       "device_count", "device_kind"}
    assert fp["backend"] == "cpu"  # conftest forces JAX_PLATFORMS=cpu
    assert fp["device_count"] >= 1


def test_validate_cli_checks_bench_files(tmp_path):
    from repro.obs import validate as v

    path = tmp_path / "h.jsonl"
    _write_run(path, ROWS)
    assert v.main(["--bench", str(path)]) == 0
    path.write_text("{}\n")
    assert v.main(["--bench", str(path)]) == 1


# --------------------------------------------------------------------------
# the regression gate
# --------------------------------------------------------------------------


def test_identical_runs_pass(tmp_path):
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, ROWS)
    _write_run(new, ROWS)
    assert perfcheck_main([str(old), str(new)]) == 0


def test_planted_2x_slowdown_fails(tmp_path, capsys):
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, ROWS)
    slow = [(n, 2 * v if kw["direction"] == "lower" else v / 2, kw)
            for n, v, kw in ROWS]
    _write_run(new, slow)
    assert perfcheck_main([str(old), str(new)]) == 1
    err = capsys.readouterr().err
    assert "2 significant regression(s)" in err


def test_within_noise_change_passes():
    old = {"name": "a/b", "value": 100.0, "unit": "us",
           "direction": "lower", "dispersion": 10.0, "n": 9}
    new = dict(old, value=120.0)  # +20% < tol and < 3*(10+10)
    r = compare_rows(old, new, tol=0.25, noise_mult=3.0)
    assert not r["regressed"]


def test_direction_higher_regresses_on_drop():
    old = {"name": "a/tok_per_s", "value": 1000.0, "unit": "tok/s",
           "direction": "higher", "dispersion": 0.0, "n": 9}
    down = dict(old, value=400.0)
    up = dict(old, value=2000.0)
    assert compare_rows(old, down, tol=0.25, noise_mult=3.0)["regressed"]
    r = compare_rows(old, up, tol=0.25, noise_mult=3.0)
    assert not r["regressed"] and r["improved"]


def test_max_rel_caps_noise_allowance():
    """A junk-IQR row (dispersion ~ value) must STILL fail on a 2x move
    — without the cap the noise term would swallow it."""
    old = {"name": "a/b", "value": 100.0, "unit": "us",
           "direction": "lower", "dispersion": 80.0, "n": 9}
    new = dict(old, value=200.0)
    capped = compare_rows(old, new, tol=0.25, noise_mult=3.0, max_rel=0.75)
    assert capped["regressed"]
    uncapped = compare_rows(old, new, tol=0.25, noise_mult=3.0,
                            max_rel=1e9)
    assert not uncapped["regressed"]


def test_disjoint_rows_never_fail(tmp_path):
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, [("old/only", 1.0, dict(unit="us"))])
    _write_run(new, [("new/only", 1.0, dict(unit="us"))])
    assert perfcheck_main([str(old), str(new)]) == 0


def test_compare_runs_partitions_rows(tmp_path):
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, ROWS + [("old/only", 1.0, dict(unit="us"))])
    _write_run(new, ROWS + [("new/only", 1.0, dict(unit="us"))])
    cmp = compare_runs(read_bench(old)[-1], read_bench(new)[-1])
    assert len(cmp["compared"]) == 2
    assert cmp["only_old"] == ["old/only"]
    assert cmp["only_new"] == ["new/only"]


def test_perfcheck_latest_run_wins(tmp_path):
    """The gate compares the LATEST run in each file, not the first."""
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, ROWS)
    _write_run(new, [(n, 100 * v, kw) for n, v, kw in ROWS])  # stale junk
    _write_run(new, ROWS)  # latest run is clean
    assert perfcheck_main([str(old), str(new)]) == 0


def test_perfcheck_missing_file_exits_2(tmp_path, capsys):
    assert perfcheck_main([str(tmp_path / "no.jsonl"),
                           str(tmp_path / "pe.jsonl")]) == 2


def test_perfcheck_json_output(tmp_path, capsys):
    old, new = tmp_path / "a.jsonl", tmp_path / "b.jsonl"
    _write_run(old, ROWS)
    _write_run(new, ROWS)
    assert perfcheck_main([str(old), str(new), "--json"]) == 0
    raw = capsys.readouterr().out
    out, _ = json.JSONDecoder().raw_decode(raw)  # summary line follows
    assert {r["name"] for r in out["compared"]} == {n for n, _, _ in ROWS}


def test_perfcheck_runs_without_jax(tmp_path):
    """The gate must run on bare CI python: importing perfcheck (and
    perf) cannot pull in jax."""
    code = (
        "import sys\n"
        "sys.modules['jax'] = None\n"  # any import attempt explodes
        "from repro.obs import perfcheck\n"
        "from repro.obs import perf\n"
        "print('ok')\n"
    )
    import os

    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        env={"PYTHONPATH": src, "PATH": os.environ.get("PATH", "")},
    )
    assert out.returncode == 0 and "ok" in out.stdout, out.stderr


# --------------------------------------------------------------------------
# roofline + profiler capture
# --------------------------------------------------------------------------


class _Cost:
    def __init__(self, flops, bytes_):
        self.flops_per_token = flops
        self.bytes_per_token = bytes_


def test_roofline_compute_vs_memory_bound():
    peak = {"flops_per_s": 100e9, "bytes_per_s": 10e9,
            "kind": "synthetic", "source": "table"}
    # ridge = 10 FLOPs/byte: intensity 100 -> compute, 1 -> memory
    hot = roofline_utilization(1e6, _Cost(10_000.0, 100.0), peak)
    assert hot["bound"] == "compute"
    assert hot["utilization"] == pytest.approx(1e6 * 1e4 / 100e9)
    cold = roofline_utilization(1e6, _Cost(100.0, 100.0), peak)
    assert cold["bound"] == "memory"
    assert cold["utilization"] == pytest.approx(1e6 * 100.0 / 10e9)


def test_device_peak_on_cpu_is_calibrated():
    """No ceiling is made up for a device outside the published table:
    the CPU (and any unlisted accelerator) raises instead of calibrating."""
    with pytest.raises(ValueError, match="no published peak"):
        device_peak()

    class FakeAccel:
        device_kind = "TPU v99"

    with pytest.raises(ValueError, match="TPU v99"):
        device_peak(FakeAccel())


def test_device_peak_known_table():
    class FakeTPU:
        device_kind = "TPU v4"

    peak = device_peak(FakeTPU())
    assert peak["source"] == "table"
    assert peak["flops_per_s"] == 275e12


def test_profile_capture_noop_when_falsy():
    with profile_capture(None) as p:
        assert p is None
    with profile_capture("") as p:
        assert p is None


def test_profile_capture_writes_trace_and_events(tmp_path):
    import jax.numpy as jnp

    from repro.obs import Obs

    obs = Obs()
    prof = tmp_path / "prof"
    with profile_capture(str(prof), obs=obs) as p:
        assert p == str(prof)
        (jnp.ones((8, 8)) @ jnp.ones((8, 8))).block_until_ready()
    names = [e["name"] for e in obs.events(kind="event")]
    assert "profile.start" in names and "profile.stop" in names
    start = obs.events(name="profile.start")[0]
    stop = obs.events(name="profile.stop")[0]
    assert 0 < start["ts"] < stop["ts"]  # anchors on the tracer's clock
    assert start["profile_dir"] == str(prof)
    assert any(prof.rglob("*")), "no trace files written"

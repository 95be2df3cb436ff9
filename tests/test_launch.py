"""The command-line entry points on the CPU: train, serve, the compile
cache they share, and the chip smoke script's refusal to run off the chip.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

from repro.runtime import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(cache_dir):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    # cache every compile, however quick, so the test can see the entries
    env["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    env.pop("XLA_FLAGS", None)
    env.pop("HOST_DEVICES", None)
    env.pop("JAX_ENABLE_X64", None)
    return env


def _run(args, env, cwd=REPO):
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=600, env=env, cwd=cwd,
    )


@pytest.mark.parametrize("cli", ["train", "serve"])
def test_cli_runs_on_one_device(tmp_path, cli):
    """Both launchers run the reduced model on a 1x1 mesh end to end, and
    keep their compile cache where JAX_COMPILATION_CACHE_DIR points."""
    cache = tmp_path / "jax_cache"
    if cli == "train":
        args = ["-m", "repro.launch.train", "--arch", "hla-1b", "--reduced",
                "--steps", "3", "--batch", "2", "--seq", "32",
                "--ckpt-dir", str(tmp_path / "ckpt")]
    else:
        args = ["-m", "repro.launch.serve", "--arch", "hla-1b", "--reduced",
                "--requests", "3", "--prompt-len", "12", "--gen-len", "8"]
    p = _run(args, _env(cache))
    assert p.returncode == 0, p.stderr[-3000:]
    assert "mesh{'data': 1, 'model': 1}" in p.stdout
    if cli == "train":
        assert "finished at step 2" in p.stdout, p.stdout
    else:
        assert "statuses: ok=3 |" in p.stdout, p.stdout
        assert "24 generated tokens" in p.stdout, p.stdout
    assert any(cache.iterdir()), "no compile cache entries written"


def test_serve_cli_exits_nonzero_on_uninjected_error(monkeypatch):
    from repro.launch import serve
    from repro.serving import engine

    def refuse(self, req):
        raise ValueError("refused for the test")

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(engine.Engine, "_validate", refuse)
    with pytest.raises(SystemExit, match="2 of 2 requests failed"):
        serve.main(["--reduced", "--requests", "2", "--gen-len", "4",
                    "--prompt-len", "8"])


def test_serve_cli_accepts_injected_error(monkeypatch, capsys):
    from repro.launch import serve

    monkeypatch.setattr(serve, "enable_compile_cache", lambda: None)
    n = serve.main(["--reduced", "--requests", "2", "--gen-len", "4",
                    "--prompt-len", "8", "--inject", "engine.prefill@0"])
    assert n == 2
    assert "ok=1 error=1" in capsys.readouterr().out


def test_compile_cache_dir(monkeypatch):
    monkeypatch.setenv(compile_cache.ENV_VAR, "/somewhere/else")
    assert compile_cache.compile_cache_dir() == "/somewhere/else"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    assert compile_cache.compile_cache_dir() == os.path.join(
        REPO, ".jax_cache")


def test_chip_smoke_refuses_to_run_without_a_tpu(tmp_path):
    p = _run(["chip_smoke.py"], _env(tmp_path / "c1"))
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == "", p.stdout
    # alone in a directory, without the rest of the repo, it fails as well
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), alone)
    p = _run(["chip_smoke.py"], _env(tmp_path / "c2"), cwd=alone)
    assert p.returncode != 0
    assert p.stdout.strip() == "", p.stdout


def test_chip_smoke_rehearsal_on_cpu(tmp_path):
    """The CPU rehearsal drives every phase on the reduced config, with
    the kernels in interpret mode, and names the platform it ran on."""
    p = _run(["chip_smoke.py", "--rehearse"], _env(tmp_path / "c"))
    assert p.returncode == 0, p.stderr[-3000:]
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last == {"ok": True,
                    "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert "phase serve: passed" in p.stdout
    assert "phase train: passed" in p.stdout

"""Whole runs of the harness at a size the CPU holds, with the chip check
skipped: sound runs are correct, and each fault a cell can have, planted
in the timed path, makes ``correct`` false.  The control (the reference in
bfloat16's place at float8) fails the same limits."""

import time

import pytest

from bench import control, run
from bench.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    d = tmp_path_factory.mktemp("bench_root")
    spec = tiny.make_root(str(d))
    return str(d), spec


def _run(root, cell, fault=None, seed=2 ** 33 + 3):
    path, spec = root
    return run.run_cell(spec, cell, seed=seed, seconds=1.0, trace=False,
                        root=path, require_chip=False, fault=fault,
                        t_start=time.perf_counter())


@pytest.mark.parametrize("cell,fault", [
    ("tiny-train", None),
    ("tiny-train", "frozen_step"),
    ("tiny-train", "half_batch"),
    ("tiny-closed", None),
    ("tiny-closed", "frozen_decode"),
    ("tiny-closed", "altered_token"),
    ("tiny-sessions", None),
    ("tiny-sessions", "altered_token"),
])
def test_correct_only_without_a_fault(root, cell, fault):
    res = _run(root, cell, fault)
    assert res["correct"] is (fault is None), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["extra"]["compiles_in_window"] == 0
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell", ["tiny-train", "tiny-closed"])
def test_control_fails_the_limits(root, cell):
    path, spec = root
    nums = control.control_readings(spec, cell, 5, 1.0, root=path,
                                    require_chip=False)
    limits = run.resolve(spec, cell, path)["limits"]
    assert any(nums[k] > limits[k] for k in limits), nums

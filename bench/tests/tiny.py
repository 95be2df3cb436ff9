"""A benchmark tree at a size the CPU holds: a copy of ``bench/`` with a
tiny configuration, three tiny traffic mixes and their cells added by
files and entries alone."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(HERE, "data")

CELLS = {
    "tiny-train": ("tiny-train", {"loss_gap": 1e-4, "grad_norm_gap": 1e-3,
                                  "update_norm_gap": 1e-2}),
    "tiny-sessions": ("tiny-sessions", {"logit_gap_mean": 1e-4}),
    "tiny-closed": ("tiny-closed", {"logit_gap_mean": 1e-4}),
}


def make_root(dest: str, limits=None) -> dict:
    """Copy the benchmark to ``dest`` and add the tiny cells; returns the
    spec (``dest/BENCHMARK.json``)."""
    shutil.copytree(BENCH, os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    shutil.copy(os.path.join(DATA, "tiny.json"),
                os.path.join(dest, "bench", "configs", "tiny.json"))
    spec["configs"].append({
        "name": "tiny", "source": "https://arxiv.org/abs/2510.27258",
        "file": "bench/configs/tiny.json", "reduced": [], "why": "tests"})
    for cell, (mix, lim) in CELLS.items():
        shutil.copy(os.path.join(DATA, mix + ".json"),
                    os.path.join(dest, "bench", "traffic", mix + ".json"))
        with open(os.path.join(dest, "bench", "limits", cell + ".json"),
                  "w") as f:
            json.dump((limits or {}).get(cell, lim), f)
        spec["workloads"].append({"name": cell, "config": "tiny",
                                  "traffic": mix, "chips": 1, "why": "tests"})
    if "train_tok_s" not in {m["name"] for m in spec["end_to_end"]}:
        spec["end_to_end"].insert(0, {
            "name": "train_tok_s", "unit": "tokens/s", "better": "higher",
            "bound": 0.05, "source": "host_clock", "workloads": []})
    for m in spec["end_to_end"]:
        if "workloads" in m:
            kind = {"train_tok_s": ["tiny-train"],
                    "tpot_p95_ms": ["tiny-sessions", "tiny-closed"],
                    "gen_tok_s": ["tiny-closed"]}[m["name"]]
            m["workloads"] = m["workloads"] + kind
    with open(os.path.join(dest, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return spec

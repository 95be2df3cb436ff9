#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the
repository's root.  Everything it needs is found by name:

* its configuration: the ``file`` of its entry in ``configs``, which names
  the model's reference and adapter (``bench/models/<arch>_ref.py``,
  ``bench/models/<arch>.py``);
* its traffic: ``bench/traffic/<traffic>.json``, whose ``loop`` names
  ``bench/loops/<loop>.py``;
* the limits of its comparison with the reference:
  ``bench/limits/<cell>.json``;
* each per-layer metric: the reader of its family,
  ``bench/metrics/<family>.py``, where the family is the metric's name up
  to its first ``.`` (``idle_share.decode`` is read by ``idle_share.py``).

With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window's last seconds.  The run fails, and prints no result, where JAX
finds no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common  # noqa: E402


def load_spec(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(root, rel):
    with open(os.path.join(root, rel)) as f:
        return json.load(f)


def applies(metric: dict, cell: str, e2e_names) -> bool:
    """Whether a metric is reported in ``cell``: listed there, or (without
    a ``workloads`` key) in every cell for an end-to-end metric, and for a
    per-layer one wherever the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return "moves" not in metric or metric["moves"] in e2e_names


def resolve(spec: dict, name: str, root: str = ROOT) -> dict:
    """Everything the cell ``name`` needs, found by the names in ``spec``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = _load_json(root, conf["file"])
    traffic = _load_json(root, f"bench/traffic/{cell['traffic']}.json")
    limits = _load_json(root, f"bench/limits/{name}.json")
    e2e = [m for m in spec["end_to_end"] if applies(m, name, ())]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"] if applies(m, name, e2e_names)]
    files = {m["name"]: os.path.join(root, "bench", "metrics",
                                     m["name"].split(".")[0] + ".py")
             for m in per_layer}
    missing = [f for f in files.values() if not os.path.isfile(f)]
    loop = os.path.join(root, "bench", "loops", traffic["loop"] + ".py")
    for f in [loop] + [os.path.join(root, "bench", "models", f"{cfg['arch']}{s}.py")
                         for s in ("", "_ref")]:
        if not os.path.isfile(f):
            missing.append(f)
    if missing:
        raise FileNotFoundError(f"cell {name}: missing {missing}")
    return {"cell": cell, "cfg": cfg, "traffic": traffic, "limits": limits,
            "e2e": e2e, "per_layer": per_layer, "metric_files": files}


def load_reader(path: str):
    mod_name = "bench_metric_" + os.path.basename(path)[:-3]
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def run_cell(spec: dict, name: str, *, seed: int, seconds: float,
             trace: bool, root: str = ROOT, require_chip: bool = True,
             fault: str = None, t_start: float = None) -> dict:
    """Run the cell and return the result line's fields (and the check)."""
    r = resolve(spec, name, root)
    ctx = common.Context(
        cell=r["cell"], cfg=r["cfg"], traffic=r["traffic"],
        limits=r["limits"], seed=seed, seconds=seconds, trace=trace,
        t_start=T_START if t_start is None else t_start,
        require_chip=require_chip, fault=fault)
    loop = importlib.import_module(f"bench.loops.{r['traffic']['loop']}")
    from bench import faults

    with faults.planted(fault):
        out = loop.run(ctx)
    if trace:
        metrics = {}
        for m in r["per_layer"]:
            v = load_reader(r["metric_files"][m["name"]])(out["obs"])
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: out["metrics"][m["name"]] for m in r["e2e"]
                   if m["name"] in out["metrics"]}
    ok = common.checks_report(out["checks"])
    ok = ok and out["failed"] == 0 and bool(out["checks"])
    return {"correct": ok, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics,
            "device": out["device"], "breakdown": out["breakdown"],
            "extra": out["extra"], "checks": out["checks"],
            "sample": out.get("sample"), "readings": out.get("readings")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    try:
        res = run_cell(load_spec(), args.workload, seed=args.seed,
                       seconds=args.seconds, trace=bool(args.trace))
    except common.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(common.result_line(
        correct=res["correct"], attempted=res["attempted"],
        failed=res["failed"], metrics=res["metrics"], device=res["device"],
        breakdown=res["breakdown"] if args.trace else None,
        extra=res["extra"], checks=res["checks"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that set the limits of a cell's comparison with the reference.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 10 \
        [--faults half_batch,altered_token] [--out readings.jsonl]

For each seed, in one process on the cell's chips:

* the control: the float32 reference put in the program's place and
  computed one step below the configuration's bfloat16 (float8 e4m3
  operands, ``lowp="fp8"``).  A training cell compares the control's
  three steps with the float32 reference's; a serving cell runs the cell
  (a window of ``--seconds``) and reads, at each position of the sampled
  prompts and served tokens, the gap of the token the control puts first,
  and their mean;
* each fault of ``--faults`` (``bench/faults.py``) planted in the program,
  through a whole run of the cell.

Each reading is one JSON line: the seed, what was run, and the numbers
compared.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import common, run  # noqa: E402


def control_readings(spec, name, seed, seconds, *, root=run.ROOT,
                     require_chip=True) -> dict:
    """The control's numbers compared for ``name`` on ``seed``."""
    r = run.resolve(spec, name, root)
    c, t = r["cfg"], r["traffic"]
    if t["loop"] == "train":
        from bench.loops import train

        common.devices(r["cell"]["chips"], require_chip)
        low = train.reference_readings(c, t, seed, lowp="fp8")
        ref = train.reference_readings(c, t, seed)
        limits = {k: float("inf") for k in ("loss_gap", "grad_norm_gap",
                                            "update_norm_gap")}
        return {k: v["value"] for k, v in
                train.compare(low, ref, limits).items()}
    from bench.loops import serve

    res = run.run_cell(spec, name, seed=seed, seconds=seconds, trace=False,
                       root=root, require_chip=require_chip,
                       t_start=time.perf_counter())
    gaps = serve.reference_gaps(c, t, seed, res["sample"], lowp="fp8")
    return {"logit_gap_mean": gaps["mean"],
            "program_logit_gap_mean": res["checks"]["logit_gap_mean"]["value"],
            "widest_logit_gap": gaps["widest"],
            "program_widest_logit_gap": res["extra"]["widest_logit_gap"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import jax

    from repro.runtime.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    spec = run.load_spec()
    seeds = [int(s) for s in args.seeds.split(",")]
    faults = [f for f in args.faults.split(",") if f]
    out = open(args.out, "a") if args.out else None
    for seed in seeds:
        jobs = [("control", None)] + [("fault", f) for f in faults]
        for kind, fault in jobs:
            t0 = time.perf_counter()
            if kind == "control":
                nums = control_readings(spec, args.workload, seed,
                                        args.seconds)
            else:
                res = run.run_cell(spec, args.workload, seed=seed,
                                   seconds=args.seconds, trace=False,
                                   fault=fault, t_start=time.perf_counter())
                nums = {k: v["value"] for k, v in res["checks"].items()}
            common.free_device_memory()
            line = json.dumps({"workload": args.workload, "seed": seed,
                               "run": fault or kind, "numbers": nums,
                               "seconds": time.perf_counter() - t0})
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())

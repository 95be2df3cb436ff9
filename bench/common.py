"""Pieces every loop shares: the device check, the compile counter, the
traced window, the result line and the comparison's report."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib
import json
import math
import os
import sys
import time
from typing import Dict, List, Optional

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Context:
    """What a loop is given: the cell, its configuration and traffic,
    and the run's arguments."""

    cell: dict
    cfg: dict
    traffic: dict
    limits: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float  # process start, perf_counter
    require_chip: bool = True
    fault: Optional[str] = None  # a planted fault (tests and control only)


def arch(cfg: dict):
    """(reference module, program adapter module) of ``cfg["arch"]``."""
    ref = importlib.import_module(f"bench.models.{cfg['arch']}_ref")
    prog = importlib.import_module(f"bench.models.{cfg['arch']}")
    return ref, prog


def devices(chips: int, require_chip: bool):
    """The devices a cell runs on.  Raises ``NoChip`` unless JAX sees a
    TPU with at least ``chips`` devices (when ``require_chip``)."""
    import jax

    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX runs on {devs[0].platform}")
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        try:
            stats = d.memory_stats() or {}
        except Exception:  # the CPU backend keeps no statistics
            stats = {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


class CompileCounter:
    """Counts programs compiled or loaded from the persistent cache while
    active: in a measured window it must stay at 0."""

    def __init__(self):
        import jax.monitoring as mon

        self.n = 0
        self.active = False
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.active and (event.endswith("backend_compile_duration")
                            or event.endswith("cache_retrieval_time_sec")):
            self.n += 1


@contextlib.contextmanager
def traced(enabled: bool, out: dict):
    """Profile the block when ``enabled``: the trace goes to a temporary
    directory, is reduced, and removed.  The block runs inside a host
    span ``bench.window``; ``out["dir"]`` names the trace directory."""
    if not enabled:
        yield
        return
    import tempfile

    import jax

    out["dir"] = tempfile.mkdtemp(prefix="bench_trace_")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(out["dir"], profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            out["t0"] = time.perf_counter()
            yield
            out["t1"] = time.perf_counter()
    finally:
        jax.profiler.stop_trace()


def remove_tree(path: Optional[str]) -> None:
    import shutil

    if path and os.path.isdir(path):
        shutil.rmtree(path, ignore_errors=True)


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile (``q`` in [0, 100])."""
    if not values:
        return float("nan")
    v = sorted(values)
    k = max(1, int(math.ceil(q / 100.0 * len(v))))
    return v[k - 1]


def free_device_memory() -> None:
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def gap_worst(prog: Dict[str, float], ref: Dict[str, float],
              skip=()) -> (float, str):
    """Worst relative gap between two per-leaf norms: |p - r| over the
    larger of the leaf's own reference norm and the median leaf's."""
    names = [k for k in ref if k not in skip]
    med = sorted(ref[k] for k in names)[len(names) // 2]
    worst, at = 0.0, ""
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if math.isnan(g):
            return math.inf, k
        if g > worst:
            worst, at = g, k
    return worst, at


def checks_report(checks: Dict[str, dict]) -> bool:
    """Print each number compared beside its limit as the last lines of
    standard error; True when every number is within its limit."""
    ok = True
    for name, c in checks.items():
        within = c["value"] <= c["limit"]
        ok = ok and within
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if within else 'FAILED'}", file=sys.stderr)
    return ok


def result_line(*, correct, attempted, failed, metrics, device, checks,
                breakdown=None, extra=None) -> str:
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    if extra:
        out.update(extra)
    out["checks"] = checks  # last key: the numbers compared and limits
    return json.dumps(out)

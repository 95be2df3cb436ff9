"""Perf observability: roofline utilization, profiler capture, bench history.

Three pieces, all consumed by ``benchmarks/run.py`` and CI:

* **Roofline** — ``device_peak()`` (one table of published accelerator
  peaks; any other device is an error) and
  ``roofline_utilization(tok_per_s, cost, peak)``
  which turns a measured throughput plus a ``repro.obs.costs`` OpCost
  into achieved FLOP/s, achieved GB/s, utilization fractions and the
  bound (compute vs memory) — the §Utilization table in
  ``benchmarks/report.py``.

* **Profiler capture** — ``profile_capture(profile_dir, obs=...)``
  wraps a region in ``jax.profiler.start_trace/stop_trace`` and marks
  the boundaries with ``profile.start`` / ``profile.stop`` anchors
  (``Tracer.anchor``: an obs event and a trace annotation at one
  instant), so the XLA trace timeline can be lined up against the
  ``repro.obs.events/v1`` spans.  No-op
  when ``profile_dir`` is falsy.  Exposed as ``--profile-dir`` on
  ``launch/train.py``, ``launch/serve.py`` and ``benchmarks/run.py``.

* **Bench history** — an append-only JSONL (schema
  ``repro.obs.bench/v1``): each bench invocation appends one ``run``
  header record carrying the env fingerprint (git sha, jax version,
  backend, device count/kind) followed by one ``row`` record per metric
  (name, value, unit, direction, dispersion, sample count).  Rows are
  compared across runs by ``repro.obs.perfcheck`` (the noise-aware
  regression gate) and rendered as the trend column in the report.

Import-purity contract (mirrors ``registry.py``): importing this module
must NOT import jax — ``perfcheck`` and ``validate`` run in bare-stdlib
contexts.  All jax use is inside functions.
"""

from __future__ import annotations

import contextlib
import json
import os
import subprocess
import time
import uuid
from typing import Optional

BENCH_SCHEMA = "repro.obs.bench/v1"

#: direction of goodness for a bench row
DIRECTIONS = ("higher", "lower")

#: published per-chip peaks, keyed by substrings of ``device.device_kind``:
#: bf16 matmul FLOP/s (MXU) and HBM bytes/s.  Source: Google Cloud TPU
#: documentation, one page per generation ("TPU v5e": 197 TFLOP/s bf16,
#: 819 GB/s HBM).
_KNOWN_PEAKS = (
    ("v6", 918e12, 1640e9),      # TPU v6e (Trillium)
    ("v5p", 459e12, 2765e9),
    ("v5 lite", 197e12, 819e9),  # v5e reports "TPU v5 lite"
    ("v5e", 197e12, 819e9),
    ("v4", 275e12, 1228e9),
    ("v3", 123e12, 900e9),
    ("v2", 46e12, 700e9),
)


def device_peak(device=None) -> dict:
    """``{"flops_per_s", "bytes_per_s", "kind", "source"}`` for a device.

    Peaks come from the published table only.  A device whose kind is not
    in it (the CPU, an accelerator not listed) raises: a utilization
    against a made-up ceiling is not a device metric.
    """
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "unknown") or "unknown"
    low = kind.lower()
    for key, flops, membw in _KNOWN_PEAKS:
        if key in low:
            return {"flops_per_s": flops, "bytes_per_s": membw,
                    "kind": kind, "source": "table"}
    raise ValueError(
        f"no published peak for device kind {kind!r}; add it to "
        "repro.obs.perf._KNOWN_PEAKS with its source"
    )


def roofline_utilization(tok_per_s: float, cost, peak: Optional[dict] = None
                         ) -> dict:
    """Achieved-vs-roofline for one (throughput, OpCost) pair.

    ``cost`` is a ``repro.obs.costs.OpCost`` (or any object with
    ``flops_per_token`` / ``bytes_per_token``).  Utilization is measured
    against whichever resource the cost model says binds (the roofline
    ridge): ``bound`` is "compute" when the arithmetic intensity
    exceeds the device's ridge intensity, else "memory".
    """
    if peak is None:
        peak = device_peak()
    achieved_flops = tok_per_s * cost.flops_per_token
    achieved_bytes = tok_per_s * cost.bytes_per_token
    compute_util = achieved_flops / peak["flops_per_s"]
    memory_util = achieved_bytes / peak["bytes_per_s"]
    intensity = cost.flops_per_token / max(cost.bytes_per_token, 1e-9)
    ridge = peak["flops_per_s"] / peak["bytes_per_s"]
    bound = "compute" if intensity >= ridge else "memory"
    return {
        "tok_per_s": tok_per_s,
        "flops_per_token": cost.flops_per_token,
        "bytes_per_token": cost.bytes_per_token,
        "achieved_flops_per_s": achieved_flops,
        "achieved_bytes_per_s": achieved_bytes,
        "compute_util": compute_util,
        "memory_util": memory_util,
        "utilization": compute_util if bound == "compute" else memory_util,
        "bound": bound,
        "peak": dict(peak),
    }


@contextlib.contextmanager
def profile_capture(profile_dir, obs=None):
    """``jax.profiler`` trace of the wrapped region, or no-op if falsy.

    Marks both ends with ``Tracer.anchor`` (``profile.start`` /
    ``profile.stop``): each is an obs event and a zero-length annotation
    in the trace at one instant, so a span's ``ts`` maps to trace time as
    ``ts - anchor.ts + start_ns`` of the anchor's annotation.
    """
    if not profile_dir:
        yield None
        return
    import jax

    os.makedirs(profile_dir, exist_ok=True)
    jax.profiler.start_trace(profile_dir)
    if obs is not None:
        obs.anchor("profile.start", profile_dir=str(profile_dir))
    try:
        yield profile_dir
    finally:
        if obs is not None:
            obs.anchor("profile.stop", profile_dir=str(profile_dir))
        jax.profiler.stop_trace()


# --------------------------------------------------------------------------
# env fingerprint + bench history
# --------------------------------------------------------------------------


def env_fingerprint() -> dict:
    """Where a bench number came from: git sha, jax version, backend,
    device count and kind.  Every field degrades to a sentinel rather
    than raising — history must be writable from bare CI runners."""
    fp = {"git_sha": "unknown", "jax_version": "unavailable",
          "backend": "none", "device_count": 0, "device_kind": "unknown"}
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True, timeout=10,
            cwd=os.path.dirname(os.path.abspath(__file__)),
        )
        if sha.returncode == 0:
            fp["git_sha"] = sha.stdout.strip()
    except Exception:
        pass
    try:
        import jax

        fp["jax_version"] = jax.__version__
        fp["backend"] = jax.default_backend()
        devs = jax.devices()
        fp["device_count"] = len(devs)
        fp["device_kind"] = getattr(devs[0], "device_kind", "unknown")
    except Exception:
        pass
    return fp


class BenchHistory:
    """Append-only ``repro.obs.bench/v1`` writer for one bench run.

    One instance == one run: the ``run`` header (env fingerprint) is
    written lazily on the first ``bench_row``, so pointing ``--history``
    at a bench that produces no rows leaves the file untouched.
    """

    def __init__(self, path, env: Optional[dict] = None,
                 run_id: Optional[str] = None):
        self.path = str(path)
        self.run_id = run_id or uuid.uuid4().hex[:12]
        self._env = env
        self._started = False
        self.rows_written = 0

    def _append(self, rec: dict):
        parent = os.path.dirname(os.path.abspath(self.path))
        os.makedirs(parent, exist_ok=True)
        with open(self.path, "a") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")

    def _start(self):
        if self._started:
            return
        self._started = True
        self._append({
            "kind": "run", "schema": BENCH_SCHEMA, "run_id": self.run_id,
            "ts": time.time(), "env": self._env or env_fingerprint(),
        })

    def bench_row(self, name: str, value: float, *, unit: str,
                  direction: str = "lower", dispersion: float = 0.0,
                  n: int = 1, **extra):
        """Append one metric row.  ``direction`` says which way is good
        ("higher" for tok/s, "lower" for latency); ``dispersion`` is the
        IQR (same unit as ``value``) from the adaptive timer."""
        if direction not in DIRECTIONS:
            raise ValueError(f"direction {direction!r} not in {DIRECTIONS}")
        self._start()
        rec = {
            "kind": "row", "run_id": self.run_id, "name": name,
            "value": float(value), "unit": unit, "direction": direction,
            "dispersion": float(dispersion), "n": int(n),
        }
        if extra:
            rec["extra"] = extra
        self._append(rec)
        self.rows_written += 1


def read_bench(path) -> list:
    """Parse a ``repro.obs.bench/v1`` file into a list of runs, oldest
    first: ``[{"run_id", "ts", "env", "rows": {name: row}}, ...]``.
    Raises ValueError on malformed records (perfcheck wants hard
    failures, not silent skips)."""
    runs = []
    by_id = {}
    with open(path) as f:
        for i, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except json.JSONDecodeError as e:
                raise ValueError(f"{path}:{i}: not JSON: {e}") from None
            err = validate_bench_record(rec)
            if err:
                raise ValueError(f"{path}:{i}: {err}")
            if rec["kind"] == "run":
                run = {"run_id": rec["run_id"], "ts": rec.get("ts"),
                       "env": rec.get("env", {}), "rows": {}}
                runs.append(run)
                by_id[rec["run_id"]] = run
            else:
                run = by_id.get(rec["run_id"])
                if run is None:
                    raise ValueError(
                        f"{path}:{i}: row for unknown run_id "
                        f"{rec['run_id']!r} (missing run header?)"
                    )
                run["rows"][rec["name"]] = rec
    return runs


def validate_bench_record(rec) -> Optional[str]:
    """One-record schema check; returns an error string or None.
    Stdlib-only — shared by ``read_bench`` and ``repro.obs.validate``."""
    if not isinstance(rec, dict):
        return "record is not an object"
    rec_kind = rec.get("kind")
    if rec_kind == "run":
        if rec.get("schema") != BENCH_SCHEMA:
            return f"run.schema != {BENCH_SCHEMA!r}: {rec.get('schema')!r}"
        if not isinstance(rec.get("run_id"), str) or not rec["run_id"]:
            return "run.run_id missing"
        env = rec.get("env")
        if not isinstance(env, dict):
            return "run.env missing"
        for key in ("git_sha", "jax_version", "backend", "device_count"):
            if key not in env:
                return f"run.env.{key} missing"
        return None
    if rec_kind == "row":
        for key, typ in (("run_id", str), ("name", str), ("unit", str),
                         ("value", (int, float)),
                         ("dispersion", (int, float)), ("n", int)):
            if not isinstance(rec.get(key), typ) or (
                typ is str and not rec[key]
            ):
                return f"row.{key} missing or mistyped"
            if typ == (int, float) and isinstance(rec[key], bool):
                return f"row.{key} missing or mistyped"
        if rec.get("direction") not in DIRECTIONS:
            return f"row.direction not in {DIRECTIONS}: " \
                   f"{rec.get('direction')!r}"
        return None
    return f"record.kind not in ('run', 'row'): {rec_kind!r}"

"""Benchmark harness — one entry per paper table/figure + system benches.

Prints ``name,us_per_call,iqr_us,derived`` CSV rows.  The paper is
algorithmic (no empirical tables); its claims map to:

* Fig. 1/2 + Thms 3.1/4.1/6.1/7.1/7.2 — `equivalence` (views agree, and
  timing of each view);
* §5 complexity (linear time, O(1) state)  — `complexity` (us/token vs n),
  `statesize` (state bytes vs n, constant);
* §4 chunk-parallel training — `chunkwidth` (throughput vs w), and
  `train_step` (fwd+bwd us/step: fused Pallas VJP with chunk-state
  checkpointing vs recompute-in-backward vs jnp reference);
* serving (continuous batching over the paper's O(1)-state decode) —
  `serving` (TTFT + steady-state decode tok/s from the state-pool engine);
* the multi-pod roofline table is produced by `benchmarks.roofline`
  (separate long-running driver) and summarized by `benchmarks.report`.

Measurement discipline (DESIGN.md §15): every timing is **adaptive** —
samples accumulate until a minimum measured wall time — and reported as
median + IQR, so run-to-run comparisons have a noise scale attached.
Every bench persists ``results/<name>.json`` through ONE shared writer
(`write_results`) stamping the schema version and env fingerprint, and
throughput/latency rows append to the ``repro.obs.bench/v1`` history
(``--history``) consumed by ``python -m repro.obs.perfcheck`` — the CI
regression gate.  ``bench_ops`` additionally computes achieved-vs-
roofline utilization per registered SequenceOp from the analytic cost
model (``repro.obs.costs``), rendered as §Utilization by
``benchmarks.report``.
"""

from __future__ import annotations

import json
import os
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")
RESULTS_SCHEMA = "repro.bench.results/v1"

# adaptive-timing knobs: sample until this much measured time (or the
# iteration cap, whichever first) — overridable for CI smoke runs
MIN_MEASURE_S = float(os.environ.get("BENCH_MIN_MEASURE_S", "0.2"))
MAX_TIME_ITERS = int(os.environ.get("BENCH_MAX_ITERS", "64"))
MIN_TIME_ITERS = 3


class Timing(NamedTuple):
    us: float      # median us per call
    iqr_us: float  # inter-quartile range of the per-call samples, us
    iters: int     # samples actually taken


def _stats(samples) -> Timing:
    q25, q75 = np.percentile(samples, [25, 75])
    return Timing(float(np.median(samples) * 1e6),
                  float((q75 - q25) * 1e6), len(samples))


def _timeit(fn, *args, warmup=2, min_time_s=None) -> Timing:
    """Adaptive timing: block-until-ready per call, accumulate samples
    until ``min_time_s`` of measured time (>= MIN_TIME_ITERS, <=
    MAX_TIME_ITERS samples), report median + IQR."""
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    budget = MIN_MEASURE_S if min_time_s is None else min_time_s
    samples, total = [], 0.0
    while len(samples) < MIN_TIME_ITERS or (
        total < budget and len(samples) < MAX_TIME_ITERS
    ):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        dt = time.perf_counter() - t0
        samples.append(dt)
        total += dt
    return _stats(samples)


def write_results(name: str, payload: dict) -> str:
    """THE results persistence path: every bench table lands in
    ``results/<name>.json`` with the schema version and env fingerprint
    stamped, so any two artifacts are comparable (and
    ``benchmarks.report`` / ad-hoc tooling parse one format)."""
    from repro.obs.perf import env_fingerprint

    os.makedirs(RESULTS_DIR, exist_ok=True)
    doc = {"schema": RESULTS_SCHEMA, "bench": name,
           "env": env_fingerprint()}
    doc.update(payload)
    path = os.path.join(RESULTS_DIR, f"{name}.json")
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)
    return path


class RowSink(list):
    """The ``rows`` list benches append ``(name, us, iqr_us, derived)``
    to, plus a side-channel for named throughput/latency metrics bound
    for the bench history (tok/s rows carry direction="higher" there —
    the regression gate must know which way is good)."""

    def __init__(self):
        super().__init__()
        self.metrics = []

    def metric(self, name, value, *, unit, direction, dispersion=0.0):
        self.metrics.append({
            "name": name, "value": float(value), "unit": unit,
            "direction": direction, "dispersion": float(dispersion),
        })


def _metric(rows, name, value, **kw):
    """Record a history metric if ``rows`` is a RowSink (no-op for the
    plain lists tests may pass)."""
    m = getattr(rows, "metric", None)
    if m is not None:
        m(name, value, **kw)


def _tps_disp(tok_per_s, t: Timing) -> float:
    """Propagate a timing IQR into tok/s units (first-order)."""
    return tok_per_s * t.iqr_us / max(t.us, 1e-9)


def _mk(rng, B, H, n, d):
    q = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.9, 0.99, (B, H)), jnp.float32)
    return q, k, v, g


def bench_equivalence(rows):
    from repro.core.hla2 import (
        hla2_chunkwise,
        hla2_naive,
        hla2_scan,
        hla2_serial,
    )

    rng = np.random.RandomState(0)
    q, k, v, g = _mk(rng, 2, 2, 256, 32)
    o_ref = hla2_naive(q, k, v, g)
    impls = {
        "hla2_serial": jax.jit(lambda *a: hla2_serial(*a)[0]),
        "hla2_scan": jax.jit(lambda *a: hla2_scan(*a)[0]),
        "hla2_chunkwise": jax.jit(lambda *a: hla2_chunkwise(*a, chunk=64)[0]),
    }
    entries = {}
    for name, fn in impls.items():
        err = float(jnp.max(jnp.abs(fn(q, k, v, g) - o_ref)))
        t = _timeit(fn, q, k, v, g)
        rows.append((f"equivalence/{name}", t.us, t.iqr_us,
                     f"max_err={err:.2e}"))
        entries[name] = {"us": round(t.us, 1), "iqr_us": round(t.iqr_us, 1),
                         "iters": t.iters, "max_err": err}
    write_results("equivalence", {
        "shape": {"B": 2, "H": 2, "n": 256, "d": 32, "chunk": 64},
        "entries": entries,
    })


def bench_complexity(rows):
    """us/token vs n: HLA2 chunkwise is linear; the naive path quadratic."""
    from repro.core.hla2 import hla2_chunkwise, hla2_naive

    rng = np.random.RandomState(1)
    chunked = jax.jit(lambda a, b, c: hla2_chunkwise(a, b, c, chunk=64)[0])
    naive = jax.jit(lambda a, b, c: hla2_naive(a, b, c))
    per_tok = {}
    entries = {}
    for n in (256, 512, 1024, 2048):
        q, k, v, _ = _mk(rng, 1, 2, n, 32)
        t = _timeit(chunked, q, k, v)
        per_tok[n] = t.us / n
        rows.append((f"complexity/hla2_chunk_n{n}", t.us, t.iqr_us,
                     f"us_per_tok={t.us/n:.2f}"))
        entries[f"hla2_chunk_n{n}"] = {
            "us": round(t.us, 1), "iqr_us": round(t.iqr_us, 1),
            "us_per_tok": round(t.us / n, 3),
        }
    for n in (256, 512, 1024):
        q, k, v, _ = _mk(rng, 1, 2, n, 32)
        t = _timeit(naive, q, k, v)
        rows.append((f"complexity/naive_n{n}", t.us, t.iqr_us,
                     f"us_per_tok={t.us/n:.2f}"))
        entries[f"naive_n{n}"] = {
            "us": round(t.us, 1), "iqr_us": round(t.iqr_us, 1),
            "us_per_tok": round(t.us / n, 3),
        }
    growth = per_tok[2048] / per_tok[256]
    rows.append((
        "complexity/linear_check", 0.0, 0.0,
        f"us_per_tok growth 256->2048 = {growth:.2f}x (1.0 = perfectly linear)",
    ))
    write_results("complexity", {
        "shape": {"B": 1, "H": 2, "d": 32, "chunk": 64},
        "growth_256_to_2048": round(growth, 3),
        "entries": entries,
    })


def bench_statesize(rows):
    """Decode state bytes: constant in context length (vs a KV cache)."""
    from repro.configs import get_config
    from repro.models import lm

    cfg = get_config("hla-1b", reduced=True)
    entries = {}
    for n_ctx in (1024, 8192, 65536):
        states = jax.eval_shape(lambda: lm.lm_init_states(cfg, 1, n_ctx))
        hla_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(states)
        )
        cfg_sm = cfg.replace(mixer="softmax")
        states_sm = jax.eval_shape(
            lambda: lm.lm_init_states(cfg_sm, 1, n_ctx)
        )
        kv_bytes = sum(
            int(np.prod(x.shape)) * x.dtype.itemsize
            for x in jax.tree.leaves(states_sm)
        )
        rows.append((
            f"statesize/ctx{n_ctx}", 0.0, 0.0,
            f"hla_state={hla_bytes/2**20:.2f}MiB kv_cache={kv_bytes/2**20:.2f}MiB",
        ))
        entries[f"ctx{n_ctx}"] = {"hla_state_bytes": hla_bytes,
                                  "kv_cache_bytes": kv_bytes}
    write_results("statesize", {
        "shape": {"arch": "hla-1b-reduced", "B": 1},
        "entries": entries,
    })


def bench_chunkwidth(rows):
    from repro.core.hla2 import hla2_chunkwise

    rng = np.random.RandomState(2)
    q, k, v, g = _mk(rng, 2, 4, 2048, 64)
    entries = {}
    for w in (16, 32, 64, 128, 256):
        fn = jax.jit(
            lambda a, b, c, gg, w=w: hla2_chunkwise(a, b, c, gg, chunk=w)[0]
        )
        t = _timeit(fn, q, k, v, g)
        tok_s = 2048 * 2 / t.us * 1e6
        rows.append((f"chunkwidth/w{w}", t.us, t.iqr_us,
                     f"tok_per_s={tok_s:.0f}"))
        entries[f"w{w}"] = {"us": round(t.us, 1),
                            "iqr_us": round(t.iqr_us, 1),
                            "tok_per_s": round(tok_s)}
    write_results("chunkwidth", {
        "shape": {"B": 2, "H": 4, "n": 2048, "d": 64},
        "entries": entries,
    })


def bench_kernels(rows):
    """Pallas kernel (interpret) correctness + jnp reference timing."""
    from repro.kernels import ref as kref
    from repro.kernels.hla2_chunk import hla2_chunk_pallas

    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(4, 256, 64) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(4, 256, 64) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(4, 256, 64) * 0.5, jnp.float32)
    o_p, _ = hla2_chunk_pallas(q, k, v, None, chunk=64, interpret=True)
    o_r, _ = kref.hla2_chunk_ref(q, k, v, None, chunk=64)
    err = float(jnp.max(jnp.abs(o_p - o_r)))
    fn = jax.jit(lambda a, b, c: kref.hla2_chunk_ref(a, b, c, None, chunk=64)[0])
    t = _timeit(fn, q, k, v)
    rows.append(("kernels/hla2_chunk_ref", t.us, t.iqr_us,
                 f"pallas_interpret_err={err:.2e}"))
    write_results("kernels", {
        "shape": {"BH": 4, "n": 256, "d": 64, "chunk": 64},
        "entries": {"hla2_chunk_ref": {
            "us": round(t.us, 1), "iqr_us": round(t.iqr_us, 1),
            "pallas_interpret_err": err,
        }},
    })


def bench_train_step(rows):
    """Training-step (fwd+bwd) timing: fused Pallas VJP vs reference paths.

    ``*_fused`` runs the chunkwise Pallas forward with chunk-state
    checkpointing and the fused reverse-chunk-walk backward;
    ``*_recompute`` is the legacy design (fused forward, jnp recompute
    under ``jax.vjp`` in the backward); ``*_ref`` is the pure-jnp chunkwise
    path end to end.  On CPU the kernels execute in interpret mode (Python
    body per grid step), so the XLA-compiled ``*_ref`` row is the relevant
    CPU number — on TPU the same entries time the native kernels.
    """
    from repro.kernels.ops import ahla_attention, hla2_attention

    rng = np.random.RandomState(4)
    B, H, n, d = 1, 2, 512, 32
    q, k, v, g = _mk(rng, B, H, n, d)

    def make_loss(fn, **kw):
        def loss(a, b, c, gg):
            return jnp.sum(fn(a, b, c, gg, chunk=64, **kw) ** 2)

        return loss

    entries = {
        "hla2_fused": make_loss(hla2_attention, use_pallas=True,
                                fused_bwd=True),
        "hla2_recompute": make_loss(hla2_attention, use_pallas=True,
                                    fused_bwd=False),
        "hla2_ref": make_loss(hla2_attention, use_pallas=False),
        "ahla_fused": make_loss(ahla_attention, use_pallas=True,
                                fused_bwd=True),
        "ahla_ref": make_loss(ahla_attention, use_pallas=False),
    }
    backend = jax.default_backend()
    results = {}
    for name, loss in entries.items():
        step = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
        t = _timeit(step, q, k, v, g, warmup=1)
        tok_s = B * n / t.us * 1e6  # tokens (not head-tokens) per second
        rows.append((
            f"train_step/{name}", t.us, t.iqr_us,
            f"tok_per_s={tok_s:.0f} backend={backend}",
        ))
        _metric(rows, f"train_step/{name}/tok_per_s", tok_s,
                unit="tok/s", direction="higher",
                dispersion=_tps_disp(tok_s, t))
        results[name] = {"us_per_step": round(t.us, 1),
                         "iqr_us": round(t.iqr_us, 1),
                         "iters": t.iters,
                         "tok_per_s": round(tok_s)}
    write_results("train_step", {
        "backend": backend,
        "shape": {"B": B, "H": H, "n": n, "d": d, "chunk": 64},
        "entries": results,
    })


def bench_decode_throughput(rows):
    """Streaming decode (view A): us/token for the reduced paper model."""
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.param import init_params

    cfg = get_config("hla-1b", reduced=True)
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    B = 4
    states = lm.lm_init_states(cfg, B, 64)
    tok = jnp.ones((B, 1), jnp.int32)
    pos = jnp.zeros((B, 1), jnp.int32)

    @jax.jit
    def step(params, tok, states, pos):
        lg, st, _ = lm.lm_apply(
            params, tok, cfg, states=states, positions=pos, mode="decode"
        )
        return lg, st

    lg, states = step(params, tok, states, pos)  # compile
    # sequential recurrence: sample per-step times in place (the state
    # advances every call, so the generic _timeit can't replay args)
    samples, total, i = [], 0.0, 0
    while len(samples) < MIN_TIME_ITERS or (
        total < MIN_MEASURE_S and len(samples) < MAX_TIME_ITERS
    ):
        t0 = time.perf_counter()
        lg, states = step(params, tok, states, pos + i)
        jax.block_until_ready(lg)
        samples.append(time.perf_counter() - t0)
        total += samples[-1]
        i += 1
    t = _stats(samples)
    tok_s = B / t.us * 1e6
    rows.append(("decode/hla2_reduced", t.us, t.iqr_us,
                 f"tok_per_s={tok_s:.0f}"))
    _metric(rows, "decode/hla2_reduced/tok_per_s", tok_s,
            unit="tok/s", direction="higher",
            dispersion=_tps_disp(tok_s, t))
    write_results("decode", {
        "backend": jax.default_backend(),
        "shape": {"B": B, "arch": "hla-1b-reduced"},
        "entries": {"hla2_reduced": {
            "us_per_step": round(t.us, 1), "iqr_us": round(t.iqr_us, 1),
            "iters": t.iters, "tok_per_s": round(tok_s),
        }},
    })


def bench_serving(rows):
    """Multi-tenant serving trace: heavy-tailed shared prefixes (Zipf),
    Poisson arrivals, mixed priorities — sustained req/s, TTFT
    cold-vs-hit, cache hit rate (DESIGN.md §16).

    The trace draws each request's prompt as ``shared prefix + unique
    suffix`` where the prefix is picked from a small pool with a Zipf
    popularity law (a few prefixes carry most of the traffic, as system
    prompts do), arrivals follow a Poisson process (exponential
    inter-arrival gaps), and priority classes / tenants are mixed.  The
    engine runs with the content-addressed ``PrefixCache``: the first
    request on each prefix is a cold prefill that inserts the chunk-
    aligned state snapshot, every later one resumes from it and prefills
    only its suffix — TTFT splits into the cold and hit histograms.
    """
    import collections

    from repro.configs import get_config
    from repro.models import lm
    from repro.models.param import init_params
    from repro.serving import Engine, GenRequest, PrefixCache

    cfg = get_config("hla-1b", reduced=True)
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    slots, gen_len, block, gran = 4, 16, 8, 16
    prefix_len, n_reqs, n_prefixes = 2 * gran, 24, 4
    suffix_lens = (4, 8, 12)  # few distinct lengths -> few jit signatures
    max_len = prefix_len + max(suffix_lens) + gen_len + 8
    engine = Engine(
        cfg, params, slots=slots, max_len=max_len, block=block,
        cache=PrefixCache(granularity=gran, budget_bytes=1 << 30,
                          namespace=cfg.name),
    )
    rng = np.random.RandomState(5)
    prefixes = [rng.randint(2, cfg.vocab, prefix_len)
                for _ in range(n_prefixes)]
    # Zipf popularity over the prefix pool (p ~ 1/rank^1.2)
    pop = 1.0 / np.arange(1, n_prefixes + 1) ** 1.2
    pop /= pop.sum()

    def make_req(rid, prefix, suffix_len, *, priority=1, tenant="default"):
        prompt = np.concatenate(
            [prefix, rng.randint(2, cfg.vocab, suffix_len)])
        return GenRequest(rid=rid, prompt=prompt, max_new=gen_len,
                          priority=priority, tenant=tenant)

    # -- warmup: trace every jit signature the measured trace will hit
    # (cold full-prompt prefill and cached suffix-resume prefill, per
    # distinct suffix length) against a throwaway prefix, then zero the
    # obs epoch and drop the warmup cache entries
    warm_prefix = rng.randint(2, cfg.vocab, prefix_len)
    wid = -1
    for s in suffix_lens:
        for _ in range(2):  # first = cold + insert, second = hit + resume
            engine.run([make_req(wid, warm_prefix, s)])
            wid -= 1
    engine.cache.clear()
    engine.obs.reset()

    # -- build the trace: Zipf prefix choice, Poisson arrivals, mixed
    # priority classes and tenants
    gaps = rng.exponential(scale=0.003, size=n_reqs)  # ~3 ms mean gap
    arrivals = np.cumsum(gaps)
    trace = []
    for i in range(n_reqs):
        req = make_req(
            i, prefixes[rng.choice(n_prefixes, p=pop)],
            int(rng.choice(suffix_lens)),
            priority=int(rng.choice([0, 1, 2], p=[0.1, 0.6, 0.3])),
            tenant=str(rng.choice(["acme", "beta", "solo"])),
        )
        trace.append((float(arrivals[i]), req))

    # -- drive: submit each request at its arrival time against the
    # wall clock, tick the engine whenever work is pending
    pending = collections.deque(trace)
    t0 = time.perf_counter()
    while pending or len(engine.scheduler) or engine.active.any():
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            engine.submit(pending.popleft()[1])
        if len(engine.scheduler) or engine.active.any():
            engine._drive_tick()
        elif pending:
            time.sleep(max(0.0, pending[0][0] - (time.perf_counter() - t0)))
    wall_s = time.perf_counter() - t0

    results = [engine.results[i] for i in range(n_reqs)]
    assert all(r.status == "ok" for r in results), \
        [(r.rid, r.status) for r in results if r.status != "ok"]
    reg = engine.obs.registry
    hits = reg.get("cache_hits_total").total()
    misses = reg.get("cache_misses_total").total()
    hit_rate = hits / max(hits + misses, 1)

    def _q(hist_name, q):
        h = reg.get(hist_name)
        return 1e3 * (h.quantile(q) or 0.0)

    cold_p50, cold_p99 = _q("serving_ttft_cold_seconds", 0.5), \
        _q("serving_ttft_cold_seconds", 0.99)
    hit_p50, hit_p99 = _q("serving_ttft_hit_seconds", 0.5), \
        _q("serving_ttft_hit_seconds", 0.99)
    req_per_s = n_reqs / max(wall_s, 1e-9)
    st = engine.stats
    decode_toks = sum(len(r.tokens) - 1 for r in results)
    tok_s = decode_toks / max(st["decode_s"], 1e-9)
    backend = jax.default_backend()
    rows.append((
        "serving/trace", wall_s * 1e6, 0.0,
        f"req_per_s={req_per_s:.1f} hit_rate={hit_rate:.2f} "
        f"ttft_ms cold_p50={cold_p50:.1f}/p99={cold_p99:.1f} "
        f"hit_p50={hit_p50:.1f}/p99={hit_p99:.1f} backend={backend}",
    ))
    rows.append((
        "serving/decode", 0.0, 0.0,
        f"tok_per_s={tok_s:.1f} slots={slots} block={block}",
    ))
    _metric(rows, "serving/req_per_s", req_per_s, unit="req/s",
            direction="higher")
    _metric(rows, "serving/ttft_cold_ms_p50", cold_p50, unit="ms",
            direction="lower", dispersion=max(cold_p99 - cold_p50, 0.0))
    _metric(rows, "serving/ttft_hit_ms_p50", hit_p50, unit="ms",
            direction="lower", dispersion=max(hit_p99 - hit_p50, 0.0))
    _metric(rows, "serving/cache_hit_rate", hit_rate, unit="ratio",
            direction="higher")
    _metric(rows, "serving/decode_tok_per_s", tok_s, unit="tok/s",
            direction="higher")
    write_results("serving", {
        "backend": backend,
        "shape": {"slots": slots, "prefix_len": prefix_len,
                  "suffix_lens": list(suffix_lens), "gen_len": gen_len,
                  "block": block, "granularity": gran,
                  "requests": n_reqs, "prefixes": n_prefixes},
        "req_per_s": round(req_per_s, 2),
        "wall_s": round(wall_s, 4),
        "ttft_cold_ms_p50": round(cold_p50, 2),
        "ttft_cold_ms_p99": round(cold_p99, 2),
        "ttft_hit_ms_p50": round(hit_p50, 2),
        "ttft_hit_ms_p99": round(hit_p99, 2),
        "cache_hit_rate": round(hit_rate, 4),
        "cache_hits": int(hits),
        "cache_misses": int(misses),
        "decode_tok_per_s": round(tok_s, 1),
        "prefill_tok_per_s": round(
            st["prompt_tokens"] / max(st["prefill_s"], 1e-9), 1
        ),
        # the same snapshot schema the serve CLI's --metrics-out dumps,
        # scoped to the bench's engine (report.py and ad-hoc tooling
        # can consume either artifact identically)
        "metrics": engine.obs.snapshot(),
    })


def bench_ops(rows):
    """Per-operator train-forward and decode throughput + roofline
    utilization over EVERY registered ``SequenceOp`` (DESIGN.md §11/§15).

    Same reduced backbone for all ops (only the mixing sublayer differs),
    so the matrix shows the relative cost of each operator AND makes any
    registry-dispatch overhead visible in the perf trajectory: train-fwd
    tok/s is one jitted ``lm_apply`` over (B, n), decode tok/s is a
    jitted ``lax.scan`` of fused single-token steps (the serving block
    path without sampling).  Each measured tok/s is combined with the
    analytic whole-model cost (``repro.obs.costs.model_cost``) and the
    device roofline into achieved-vs-peak utilization — the §Utilization
    table in ``benchmarks.report`` and the number the fused-kernel
    ROADMAP work is judged by.
    """
    import functools

    from repro.configs import get_config
    from repro.models import lm, seq_op
    from repro.models.config import MambaConfig
    from repro.models.param import init_params
    from repro.obs import costs
    from repro.obs.perf import device_peak, roofline_utilization

    base = get_config("hla-1b", reduced=True)
    B, n, steps = 4, 256, 16
    # utilization divides by the chip's published peak; off the chip it is
    # not measured
    peak = device_peak() if jax.default_backend() == "tpu" else None
    entries = {}
    for name in seq_op.registered_op_names():
        cfg = base.replace(mixer=("softmax" if name == "attn" else name))
        if name == "mamba":
            cfg = cfg.replace(mamba=MambaConfig(d_state=8))
        params = init_params(lm.lm_specs(cfg), jax.random.key(0))
        rng = np.random.RandomState(7)
        toks = jnp.asarray(rng.randint(1, cfg.vocab, (B, n)), jnp.int32)

        fwd = jax.jit(functools.partial(
            lambda p, t, cfg: lm.lm_apply(p, t, cfg)[0], cfg=cfg
        ))
        t_fwd = _timeit(fwd, params, toks, warmup=1)

        _, states = jax.jit(functools.partial(
            lambda p, t, cfg: lm.lm_prefill(p, t, cfg), cfg=cfg
        ))(params, toks)

        def decode_block(p, st, tok, pos, cfg=cfg):
            def body(carry, _):
                st, tok, pos = carry
                lg, st, _ = lm.lm_apply(
                    p, tok, cfg, states=st, positions=pos, mode="decode"
                )
                nxt = jnp.argmax(lg[:, -1], -1)[:, None].astype(jnp.int32)
                return (st, nxt, pos + 1), ()
            (st, tok, _), _ = jax.lax.scan(
                body, (st, tok, pos), length=steps
            )
            return st, tok

        tok0 = toks[:, -1:]
        pos0 = jnp.full((B, 1), n, jnp.int32)
        t_dec = _timeit(
            jax.jit(decode_block), params, states, tok0, pos0, warmup=1,
        )

        op = seq_op.get_op(name)
        train_tok_s = B * n / (t_fwd.us / 1e6)
        decode_tok_s = B * steps / (t_dec.us / 1e6)

        # achieved-vs-roofline: measured tok/s x analytic whole-model
        # FLOPs/token against the device peak
        cost_f = costs.model_cost(cfg, mode="train_fwd", seq_len=n, batch=B)
        cost_d = costs.model_cost(cfg, mode="decode_step", seq_len=n + steps,
                                  batch=B)
        nm = {"utilization": None, "bound": "not measured"}
        util_f = util_d = nm
        if peak is not None:
            util_f = roofline_utilization(train_tok_s, cost_f, peak)
            util_d = roofline_utilization(decode_tok_s, cost_d, peak)

        entries[name] = {
            "train_fwd_tok_per_s": round(train_tok_s, 1),
            "train_iqr_us": round(t_fwd.iqr_us, 1),
            "decode_tok_per_s": round(decode_tok_s, 1),
            "decode_iqr_us": round(t_dec.iqr_us, 1),
            "train_flops_per_token": round(cost_f.flops_per_token),
            "decode_flops_per_token": round(cost_d.flops_per_token),
            "train_util": util_f["utilization"],
            "train_bound": util_f["bound"],
            "decode_util": util_d["utilization"],
            "decode_bound": util_d["bound"],
            "state_bytes": cost_f.state_bytes,
            "streaming": op.streaming,
            "has_fused_kernels": op.has_fused_kernels,
            "spec_decodable": op.spec_decodable,
        }
        rows.append((
            f"ops/{name}", t_fwd.us, t_fwd.iqr_us,
            f"train_fwd_tok_per_s={train_tok_s:.0f} "
            f"decode_tok_per_s={decode_tok_s:.0f} "
            f"train_util={util_f['utilization']} "
            f"decode_util={util_d['utilization']}",
        ))
        _metric(rows, f"ops/{name}/train_fwd_tok_per_s", train_tok_s,
                unit="tok/s", direction="higher",
                dispersion=_tps_disp(train_tok_s, t_fwd))
        _metric(rows, f"ops/{name}/decode_tok_per_s", decode_tok_s,
                unit="tok/s", direction="higher",
                dispersion=_tps_disp(decode_tok_s, t_dec))

    write_results("ops", {
        "backend": jax.default_backend(),
        "shape": {"B": B, "n": n, "decode_steps": steps,
                  "arch": "hla-1b-reduced"},
        "peak": peak,
        "entries": entries,
    })


def bench_spec(rows):
    """Speculative decoding vs plain block decode (acceptance + tok/s).

    A meaningful acceptance rate needs a model whose continuations are
    actually predictable, so the bench first TRAINS a small HLA2 LM
    (~120 AdamW steps, seconds on CPU) on a cyclic token language until
    greedy decode reproduces the cycle — the classic repetitive-text
    workload (templated/extractive generation) where prompt-lookup
    drafting shines.  Then, on identical requests:

    * plain block decode (block=8, the §Serving path) is the baseline;
    * speculative decode with the model-free n-gram drafter at
      k in {2, 4, 8} measures end-to-end decode tok/s, acceptance rate,
      and rollback rounds — with the greedy streams asserted
      token-for-token equal to the baseline's (the DESIGN.md §10
      exactness contract, also enforced in tests/test_spec_decode.py).

    The win mechanism: a fully-accepted round commits k+1 tokens for ONE
    chunk-parallel verify call, while plain decode pays k+1 sequential
    full-model steps.
    """
    from repro.configs import get_config
    from repro.models import lm
    from repro.models.param import init_params
    from repro.optim import adamw
    from repro.serving import Engine, GenRequest, SpecConfig

    cfg = get_config("hla-1b", reduced=True).replace(
        n_layers=4, d_model=256, n_heads=4, n_kv_heads=4, d_ff=768,
        vocab=512,
    )
    train_steps, period = 120, 16
    pattern = np.random.RandomState(0).permutation(
        np.arange(2, 2 + period)
    ).astype(np.int64)
    seq = np.tile(pattern, 8)  # the cyclic language

    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    opt = adamw.init_opt_state(params)
    oc = adamw.OptConfig(lr=3e-3, warmup_steps=10, total_steps=train_steps)

    @jax.jit
    def train_step(params, opt, tokens, labels):
        (l, _), g = jax.value_and_grad(lm.lm_loss, has_aux=True)(
            params, tokens, labels, cfg
        )
        params, opt, _ = adamw.adamw_update(params, g, opt, oc)
        return params, opt, l

    t0 = time.perf_counter()
    for s in range(train_steps):
        offs = np.random.RandomState(s).randint(0, period, 8)
        toks = np.stack([np.roll(seq, -o)[:64] for o in offs])
        params, opt, loss = train_step(
            params, opt, jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
        )
    rows.append((
        "spec/train_workload", 0.0, 0.0,
        f"steps={train_steps} final_loss={float(loss):.1e} "
        f"train_s={time.perf_counter() - t0:.1f}",
    ))

    slots, gen_len = 2, 96
    prompt = np.tile(pattern, 2)
    mk_reqs = lambda: [  # noqa: E731
        GenRequest(rid=i, prompt=np.roll(prompt, -i), max_new=gen_len)
        for i in range(4)
    ]

    def measure(spec):
        eng = Engine(
            cfg, params, slots=slots,
            max_len=len(prompt) + gen_len + 16, block=8, spec=spec,
        )
        eng.run([GenRequest(rid=-1, prompt=prompt, max_new=16)])  # warm jits
        eng.obs.reset()  # fresh metrics epoch for the measured traffic
        eng.reset_breaker()  # warmup zero-acceptance must not leak
        results = eng.run(mk_reqs())
        st = eng.stats
        decode_toks = sum(len(r.tokens) - 1 for r in results)
        return decode_toks / max(st["decode_s"], 1e-9), st, results

    plain_tps, _, plain_res = measure(None)
    rows.append((
        "spec/plain_decode", 0.0, 0.0, f"tok_per_s={plain_tps:.1f} block=8",
    ))
    _metric(rows, "spec/plain_decode/tok_per_s", plain_tps,
            unit="tok/s", direction="higher")
    entries = []
    for k in (2, 4, 8):
        tps, st, res = measure(SpecConfig(k=k, drafter="ngram"))
        # correctness sanity: greedy spec streams must equal plain greedy
        assert [r.tokens for r in res] == [r.tokens for r in plain_res], (
            f"speculative greedy diverged from plain greedy at k={k}"
        )
        acc = st["spec_accepted"] / max(st["spec_drafted"], 1)
        ent = {
            "k": k,
            "tok_per_s": round(tps, 1),
            "speedup": round(tps / max(plain_tps, 1e-9), 2),
            "acceptance": round(acc, 3),
            "rounds": st["spec_rounds"],
            "rollback_rounds": st["spec_replays"],
        }
        entries.append(ent)
        rows.append((
            f"spec/ngram_k{k}", 0.0, 0.0,
            f"tok_per_s={tps:.1f} speedup={ent['speedup']}x "
            f"acceptance={acc:.2f}",
        ))
        _metric(rows, f"spec/ngram_k{k}/tok_per_s", tps,
                unit="tok/s", direction="higher")
    write_results("spec", {
        "backend": jax.default_backend(),
        "shape": {"slots": slots, "prompt_len": len(prompt),
                  "gen_len": gen_len, "requests": 4,
                  "drafter": "ngram", "model": "hla2-4L-256d",
                  "workload": f"cyclic period-{period} (trained)"},
        "plain_tok_per_s": round(plain_tps, 1),
        "entries": entries,
    })


def bench_distributed(rows):
    """Multi-device scaling: train-step tok/s per device, 1 -> 8 host
    devices (each device count runs in a fresh subprocess because XLA
    locks the host platform device count at first init).

    The subprocess drives the REAL sharded train step
    (``distributed.steps.make_train_step`` on a ("data", "model") mesh
    from ``launch.mesh.make_mesh``) over the reduced paper model.  The
    children are CPU-only: a child that reached for the chip would find it
    held by this process.  The absolute numbers are smoke-level; the
    per-device ratio tracks sharding overhead.
    """
    import subprocess
    import sys
    import textwrap

    B, n, steps = 8, 64, 6
    # single source for the shape: injected into the subprocess source AND
    # recorded in results/distributed.json below
    body = f"B, n, steps = {B}, {n}, {steps}\n" + textwrap.dedent("""
        import json, time, functools
        import jax, jax.numpy as jnp
        from repro.configs import get_config
        from repro.distributed import steps as steps_mod, sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models.param import init_params
        from repro.optim import adamw
        from repro.data.pipeline import DataConfig, SyntheticStream

        cfg = get_config("hla-1b", reduced=True)
        specs = steps_mod.model_specs(cfg)
        mesh = make_mesh()
        stream = SyntheticStream(DataConfig(cfg.vocab, n, B, seed=0))
        with mesh:
            ps = shd.param_shardings(specs, mesh)
            params = jax.jit(functools.partial(init_params, specs),
                             out_shardings=ps)(jax.random.key(0))
            opt = adamw.init_opt_state(params)
            step = jax.jit(steps_mod.make_train_step(
                cfg, adamw.OptConfig(total_steps=steps),
                grad_shardings=ps))
            place = lambda b: {
                k: jax.device_put(jnp.asarray(v),
                                  shd.batch_sharding(mesh, v.shape))
                for k, v in b.items()}
            params, opt, m = step(params, opt, place(stream.batch(0)))
            jax.block_until_ready(m["loss"])  # compile + warm
            t0 = time.perf_counter()
            for s in range(1, steps + 1):
                params, opt, m = step(params, opt, place(stream.batch(s)))
            jax.block_until_ready(m["loss"])
            dt = (time.perf_counter() - t0) / steps
        ndev = len(jax.devices())
        print(json.dumps({
            "devices": ndev,
            "steps_per_s": round(1.0 / dt, 3),
            "tok_per_s": round(B * n / dt, 1),
            "tok_per_s_per_device": round(B * n / dt / ndev, 1),
        }))
    """)
    entries = []
    for ndev in (1, 8):
        env = dict(os.environ)
        # the children run on forced host devices by design; keep them off
        # any accelerator, which the parent process may already hold
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
        env["PYTHONPATH"] = os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
        )
        proc = subprocess.run(
            [sys.executable, "-c", body], capture_output=True, text=True,
            timeout=900, env=env,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        r = json.loads(proc.stdout.strip().splitlines()[-1])
        entries.append(r)
        rows.append((
            f"distributed/train_dev{r['devices']}",
            1e6 / r["steps_per_s"], 0.0,
            f"tok_per_s={r['tok_per_s']} per_device={r['tok_per_s_per_device']}",
        ))
        _metric(rows, f"distributed/train_dev{r['devices']}/tok_per_s",
                r["tok_per_s"], unit="tok/s", direction="higher")
    write_results("distributed", {
        "backend": "cpu-host-mesh",
        "shape": {"B": B, "n": n, "arch": "hla-1b-reduced"},
        "entries": entries,
    })


BENCHES = {
    "bench_equivalence": bench_equivalence,
    "bench_complexity": bench_complexity,
    "bench_statesize": bench_statesize,
    "bench_chunkwidth": bench_chunkwidth,
    "bench_kernels": bench_kernels,
    "bench_train_step": bench_train_step,
    "bench_decode_throughput": bench_decode_throughput,
    "bench_serving": bench_serving,
    "bench_ops": bench_ops,
    "bench_spec": bench_spec,
    "bench_distributed": bench_distributed,
}

# bench_distributed spawns its own multi-device subprocesses — too slow
# for the default everything run; select it explicitly.
DEFAULT_BENCHES = [k for k in BENCHES if k != "bench_distributed"]


def main(argv=None) -> None:
    """``python -m benchmarks.run [bench_name ...]`` — no args runs the
    default set (everything except the subprocess-spawning
    ``bench_distributed``)."""
    import argparse

    from repro.obs import Obs
    from repro.obs.perf import BenchHistory, profile_capture
    from repro.runtime.compile_cache import enable_compile_cache

    ap = argparse.ArgumentParser(
        prog="python -m benchmarks.run",
        description="Benchmark harness; no names runs the default set.",
    )
    ap.add_argument("benches", nargs="*", metavar="bench_name",
                    help=f"subset of {list(BENCHES)}")
    ap.add_argument("--history", default=None, metavar="PATH",
                    help="append this run's rows to a repro.obs.bench/v1 "
                         "history JSONL (perfcheck's input)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the whole run "
                         "into DIR (view with TensorBoard / Perfetto)")
    args = ap.parse_args(argv)

    names = args.benches or list(DEFAULT_BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        raise SystemExit(f"unknown benches {unknown}; have {list(BENCHES)}")
    enable_compile_cache()
    rows = RowSink()
    obs = Obs()
    with profile_capture(args.profile_dir, obs=obs):
        for n in names:
            with obs.span("bench.run", bench=n):
                BENCHES[n](rows)
    print("name,us_per_call,iqr_us,derived")
    for name, us, iqr, derived in rows:
        print(f"{name},{us:.1f},{iqr:.1f},{derived}")
    if args.history:
        hist = BenchHistory(args.history)
        for name, us, iqr, derived in rows:
            if us > 0:
                hist.bench_row(name, us, unit="us", direction="lower",
                               dispersion=iqr)
        for m in rows.metrics:
            hist.bench_row(m["name"], m["value"], unit=m["unit"],
                           direction=m["direction"],
                           dispersion=m["dispersion"])
        print(f"# history: {hist.rows_written} rows appended to "
              f"{args.history} (run {hist.run_id})")


if __name__ == "__main__":
    main()

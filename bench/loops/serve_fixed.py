"""Closed-loop serving on one plan, measured over whole rounds of it.

A closed mix served as ``serve`` serves it, with two changes that make
the measured stretch the same work on every seed:

* one plan of sizes: every seed sends the same requests in the same
  order (the prompt and output lengths the traffic generator draws for
  seed 0); the seed draws only the prompts' tokens and the weights.  The
  engine's ticks (admissions into free slots, then one decode block) are
  then one sequence for every seed, in work if not in time;
* a window of whole rounds: a round is ``clients`` admissions.  The
  window opens at the start of the first tick after ``lead_s`` that
  begins a round and closes at the start of the first tick, at least
  ``--seconds`` later, that begins one.

Where prompts are long enough that admissions take a large share of the
device's time (``longdoc-24k``: 8k-24k-token prompts, about 47% of the
ticks' time), a window of a fixed length in time holds as many whole
prefills as the phase at its ends allows, and that phase drifts with
the host's share of each run.  Over six seeds on one TPU v5e, with the
sizes in another order for each seed (``serve``), ``gen_tok_s`` spread
7.3% and ``tpot_p95_ms`` 7.1%; with one plan and a window of fixed
length, ``gen_tok_s`` still spread 0.4-3.8% between sets of six.  Over
whole rounds of one plan, the window holds the same ticks on every seed
unless a run's drift reaches the tick that begins a round.

The metrics and the check read as in ``serve``: ``setup_s`` up to the
end of the lead (the wait for the round to begin is not set-up), and
``tpot_p95_ms``, ``gen_tok_s`` and the per-layer readings over the
window; a traced run profiles the last ``trace_seconds`` before
``--seconds`` have passed.
"""

from __future__ import annotations

import time

import numpy as np

from bench import common, trace_reduce, traffic
from bench.loops import serve


def closed_requests(t: dict, seed: int, vocab: int, per_client: int):
    """Per client, a list of (prompt, max_new) pairs: the sizes of seed 0
    for every seed, the tokens from ``seed``."""
    rng = traffic.rng_for(seed, 4)
    return [[(rng.integers(0, vocab, len(p), dtype=np.int32), m)
             for p, m in reqs]
            for reqs in traffic.closed_requests(t, 0, vocab, per_client)]


class Recorder(serve.Recorder):
    """``serve.Recorder`` that also counts the load's admissions (a
    request's first token comes at its admission's sync)."""

    def __init__(self):
        super().__init__()
        self.admitted = 0

    def on_stream(self, rid, toks, result):
        if toks and rid < serve.WARM_RID and rid not in self.first:
            self.admitted += 1
        super().on_stream(rid, toks, result)


def run(ctx: common.Context) -> dict:
    import jax

    from repro.obs import Obs
    from repro.serving import GenRequest
    from repro.serving.engine import Engine
    from repro.serving.sampling import SamplingConfig

    c, t = ctx.cfg, ctx.traffic
    ref, prog = common.arch(c)
    devs = common.devices(ctx.cell["chips"], ctx.require_chip)
    pcfg = prog.program_config(c)
    params = jax.jit(lambda k: prog.to_program(ref.make_weights(c, k)))(
        ref.seed_key(ctx.seed))
    prog.check_tree(pcfg, params)
    eng = Engine(pcfg, params, slots=t["slots"], max_len=t["max_len"],
                 block=t["block"], sampling=SamplingConfig("greedy"),
                 obs=Obs(ring=1 << 20))
    rec = Recorder()
    eng.on_stream = rec.on_stream
    clients = closed_requests(t, ctx.seed, c["vocab"], t["per_client"])
    serve._warm(eng, t, [], c["vocab"], traffic.rng_for(ctx.seed, 9),
                GenRequest)

    # ---- the load: each client sends its next request as one finishes ---
    info = {}  # rid -> {"prompt", "owner", "turn"}, as serve's schedule

    def submit(owner, turn):
        if turn < len(clients[owner]):
            prompt, max_new = clients[owner][turn]
            rid = len(info)
            info[rid] = {"prompt": prompt, "owner": owner, "turn": turn}
            eng.submit(GenRequest(rid=rid, prompt=prompt, max_new=max_new))

    round_len = t["clients"]

    def tick():
        """One engine tick and the follow-ups it frees; returns (start,
        tokens before it, the round it began or None), or None once the
        plan is served."""
        if not (len(eng.scheduler) or eng.active.any()):
            return None
        start, tokens, admitted = (time.perf_counter(), rec.tokens,
                                   rec.admitted)
        eng._drive_tick()
        with jax.profiler.TraceAnnotation("bench.submit"):
            while rec.finished:
                rid = rec.finished.pop(0)
                r = info.get(rid)
                if r is not None and rec.done[rid][1].status == "ok":
                    submit(r["owner"], r["turn"] + 1)
        began = rec.admitted // round_len > admitted // round_len
        return start, tokens, rec.admitted // round_len if began else None

    def drive(until):
        while time.perf_counter() < until:
            if tick() is None:
                return

    def to_round():
        """Ticks until one begins a round: its (start, tokens before, the
        round)."""
        while True:
            step = tick()
            if step is None:
                return (time.perf_counter(), rec.tokens,
                        -(-rec.admitted // round_len))
            if step[2] is not None:
                return step

    t_gen0 = time.perf_counter()
    for owner in range(len(clients)):
        submit(owner, 0)
    drive(t_gen0 + t.get("lead_s", 0.0))
    setup_s = time.perf_counter() - ctx.t_start
    counter = common.CompileCounter()
    counter.active = True
    t0, tokens0, round0 = to_round()
    end = t0 + ctx.seconds
    tr: dict = {}
    if ctx.trace:
        drive(end - min(ctx.seconds, t.get("trace_seconds", ctx.seconds)))
    with common.traced(ctx.trace, tr):
        drive(end)
    t_end, tokens1, round1 = to_round()
    counter.active = False
    device = common.device_info(devs)

    # ---- the window's readings, as serve's ----------------------------
    tpot, finished = [], []
    for rid, (when, res) in rec.done.items():
        if rid >= serve.WARM_RID or not (t0 <= when <= t_end):
            continue
        if res.status != "ok":
            continue
        finished.append(rid)
        n = len(res.tokens)
        if n >= 2:
            tpot.append((rec.last[rid] - rec.first[rid]) / (n - 1))
    failed = sum(1 for rid, (_, res) in rec.done.items()
                 if rid < serve.WARM_RID and res.status != "ok")
    window_s = t_end - t0
    metrics = {"setup_s": {"value": setup_s, "unit": "s"},
               "gen_tok_s": {"value": (tokens1 - tokens0) / window_s,
                             "unit": "tokens/s"}}
    if tpot:
        metrics["tpot_p95_ms"] = {
            "value": 1e3 * common.percentile(tpot, 95), "unit": "ms"}

    evs = eng.obs.events
    obs = {"kind": "serve", "cfg": c, "traffic": t, "chips": len(devs),
           "device_kind": device["kind"], "trace": None, "counters": {},
           "ttft_p95_ms": None,
           "decode_block_s": [e["dur_s"] for e in evs("engine.decode_block",
                                                      kind="span")
                              if t0 <= e["ts"] <= t_end]}
    breakdown = None
    if ctx.trace:
        lo, hi = tr["t0"], tr["t1"]
        obs["prefills"] = [e["prompt_len"] - e.get("cached_prefix", 0)
                           for e in evs("request.admitted")
                           if lo <= e["ts"] <= hi]
        obs["decode_steps"] = [
            e["slots_active"] for e in evs("engine.decode_block", kind="span")
            if lo <= e["ts"] <= hi for _ in range(e["steps"])]
        summ = trace_reduce.reduce_trace(
            tr["dir"], attribute=["bench.", "engine.", "train."])
        common.remove_tree(tr["dir"])
        obs["trace"] = summ
        device["busy_s"] = summ.busy_ns / 1e9
        device["window_s"] = summ.busy_window_ns / 1e9
        breakdown = {"device_ops": summ.top_ops, "idle_gaps": summ.idle_gaps}

    # ---- correctness: the sample against the reference ------------------
    sample = serve._sample(finished, info, rec, t, ctx.seed)
    del eng, params
    common.free_device_memory()
    pairs = [(info[r]["prompt"], rec.done[r][1].tokens) for r in sample]
    gaps = serve.reference_gaps(c, t, ctx.seed, pairs)
    checks = {}
    if "logit_gap_mean" in ctx.limits:
        checks["logit_gap_mean"] = {"value": gaps["mean"],
                                    "limit": ctx.limits["logit_gap_mean"]}
    if not sample:
        checks["sampled_requests"] = {"value": 0.0, "limit": -1.0}
    extra = {
        "longest_idle_gaps": obs["trace"].longest_gaps if ctx.trace else [],
        "compiles_in_window": counter.n,
        "requests_finished": len(finished),
        "sampled_tokens": sum(len(rec.done[r][1].tokens) for r in sample),
        "widest_logit_gap": gaps["widest"],
        "window_s": window_s,
        "window_rounds": round1 - round0,
        "window_admissions": sum(1 for rid, ts in rec.first.items()
                                 if rid < serve.WARM_RID
                                 and t0 <= ts < t_end),
    }
    return {"metrics": metrics, "obs": obs, "checks": checks,
            "attempted": len(finished) + failed, "failed": failed,
            "device": device, "breakdown": breakdown, "extra": extra,
            "sample": pairs}

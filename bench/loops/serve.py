"""Serving cells: the program's ``Engine`` under open- or closed-loop load.

The window drives ``Engine.submit`` and ``Engine._drive_tick`` from one
thread.  Every request's times are taken on the host by the engine's own
streaming hook (``Engine.on_stream``), which it calls right after each
token transfer: a request's first token is at its admission's sync, later
tokens at their decode block's sync.

* ``ttft_p95_ms`` (sessions; reported per layer as ``ttft_p95_ms.sessions``,
  its runs spread too widely for an end-to-end bound): over the requests
  due in the window, from the moment the schedule made each due to its
  first token.  After the window the
  engine runs on, without new arrivals, until each of them has its first
  token or a minute has passed; a request that fails, or never gets a
  first token, counts with the wait until then.
* ``tpot_p95_ms``: over the requests finished in the window, (last-token
  time - first-token time) / (output tokens - 1).
* ``gen_tok_s``: tokens delivered from the window's start to the end of
  its last tick, over that time.

Correctness: once the window has closed and the engine is freed, a sample
of ``check_requests`` requests finished in the window (so as many slots),
drawn from the seed with the longest among them, is run through the
float32 reference over prompt and served tokens.  At each served (greedy)
token the gap is how far the token's reference logit lies below the
reference's best logit.  The number compared is the mean gap over every
served token of the sample (``logit_gap_mean``); the widest gap is
reported beside it (``widest_logit_gap``) and not compared: after a long
greedy repetition the model is ill-conditioned at a few positions, where
any bfloat16 computation of it departs from the float32 reference.
"""

from __future__ import annotations

import heapq
import time

import numpy as np

from bench import common, costs, trace_reduce
from bench import traffic as traffic_mod

WARM_RID = 1 << 40


class Recorder:
    """Host times of every request's tokens, from the engine's hook."""

    def __init__(self):
        self.first, self.last = {}, {}
        self.done = {}
        self.finished = []
        self.tokens = 0  # every token delivered so far

    def on_stream(self, rid, toks, result):
        now = time.perf_counter()
        if toks:
            self.first.setdefault(rid, now)
            self.last[rid] = now
            self.tokens += len(toks)
        if result is not None:
            self.done[rid] = (now, result)
            self.finished.append(rid)


class Schedule:
    """Requests by the time they fall due.  ``pop_due`` hands out every
    request due by ``upto`` and stamps it with the time it was handed
    out; the generator's lateness is that stamp less the due time."""

    def __init__(self):
        self.info = {}  # rid -> dict(due, submit, and the request's fields)
        self.pending = []  # heap of (due, rid)

    def add(self, due: float, **fields) -> int:
        rid = len(self.info)
        self.info[rid] = {"due": due, "submit": None, **fields}
        heapq.heappush(self.pending, (due, rid))
        return rid

    def pop_due(self, upto: float, now: float):
        out = []
        while self.pending and self.pending[0][0] <= upto:
            _, rid = heapq.heappop(self.pending)
            self.info[rid]["submit"] = now
            out.append((rid, self.info[rid]))
        return out

    def lateness(self, rids):
        return [self.info[r]["submit"] - self.info[r]["due"] for r in rids
                if self.info[r]["submit"] is not None]


def _prompt_lengths(t: dict):
    """Every prompt length a sessions mix can send."""
    lo, hi, step = t["user_len"]
    out = set()
    lengths = {t["doc_len"]}
    for turn in range(t["turns"]):
        grow = set()
        for base in lengths:
            for u in range(lo, hi + 1, step):
                grow.add(base + u + (t["answer_len"] if turn else 0))
        lengths = grow
        out |= grow
    return sorted(out)


def _warm(eng, t, docs, vocab, rng, GenRequest):
    """Compile (or load) every program shape this cell's traffic uses, and
    put each document's state in the prefix cache."""
    import jax.numpy as jnp

    rid = [WARM_RID]

    def req(prompt, n):
        rid[0] += 1
        return GenRequest(rid=rid[0], prompt=np.asarray(prompt, np.int32),
                          max_new=n)

    noise = lambda m: rng.integers(0, vocab, m, dtype=np.int32)  # noqa: E731
    blocks = 2 * eng.block
    if t["kind"] == "sessions":
        g = t["cache_granularity"]
        for d in docs:  # the document's snapshot at its chunk boundary
            eng.run([req(np.concatenate([d, noise(g)]), 1)])
        lo, hi, step = t["user_len"]
        for u in range(lo + g, hi + 2 * g + 1, step):  # every resumed shape
            eng.run([req(np.concatenate([docs[0], noise(u)]), 1)])
        eng.run([req(np.concatenate([docs[0], noise(g)]), blocks)])
        # the engine slices each prompt by its length and segment length
        segs = sorted({g} | set(range(g, hi + 2 * g + 1, step)))
        for L in _prompt_lengths(t):
            x = jnp.zeros((1, L), jnp.int32)
            for s in segs:
                if s < L:
                    x[:, L - s:].block_until_ready()
    else:
        for p in traffic_mod.grid(t["prompt_len"]):
            eng.run([req(noise(p), blocks)])


def run(ctx: common.Context) -> dict:
    import jax

    from repro.obs import Obs
    from repro.serving import GenRequest
    from repro.serving.cache import PrefixCache
    from repro.serving.engine import Engine
    from repro.serving.sampling import SamplingConfig

    c, t = ctx.cfg, ctx.traffic
    ref, prog = common.arch(c)
    devs = common.devices(ctx.cell["chips"], ctx.require_chip)
    pcfg = prog.program_config(c)
    key = ref.seed_key(ctx.seed)
    vocab = c["vocab"]
    params = jax.jit(lambda k: prog.to_program(ref.make_weights(c, k)))(key)
    prog.check_tree(pcfg, params)
    cache = None
    if t.get("cache_entries"):
        cache = PrefixCache(
            granularity=t["cache_granularity"],
            budget_bytes=t["cache_entries"] * costs.state_bytes(c))
    eng = Engine(pcfg, params, slots=t["slots"], max_len=t["max_len"],
                 block=t["block"], sampling=SamplingConfig("greedy"),
                 cache=cache, obs=Obs(ring=1 << 20))
    rec = Recorder()
    eng.on_stream = rec.on_stream
    rng = traffic_mod.rng_for(ctx.seed, 9)

    lead = t.get("lead_s", 0.0)
    horizon = lead + ctx.seconds
    if t["kind"] == "sessions":
        docs, sess = traffic_mod.sessions(t, ctx.seed, vocab, horizon)
    else:
        docs = []
        clients = traffic_mod.closed_requests(t, ctx.seed, vocab,
                                              t["per_client"])
    _warm(eng, t, docs, vocab, rng, GenRequest)

    # ---- the load: open-loop sessions or closed-loop clients -------------
    load = Schedule()
    info, pending = load.info, load.pending

    def schedule(due, prompt, max_new, owner, turn):
        load.add(due, prompt=prompt, max_new=max_new, owner=owner, turn=turn)

    t_gen0 = time.perf_counter()
    if t["kind"] == "sessions":
        for s in sess:
            schedule(t_gen0 + s.arrival,
                     np.concatenate([docs[s.doc], s.user[0]]),
                     t["answer_len"], s.sid, 0)
    else:
        for i, reqs in enumerate(clients):
            schedule(t_gen0, reqs[0][0], reqs[0][1], i, 0)

    def follow_up(rid):
        now, res = rec.done[rid]
        r = info.get(rid)
        if r is None or res.status != "ok":
            return
        nxt = r["turn"] + 1
        if t["kind"] == "sessions":
            s = sess[r["owner"]]
            if nxt < t["turns"]:
                prompt = np.concatenate([r["prompt"],
                                         np.asarray(res.tokens, np.int32),
                                         s.user[nxt]])
                schedule(now + s.think[nxt - 1], prompt, t["answer_len"],
                         r["owner"], nxt)
        elif nxt < len(clients[r["owner"]]):
            p, m = clients[r["owner"]][nxt]
            schedule(now, p, m, r["owner"], nxt)

    def submit_due(upto):
        for rid, r in load.pop_due(upto, time.perf_counter()):
            eng.submit(GenRequest(rid=rid, prompt=r["prompt"],
                                  max_new=r["max_new"]))

    def drive(until, arrivals=True, stop=None):
        while time.perf_counter() < until:
            if stop is not None and stop():
                return
            if arrivals:
                with jax.profiler.TraceAnnotation("bench.submit"):
                    submit_due(time.perf_counter())
            if len(eng.scheduler) or eng.active.any():
                eng._drive_tick()
            else:
                now = time.perf_counter()
                nxt = pending[0][0] if (pending and arrivals) else until
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, min(nxt, until) - now))
            while rec.finished:
                follow_up(rec.finished.pop(0))

    reg = eng.obs.registry

    def counters():
        hit = reg.get("cache_hit_prefix_tokens")
        return {"prompt_tokens": reg.get(
                    "serving_prompt_tokens_total").total(),
                "hit_tokens": hit.sum() if hit is not None else 0.0}

    drive(t_gen0 + lead)
    counter = common.CompileCounter()
    counter.active = True
    t0 = time.perf_counter()
    setup_s = t0 - ctx.t_start
    end = t0 + ctx.seconds
    tokens0 = rec.tokens
    c0 = counters()
    tr: dict = {}
    trace_s = min(ctx.seconds, t.get("trace_seconds", ctx.seconds))
    if ctx.trace:
        drive(end - trace_s)
    with common.traced(ctx.trace, tr):
        drive(end)
    t_end = time.perf_counter()
    window_tokens = rec.tokens - tokens0
    counter.active = False
    c1 = counters()

    # ---- after the window: first tokens of every request due in it ------
    due_in = [rid for rid, r in info.items() if t0 <= r["due"] < end]
    if t["kind"] == "sessions":
        submit_due(end)

        def settled():
            return all(rid in rec.first or (rid in rec.done and
                                            rec.done[rid][1].status != "ok")
                       for rid in due_in)

        drive(t_end + 60.0, arrivals=False, stop=settled)
    t_drain = time.perf_counter()
    device = common.device_info(devs)

    ttft = []
    failed = 0
    for rid in due_in:
        bad = rid in rec.done and rec.done[rid][1].status != "ok"
        if rid in rec.first and not bad:
            ttft.append(rec.first[rid] - info[rid]["due"])
        else:
            failed += 1
            ttft.append(t_drain - info[rid]["due"])
    tpot = []
    finished = []
    for rid, (when, res) in rec.done.items():
        if rid >= WARM_RID or not (t0 <= when <= end):
            continue
        if res.status != "ok":
            continue
        finished.append(rid)
        n = len(res.tokens)
        if n >= 2:
            tpot.append((rec.last[rid] - rec.first[rid]) / (n - 1))
    if t["kind"] != "sessions":
        failed = sum(1 for rid, (w, res) in rec.done.items()
                     if rid < WARM_RID and res.status != "ok")
    late = load.lateness(due_in)
    window_s = t_end - t0
    metrics = {"setup_s": {"value": setup_s, "unit": "s"}}
    if ttft and t["kind"] == "sessions":
        metrics["ttft_p95_ms"] = {
            "value": 1e3 * common.percentile(ttft, 95), "unit": "ms"}
    if tpot:
        metrics["tpot_p95_ms"] = {
            "value": 1e3 * common.percentile(tpot, 95), "unit": "ms"}
    metrics["gen_tok_s"] = {"value": window_tokens / window_s,
                            "unit": "tokens/s"}

    # ---- per-layer readings ------------------------------------------------
    evs = eng.obs.events
    obs = {"kind": "serve", "cfg": c, "traffic": t, "chips": len(devs),
           "device_kind": device["kind"], "trace": None,
           "counters": {k: c1[k] - c0[k] for k in c0},
           "ttft_p95_ms": metrics.get("ttft_p95_ms", {}).get("value"),
           "decode_block_s": [e["dur_s"] for e in evs("engine.decode_block",
                                                      kind="span")
                              if t0 <= e["ts"] <= end]}
    breakdown = None
    if ctx.trace:
        lo, hi = tr["t0"], tr["t1"]
        obs["prefills"] = [
            e["prompt_len"] - e.get("cached_prefix", 0)
            for e in evs("request.admitted") if lo <= e["ts"] <= hi]
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
    sample = _sample(finished, info, rec, t, ctx.seed)
    del eng, params, cache
    common.free_device_memory()
    pairs = [(info[r]["prompt"], rec.done[r][1].tokens) for r in sample]
    gaps = reference_gaps(c, t, ctx.seed, pairs)
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
        "generator_late_p95_ms": 1e3 * common.percentile(late, 95)
        if late else 0.0,
    }
    return {"metrics": metrics, "obs": obs, "checks": checks,
            "attempted": len(due_in) if t["kind"] == "sessions"
            else len(finished) + failed,
            "failed": failed, "device": device, "breakdown": breakdown,
            "extra": extra,
            "sample": pairs}


def _sample(finished, info, rec, t, seed):
    """The longest finished request, then others in seeded order, to
    ``check_requests`` requests."""
    if not finished:
        return []
    size = lambda r: len(info[r]["prompt"]) + len(rec.done[r][1].tokens)  # noqa: E731,E501
    longest = max(finished, key=size)
    rest = [r for r in sorted(finished) if r != longest]
    traffic_mod.rng_for(seed, 7).shuffle(rest)
    return [longest] + rest[: t["check_requests"] - 1]


def reference_gaps(c, t, seed, pairs, lowp=None):
    """Gaps, over every served token of ``pairs`` (prompt, served tokens),
    between the reference's best logit and the logit of the token served:
    their mean (``"mean"``) and the widest (``"widest"``).  With ``lowp``
    the token served is the one the reference computed at that precision
    puts first (the control)."""
    import jax
    import jax.numpy as jnp

    ref, _ = common.arch(c)
    W = jax.jit(lambda k: ref.make_weights(c, k))(ref.seed_key(seed))
    P, M = t["check_len"], t["check_max_new"]

    @jax.jit
    def gaps(W, toks, pos, served, valid):
        with jax.default_matmul_precision("highest"):
            lg = ref.logits_at(c, W, toks, pos)
            if lowp is not None:
                served = jnp.argmax(ref.logits_at(c, W, toks, pos,
                                                  lowp=lowp), -1)
        best = jnp.max(lg, -1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        g = jnp.where(valid, best - got, 0.0)
        return jnp.sum(g), jnp.max(g)

    total, widest, count = 0.0, 0.0, 0
    for prompt, served in pairs:
        served = np.asarray(served, np.int32)
        L, n = len(prompt), len(served)
        seq = np.zeros(P, np.int32)
        seq[:L] = prompt
        seq[L:L + n - 1] = served[:-1]
        pos = np.zeros(M, np.int32)
        pos[:n] = np.arange(L - 1, L + n - 1)
        srv = np.zeros(M, np.int32)
        srv[:n] = served
        valid = np.arange(M) < n
        s, w = gaps(W, seq, pos, srv, valid)
        total += float(s)
        widest = max(widest, float(w))
        count += n
    return {"mean": total / max(count, 1), "widest": widest}

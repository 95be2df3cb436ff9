"""Serving CLI — a thin shell over ``repro.serving.Engine``.

Continuous batching over fixed slots with chunk-parallel prefill
admission, step-locked block decode, and device-side sampling
(DESIGN.md §8).  Synthetic prompts stand in for traffic.

    PYTHONPATH=src python -m repro.launch.serve --arch hla-1b --reduced \
        --slots 4 --requests 8 --gen-len 32 --block 8 --sampling greedy

``--spec ngram|lm`` turns on speculative decoding (DESIGN.md §10): the
drafter proposes ``--spec-k`` tokens per round, one chunk-parallel verify
call scores them, rejections roll back via state snapshots.  ``--spec lm``
drafts with a small HLA LM loaded from the ``--draft-arch`` registry entry
(random weights here — the CLI has no trained draft checkpoint).

``--inject point[@at[+]][:arg]`` (repeatable) schedules deterministic
faults from the ``runtime.faults`` catalog — e.g.
``--inject engine.nan_state@1:0`` poisons slot 0's state before the 2nd
decode block (quarantine), ``--inject drafter.propose@0+`` crashes the
drafter every round (circuit breaker -> plain fallback).  ``--deadline-s``
gives every request a wall-clock budget; expired requests finish with
``status="timeout"``.  The summary line counts terminal statuses; without
``--inject``, a request that ends in ``error`` makes the command exit
nonzero.

``HOST_DEVICES=N`` simulates an N-device host mesh (like launch.train);
params and slot states then come up sharded via the same
``distributed.sharding`` / ``distributed.steps`` source of truth the
trainer uses.
"""

import os

# must run at import, before jax initializes its backend: XLA locks the
# host device count on first use (same contract as launch/train.py)
_hd = os.environ.get("HOST_DEVICES")
if _hd:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_hd} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse  # noqa: E402
import collections  # noqa: E402
import functools  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from ..configs import get_config  # noqa: E402
from ..distributed import sharding as shd  # noqa: E402
from ..models import lm  # noqa: E402
from ..models.param import init_params  # noqa: E402
from ..obs import JsonlSink, Obs, profile_capture, write_metrics  # noqa: E402
from ..runtime.compile_cache import enable_compile_cache  # noqa: E402
from ..runtime.faults import FaultPlan, parse_fault  # noqa: E402
from ..serving import (  # noqa: E402
    Engine,
    GenRequest,
    PrefixCache,
    SamplingConfig,
    SpecConfig,
)
from .mesh import make_mesh, mesh_summary  # noqa: E402


def _run_streaming(engine, requests):
    """Serve through the asyncio front-end: every request submitted
    concurrently, each stream consumed by its own task, graceful drain
    on exit.  Results come back in request order (same contract as
    ``engine.run``)."""
    import asyncio

    from ..serving.server import AsyncServer, collect

    async def _main():
        async with AsyncServer(engine) as srv:
            outs = await asyncio.gather(*[collect(srv, r)
                                          for r in requests])
        return [res for _, res in outs]

    return asyncio.run(_main())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hla-1b")
    ap.add_argument("--mixer", default=None,
                    help="override the arch's sequence op with any "
                         "registered SequenceOp (e.g. gla, ahla, linattn; "
                         "DESIGN.md §11) — the engine gates on the op's "
                         "streaming capability flag")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--block", type=int, default=8)
    ap.add_argument("--sampling", default="greedy",
                    choices=["greedy", "temperature", "top_k", "top_p"])
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--top-k", type=int, default=40)
    ap.add_argument("--top-p", type=float, default=0.9)
    ap.add_argument("--spec", default="off",
                    choices=["off", "ngram", "lm"],
                    help="speculative decoding drafter (off = plain blocks)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens per speculative round")
    ap.add_argument("--draft-arch", default="hla-1b",
                    help="configs entry for the --spec lm draft model "
                         "(loaded reduced)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--stream", action="store_true",
                    help="serve through the asyncio streaming front-end "
                         "(serving.server.AsyncServer): per-token async "
                         "generators, backpressure, graceful drain")
    ap.add_argument("--cache-mb", type=float, default=0.0,
                    help="prefix/state cache budget in MiB (0 = no "
                         "cache); cache hits resume admission from an "
                         "O(1) state snapshot (DESIGN.md §16)")
    ap.add_argument("--cache-chunk", type=int, default=0,
                    help="cache key granularity in tokens (0 = the "
                         "model's chunk width)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens shared by every synthetic prompt — "
                         "nonzero exercises prefix-cache hits")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock budget; expiry -> "
                         "status=timeout with the partial stream")
    ap.add_argument("--inject", action="append", default=[],
                    metavar="POINT[@AT[+]][:ARG]",
                    help="schedule a deterministic fault "
                         "(runtime.faults catalog; repeatable)")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the final metrics registry snapshot "
                         "(repro.obs.metrics/v1 JSON) on exit")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="stream span/event records (repro.obs.events/v1 "
                         "JSONL) for the measured run — request "
                         "lifecycles, decode blocks, fired faults")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the measured "
                         "traffic (not the warmup) into DIR; "
                         "profile.start/stop anchors tie the trace's "
                         "clock to the obs stream's")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced, mixer=args.mixer)
    mesh = make_mesh()
    print(f"[serve] {cfg.name} on {mesh_summary(mesh)}")
    rng = np.random.RandomState(args.seed)
    with mesh:
        specs = lm.lm_specs(cfg)
        params = jax.jit(
            functools.partial(init_params, specs),
            out_shardings=shd.param_shardings(specs, mesh),
        )(jax.random.key(args.seed))
        spec = None
        if args.spec != "off":
            spec = SpecConfig(
                k=args.spec_k, drafter=args.spec,
                draft_arch=args.draft_arch, draft_reduced=args.reduced,
            )
        obs = Obs()
        engine = Engine(
            cfg, params,
            slots=args.slots,
            max_len=args.prompt_len + args.gen_len + 8,
            sampling=SamplingConfig(
                method=args.sampling, temperature=args.temperature,
                top_k=args.top_k, top_p=args.top_p,
            ),
            block=args.block,
            seed=args.seed,
            mesh=mesh,
            spec=spec,
            obs=obs,
        )
        shared = min(args.shared_prefix, args.prompt_len)
        prefix = rng.randint(2, cfg.vocab, size=shared)
        requests = [
            GenRequest(
                rid=i,
                prompt=np.concatenate([
                    prefix,
                    rng.randint(2, cfg.vocab,
                                size=args.prompt_len - shared),
                ]).astype(np.int64),
                max_new=args.gen_len,
                deadline_s=args.deadline_s,
            )
            for i in range(args.requests)
        ]
        # warm the prefill/decode jits so TTFT and tok/s measure steady
        # state, not trace+compile (same protocol as benchmarks.run).
        # Warmup MUST go through the measured execution mode: the jit
        # cache keys on the ambient mesh-context stack, and the streaming
        # server drives the engine from a worker thread where only the
        # engine's own mesh context is active — a main-thread-only warmup
        # would leave the measured run's first admissions to recompile.
        runner = _run_streaming if args.stream else (
            lambda eng, reqs: eng.run(reqs))
        runner(engine, [GenRequest(
            rid=-1, prompt=requests[0].prompt, max_new=args.block,
        )])
        cache = None
        if args.cache_mb > 0:
            gran = args.cache_chunk if args.cache_chunk else cfg.hla.chunk
            if shared and shared < gran + 1:
                print(f"[serve] note: shared prefix {shared} <= cache "
                      f"granularity {gran}: no cache hits possible")
            # warm the carry/resume jits against a throwaway cache so the
            # measured run's first hit pays a lookup, not a compile
            engine.cache = PrefixCache(
                granularity=gran, budget_bytes=int(args.cache_mb * 2**20))
            for rid in (-2, -3):  # miss + insert, then hit + resume
                runner(engine, [GenRequest(
                    rid=rid, prompt=requests[0].prompt, max_new=2)])
            cache = PrefixCache(
                granularity=gran, budget_bytes=int(args.cache_mb * 2**20),
                namespace=cfg.name, obs=engine.obs,
            )
            engine.cache = cache
        # fresh obs epoch: zero every metric series and drop warmup
        # events, so the artifacts below describe only measured traffic
        engine.obs.reset()
        engine.reset_breaker()  # warmup zero-acceptance must not leak
        sink = None
        if args.events_out:
            sink = JsonlSink(args.events_out)
            engine.obs.attach(sink)
        # attach the fault plan AFTER the warmup run so injection-point
        # hit counts start at the measured traffic, not at trace time
        if args.inject:
            engine.faults = FaultPlan(*[parse_fault(s) for s in args.inject])
        t0 = time.time()
        with profile_capture(args.profile_dir, obs=engine.obs):
            if args.stream:
                results = _run_streaming(engine, requests)
            else:
                results = engine.run(requests)
        dt = time.time() - t0
        st = engine.stats
        gen = st["generated_tokens"]
        # each request's first token comes from the prefill call; count only
        # decode-block tokens against decode wall time
        # (non-ok results may have produced no tokens at all)
        decode_toks = max(gen - len(results), 0)
        ttft = engine.obs.registry.get("serving_ttft_seconds")
        p50 = ttft.quantile(0.5) or 0.0
        p99 = ttft.quantile(0.99) or 0.0
        decode_tps = decode_toks / st["decode_s"] if st["decode_s"] else 0.0
        print(
            f"[serve] {len(results)} requests, {gen} generated tokens in "
            f"{dt:.2f}s | TTFT p50 {1e3 * p50:.1f}ms p99 {1e3 * p99:.1f}ms "
            f"| decode {decode_tps:.1f} tok/s | "
            f"prefill {st['prompt_tokens']/max(st['prefill_s'],1e-9):.1f} tok/s"
        )
        if spec is not None:
            acc = st["spec_accepted"] / max(st["spec_drafted"], 1)
            print(
                f"[serve] spec: {st['spec_rounds']} rounds, "
                f"acceptance {acc:.2f}, {st['spec_replays']} rollbacks, "
                f"{decode_toks/max(st['spec_rounds'],1):.2f} committed "
                "tok/round"
            )
        statuses = collections.Counter(r.status for r in results)
        status_str = " ".join(
            f"{k}={statuses[k]}" for k in ("ok", "error", "timeout",
                                           "cancelled") if statuses[k]
        )
        print(
            f"[serve] statuses: {status_str or 'ok=0'} | "
            f"quarantined={st['quarantined']} "
            f"breaker_trips={st['breaker_trips']}"
        )
        if cache is not None:
            cs = cache.stats()
            print(
                f"[serve] cache: {int(cs['entries'])} entries "
                f"{cs['bytes'] / 2**20:.2f} MiB | hit rate "
                f"{cs['hit_rate']:.2f} ({int(cs['hits'])} hits, "
                f"{int(cs['misses'])} misses, "
                f"{int(cs['evicted_bytes'])} bytes evicted)"
            )
        if sink is not None:
            sink.close()
            print(f"[serve] events -> {args.events_out}")
        if args.metrics_out:
            write_metrics(engine.obs.snapshot(), args.metrics_out)
            print(f"[serve] metrics -> {args.metrics_out}")
    failed = [r for r in results if r.status == "error"]
    if failed and not args.inject:
        raise SystemExit(
            f"[serve] {len(failed)} of {len(results)} requests failed with "
            f"no injected fault; first: request {failed[0].rid}: "
            f"{failed[0].error}"
        )
    return len(results)


if __name__ == "__main__":
    main()

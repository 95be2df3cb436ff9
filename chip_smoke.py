#!/usr/bin/env python3
"""Smoke test of the main path on a TPU: serve hla-1b, train it, check both.

    python3 chip_smoke.py               # one chip: the serve and train phases
    python3 chip_smoke.py --chips 4     # four chips: the sharded phase only
    python3 chip_smoke.py --rehearse    # reduced config on any backend

One chip:

* **serve** — the full 24-layer hla-1b at its published widths, from seeded
  random weights, through ``serving.Engine`` (the engine ``launch/serve.py``
  drives): ragged prompts of 512 to 1,000 tokens, 32 greedy tokens each.
  Every request must end ``ok``, and prefill-then-decode logits must agree
  with the float32 ``jax.numpy`` forward pass run at "highest" precision.
* **train** — hla-1b at published widths with its depth cut to fit weights,
  gradients and AdamW state on one chip, trained a few steps through
  ``distributed.steps.make_train_step`` and ``runtime.ft.FaultTolerantLoop``
  (the loop ``launch/train.py`` runs).  The first step's loss and gradient
  norm must agree between the fused Pallas kernels and the jnp path.

Both phases assert that the compiled programs hold the Mosaic kernels
(``tpu_custom_call``).  ``--chips 4`` runs only the sharded phase on a 1x4
mesh (heads over ``model``) and compares it with one device.

The printed times are smoke readings of one run (compilation included
where it says so), not benchmark results.  Without a TPU the script exits
nonzero unless ``--rehearse`` is given.  The last line of standard output
is one JSON object: ``{"ok": true, "device": {...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import json
import os
import sys
import tempfile
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Served logits are computed in bfloat16 activations (the published
# config) and compared with a float32 reference.  bf16 keeps 8 mantissa
# bits, so each of the 24 residual layers adds a relative rounding error
# near 2^-9 to the hidden state; summed over the stack that is a few per
# cent of the logits' norm.  A wrong carry, decay or tile layout in a kernel
# moves logits by a sizeable fraction of their norm.
SERVE_LOGIT_RTOL = 0.08
# The fused and the jnp training paths see the same bf16 activations; they
# differ in where the chunk math rounds (the kernel computes each chunk in
# float32, the jnp path in its activation dtype).
TRAIN_LOSS_RTOL = 1e-2
TRAIN_GRAD_NORM_RTOL = 5e-2
# Sharded vs one-device runs compute in float32 at "highest" precision:
# only the order of cross-device reductions differs.
SHARDED_LOGIT_RTOL = 1e-4
SHARDED_LOSS_RTOL = 1e-5


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


# --------------------------------------------------------------------------
# shared pieces
# --------------------------------------------------------------------------


def init_sharded_params(cfg, mesh, seed: int):
    import jax

    from repro.distributed import sharding as shd
    from repro.models import lm
    from repro.models.param import init_params

    specs = lm.lm_specs(cfg)
    with mesh:
        return jax.jit(
            functools.partial(init_params, specs),
            out_shardings=shd.param_shardings(specs, mesh),
        )(jax.random.key(seed))


def make_requests(vocab: int, lengths, max_new: int, seed: int):
    import numpy as np

    from repro.serving import GenRequest

    rng = np.random.RandomState(seed)
    return [
        GenRequest(rid=i, prompt=rng.randint(2, vocab, size=int(n)),
                   max_new=max_new)
        for i, n in enumerate(lengths)
    ]


def served_logits(params, prompt, gen, cfg):
    """Logits the serving path produces for ``gen`` after ``prompt``: one
    chunk-parallel prefill, then one decode step per generated token
    (teacher-forced with ``gen``).  Row j predicts ``gen[j]``."""
    import jax
    import jax.numpy as jnp

    from repro.models import lm

    last, states = lm.lm_prefill(params, prompt, cfg)

    def step(carry, tok):
        states, pos = carry
        logits, states, _ = lm.lm_apply(
            params, tok.reshape(1, 1), cfg, states=states, positions=pos,
            mode="decode",
        )
        return (states, pos + 1), logits[0, -1]

    pos0 = jnp.full((1, 1), prompt.shape[1], jnp.int32)
    _, dec = jax.lax.scan(step, (states, pos0), gen[:-1])
    return jnp.concatenate([last, dec], 0).astype(jnp.float32)


def reference_logits(params, prompt, gen, cfg):
    """The same positions from the float32 jnp forward over prompt+gen."""
    import jax.numpy as jnp

    from repro.models import lm

    L = prompt.shape[1]
    toks = jnp.concatenate([prompt, gen[None, :-1]], 1)
    logits, _, _ = lm.lm_apply(params, toks, cfg)
    return logits[0, L - 1:].astype(jnp.float32)


def reference_config(cfg):
    return cfg.replace(
        dtype="float32",
        hla=dataclasses.replace(cfg.hla, use_pallas=False, force_pallas=False),
    )


def assert_native_kernels(name: str, text: str, on_tpu: bool) -> None:
    if not on_tpu:
        log(f"{name}: kernel check needs a TPU; not checked")
        return
    n = text.count("tpu_custom_call")
    assert n > 0, f"{name}: no tpu_custom_call in the compiled program"
    log(f"{name}: compiled program holds {n} tpu_custom_call ops")


def engine_programs(eng, prompt_len: int, scfg):
    """Compiled text of the engine's own prefill and decode-block programs
    (the persistent compile cache serves both from the run just made)."""
    import jax
    import jax.numpy as jnp

    with eng._mesh_ctx():
        prefill = eng._prefill.lower(
            eng.params, jnp.zeros((1, prompt_len), jnp.int32),
            jax.random.key(0), scfg=scfg,
        ).compile().as_text()
        decode = eng._decode_block.lower(
            eng.params, eng.pool.states, eng.tokens, eng.positions,
            jnp.asarray(eng.active), jax.random.key(0),
            jnp.zeros((eng.pool.slots,), jnp.int32),
            n_steps=eng.block, scfgs=(scfg,),
        ).compile().as_text()
    return prefill, decode


def train_setup(cfg, mesh, batch: int, seq: int, seed: int):
    """Params, optimizer state and the batch placer of ``launch/train.py``."""
    import jax

    from repro.distributed import sharding as shd
    from repro.distributed import steps as steps_mod
    from repro.models.param import init_params
    from repro.optim import adamw

    specs = steps_mod.model_specs(cfg)
    pshard, opt_shard = steps_mod.make_shardings(cfg, mesh)
    with mesh:
        params = jax.jit(
            functools.partial(init_params, specs), out_shardings=pshard
        )(jax.random.key(seed))
        opt = jax.jit(adamw.init_opt_state, out_shardings=opt_shard)(params)

    def place(b):
        return {
            k: jax.device_put(v, shd.batch_sharding(mesh, v.shape))
            for k, v in b.items()
        }

    return params, opt, pshard, place


def compile_train_step(cfg, opt_cfg, pshard, mesh, params, opt, batch):
    """AOT-compiled, donated train step; returns (compiled, seconds)."""
    import jax

    from repro.distributed import steps as steps_mod

    t0 = time.perf_counter()
    with mesh:
        step = jax.jit(
            steps_mod.make_train_step(cfg, opt_cfg, grad_shardings=pshard),
            donate_argnums=(0, 1),
        )
        compiled = step.lower(params, opt, batch).compile()
    return compiled, time.perf_counter() - t0


# --------------------------------------------------------------------------
# one chip
# --------------------------------------------------------------------------


def serve_phase(cfg, *, lengths, gen_len, block, seed, check_reqs, on_tpu,
                kind):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_mesh, mesh_summary
    from repro.serving import Engine, SamplingConfig

    mesh = make_mesh()
    log(f"serve: {cfg.name} {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads, vocab {cfg.vocab}, {cfg.dtype} activations "
        f"on {mesh_summary(mesh)}")
    params = init_sharded_params(cfg, mesh, seed)
    scfg = SamplingConfig(method="greedy")
    eng = Engine(
        cfg, params, slots=len(lengths), max_len=max(lengths) + gen_len + 8,
        sampling=scfg, block=block, seed=seed, mesh=mesh,
    )

    def run():
        reqs = make_requests(cfg.vocab, lengths, gen_len, seed)
        t0 = time.perf_counter()
        res = eng.run(reqs)
        return reqs, res, time.perf_counter() - t0

    reqs, warm, warm_s = run()  # compiles every prompt length
    bad = [(r.rid, r.status, r.error) for r in warm if r.status != "ok"]
    assert not bad, f"serve: requests not ok: {bad}"
    _, steady, steady_s = run()
    bad = [(r.rid, r.status, r.error) for r in steady if r.status != "ok"]
    assert not bad, f"serve: requests not ok: {bad}"
    for a, b in zip(warm, steady):
        assert a.tokens == b.tokens, f"serve: greedy rerun differs ({a.rid})"
    gen_toks = sum(len(r.tokens) for r in steady)
    assert gen_toks == gen_len * len(lengths), gen_toks
    prompt_toks = int(sum(lengths))
    log(f"serve [{kind}]: {len(steady)} requests all ok; "
        f"{prompt_toks} prompt + {gen_toks} generated tokens per run")
    log(f"serve [{kind}]: smoke reading, not a benchmark: first run "
        f"{warm_s:.3f} s (compiles included), steady rerun {steady_s:.3f} s, "
        f"compile ~{warm_s - steady_s:.3f} s")

    pre_text, dec_text = engine_programs(eng, len(reqs[0].prompt), scfg)
    assert_native_kernels("serve prefill", pre_text, on_tpu)
    assert_native_kernels("serve decode block", dec_text, on_tpu)

    ref_cfg = reference_config(cfg)
    served = jax.jit(functools.partial(served_logits, cfg=cfg))
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(functools.partial(reference_logits, cfg=ref_cfg))
        for i in check_reqs:
            prompt = jnp.asarray(reqs[i].prompt[None], jnp.int32)
            gen = jnp.asarray(np.asarray(steady[i].tokens), jnp.int32)
            with mesh:
                got = served(params, prompt, gen)
            want = ref(params, prompt, gen)
            got, want = np.asarray(got), np.asarray(want)
            assert np.isfinite(got).all(), "serve: non-finite served logits"
            errs = [rel_err(g, w) for g, w in zip(got, want)]
            agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
            log(f"serve logits vs float32 reference, request {i} "
                f"(prompt {prompt.shape[1]}): max relative L2 "
                f"{max(errs):.3e} over {len(errs)} positions (limit "
                f"{SERVE_LOGIT_RTOL}); top-1 agreement {agree:.3f}")
            assert max(errs) <= SERVE_LOGIT_RTOL, errs
    del eng, params
    gc.collect()


def train_phase(cfg, *, steps, batch, seq, seed, on_tpu, kind):
    import jax
    import numpy as np

    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.launch.mesh import make_mesh, mesh_summary
    from repro.obs import Obs
    from repro.optim import adamw
    from repro.runtime.ft import FaultTolerantLoop

    mesh = make_mesh()
    n_params = sum(
        int(np.prod(x.shape))
        for x in jax.tree.leaves(
            jax.eval_shape(lambda: train_setup_shapes(cfg))
        )
    )
    log(f"train: {cfg.name} with {cfg.n_layers} layers "
        f"(d_model {cfg.d_model}, {cfg.n_heads} heads, d_ff "
        f"{cfg.d_ff}, vocab {cfg.vocab}): {n_params} params, "
        f"{16 * n_params / 2**30:.2f} GiB at 16 B/param; batch {batch} x "
        f"{seq} tokens on {mesh_summary(mesh)}")
    opt_cfg = adamw.OptConfig(total_steps=steps, warmup_steps=1)
    stream = SyntheticStream(DataConfig(cfg.vocab, seq, batch, seed=seed))

    params, opt, pshard, place = train_setup(cfg, mesh, batch, seq, seed)
    batch0 = place(stream.batch(0))
    step, compile_s = compile_train_step(
        cfg, opt_cfg, pshard, mesh, params, opt, batch0
    )
    assert_native_kernels("train step", step.as_text(), on_tpu)
    with tempfile.TemporaryDirectory() as tmp:  # fresh: nothing to resume
        metrics_path = os.path.join(tmp, "metrics.jsonl")
        obs = Obs()
        loop = FaultTolerantLoop(
            step, stream, os.path.join(tmp, "ckpt"), ckpt_every=steps + 1,
            metrics_path=metrics_path, place_batch=place, obs=obs,
            log=lambda m: None,
        )
        with mesh:
            params, opt, last = loop.run(params, opt, steps)
        with open(metrics_path) as f:
            rows = [json.loads(line) for line in f]
    assert last == steps - 1 and len(rows) == steps, (last, len(rows))
    losses = [r["loss"] for r in rows]
    assert np.isfinite(losses).all(), losses
    step_s = [r["step_time_s"] for r in rows]
    log(f"train [{kind}]: {steps} steps, losses {losses}")
    log(f"train [{kind}]: smoke reading, not a benchmark: compile "
        f"{compile_s:.3f} s, steady step {float(np.median(step_s[1:])):.3f} s "
        f"(median of steps 1..{steps - 1}), {batch * seq} tokens per step")
    del params, opt, step, loop
    gc.collect()

    # the first step again, on the jnp path from the same seed and batch
    jcfg = cfg.replace(hla=dataclasses.replace(
        cfg.hla, use_pallas=False, force_pallas=False))
    params, opt, pshard, place = train_setup(jcfg, mesh, batch, seq, seed)
    jstep, _ = compile_train_step(
        jcfg, opt_cfg, pshard, mesh, params, opt, batch0
    )
    with mesh:
        _, _, m = jstep(params, opt, batch0)
    m = {k: float(v) for k, v in jax.device_get(m).items()}
    for key, tol in (("loss", TRAIN_LOSS_RTOL),
                     ("grad_norm", TRAIN_GRAD_NORM_RTOL)):
        fused, plain = rows[0][key], m[key]
        err = abs(fused - plain) / abs(plain)
        log(f"train first-step {key}: fused {fused:.6f} vs jnp {plain:.6f}, "
            f"relative {err:.3e} (limit {tol})")
        assert err <= tol, (key, fused, plain)
    del params, opt, jstep
    gc.collect()


def train_setup_shapes(cfg):
    """Parameter shapes only (for counting); nothing is allocated."""
    from repro.distributed import steps as steps_mod
    from repro.models.param import abstract_params

    return abstract_params(steps_mod.model_specs(cfg))


# --------------------------------------------------------------------------
# four chips
# --------------------------------------------------------------------------


def sharded_phase(cfg, **kw):
    import jax

    # Both sides run at "highest": on the chip the default float32 matmul
    # rounds its inputs to bfloat16, and the two programs fuse and round
    # in different places.
    with jax.default_matmul_precision("highest"):
        _sharded_phase(cfg, **kw)


def _sharded_phase(cfg, *, lengths, gen_len, block, batch, seq, seed, on_tpu):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import SingleDeviceSharding

    from repro.data.pipeline import DataConfig, SyntheticStream
    from repro.launch.mesh import make_mesh, mesh_summary
    from repro.optim import adamw
    from repro.serving import Engine, SamplingConfig

    mesh = make_mesh((1, len(jax.devices())), ("data", "model"))
    one = make_mesh((1, 1), ("data", "model"))
    dev0 = SingleDeviceSharding(jax.devices()[0])
    log(f"sharded: {cfg.name} {cfg.n_layers} layers, d_model "
        f"{cfg.d_model}, {cfg.n_heads} heads, {cfg.dtype}, "
        f"{mesh_summary(mesh)} vs one device")
    scfg = SamplingConfig(method="greedy")
    results, logits = {}, {}
    params4 = init_sharded_params(cfg, mesh, seed)
    params1 = jax.device_put(params4, dev0)
    served = jax.jit(functools.partial(served_logits, cfg=cfg))
    for name, params, m in (("sharded", params4, mesh),
                            ("one device", params1, None)):
        eng = Engine(
            cfg, params, slots=len(lengths),
            max_len=max(lengths) + gen_len + 8, sampling=scfg, block=block,
            seed=seed, mesh=m,
        )
        reqs = make_requests(cfg.vocab, lengths, gen_len, seed)
        t0 = time.perf_counter()
        res = eng.run(reqs)
        dt = time.perf_counter() - t0
        bad = [(r.rid, r.status, r.error) for r in res if r.status != "ok"]
        assert not bad, f"{name}: requests not ok: {bad}"
        results[name] = res
        log(f"serve [{name}]: {len(res)} requests all ok in {dt:.3f} s "
            "(compiles included; smoke reading)")
        if m is not None:
            pre_text, dec_text = engine_programs(eng, len(reqs[0].prompt),
                                                 scfg)
            assert_native_kernels("sharded prefill", pre_text, on_tpu)
            assert_native_kernels("sharded decode block", dec_text, on_tpu)
        prompt = jnp.asarray(reqs[0].prompt[None], jnp.int32)
        gen = jnp.asarray(np.asarray(results["sharded"][0].tokens), jnp.int32)
        with m if m is not None else one:
            logits[name] = np.asarray(served(params, prompt, gen))
        del eng
    for a, b in zip(results["sharded"], results["one device"]):
        assert a.tokens == b.tokens, ("sharded tokens differ", a.rid)
    err = max(rel_err(g, w) for g, w in
              zip(logits["sharded"], logits["one device"]))
    log(f"sharded vs one device: tokens equal in all {len(lengths)} "
        f"requests; logits max relative L2 {err:.3e} (limit "
        f"{SHARDED_LOGIT_RTOL})")
    assert err <= SHARDED_LOGIT_RTOL, err
    del params4, params1
    gc.collect()

    opt_cfg = adamw.OptConfig(total_steps=1, warmup_steps=1)
    stream = SyntheticStream(DataConfig(cfg.vocab, seq, batch, seed=seed))
    losses = {}
    for name, m in (("sharded", mesh), ("one device", one)):
        params, opt, pshard, place = train_setup(cfg, m, batch, seq, seed)
        b0 = place(stream.batch(0))
        step, compile_s = compile_train_step(
            cfg, opt_cfg, pshard, m, params, opt, b0
        )
        if m is mesh:
            assert_native_kernels("sharded train step", step.as_text(),
                                  on_tpu)
        with m:
            _, _, met = step(params, opt, b0)
        losses[name] = float(met["loss"])
        log(f"train [{name}]: one step, loss {losses[name]:.6f}, compile "
            f"{compile_s:.3f} s (smoke reading)")
        del params, opt, step
        gc.collect()
    err = abs(losses["sharded"] - losses["one device"]) / abs(
        losses["one device"])
    log(f"sharded vs one device train loss: relative {err:.3e} (limit "
        f"{SHARDED_LOSS_RTOL})")
    assert err <= SHARDED_LOSS_RTOL, losses


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the sharded phase, on a 1x4 mesh")
    ap.add_argument("--rehearse", action="store_true",
                    help="reduced config, Pallas kernels in interpret mode "
                         "off the chip; runs on whatever backend JAX finds")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if args.rehearse and args.chips == 4:
        # four host devices stand in for the chips (set before jax starts)
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count=4 "
            + os.environ.get("XLA_FLAGS", "")
        )
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax

    from repro.configs import get_config
    from repro.kernels import ops as kops
    from repro.runtime.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    on_tpu = platform == "tpu"
    if not on_tpu and not args.rehearse:
        print(f"chip_smoke: no TPU (JAX found {platform}); pass --rehearse "
              "to run the reduced config here", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    cache = enable_compile_cache()
    kind = devices[0].device_kind
    log(f"devices: {len(devices)} x {kind} ({platform}); jax "
        f"{jax.__version__}; compile cache {cache}")

    base = get_config("hla-1b", reduced=args.rehearse)
    if args.rehearse:  # off the chip the kernels only run when asked
        base = base.replace(hla=dataclasses.replace(
            base.hla, force_pallas=not on_tpu))
    phases = []
    if args.chips == 4:
        cfg4 = base.replace(dtype="float32")
        if not args.rehearse:
            cfg4 = cfg4.replace(n_layers=4)
        phases.append(("sharded", lambda: sharded_phase(
            cfg4, lengths=(300, 357, 411, 480) if not args.rehearse
            else (9, 17, 23, 30), gen_len=16, block=8,
            batch=4, seq=512 if not args.rehearse else 32,
            seed=args.seed, on_tpu=on_tpu)))
    else:
        lengths = (
            (517, 588, 640, 701, 777, 843, 912, 1000) if not args.rehearse
            else (5, 9, 12, 17, 20, 26, 31, 40)
        )
        phases.append(("serve", lambda: serve_phase(
            base, lengths=lengths, gen_len=32, block=8, seed=args.seed,
            check_reqs=(0, len(lengths) - 1), on_tpu=on_tpu, kind=kind)))
        train_cfg = base if args.rehearse else base.replace(n_layers=8)
        phases.append(("train", lambda: train_phase(
            train_cfg, steps=6, batch=4 if not args.rehearse else 2,
            seq=1024 if not args.rehearse else 64, seed=args.seed,
            on_tpu=on_tpu, kind=kind)))

    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED after {time.perf_counter() - t0:.1f} s")
        else:
            log(f"phase {name}: passed in {time.perf_counter() - t0:.1f} s")
    log(f"kernel traces: {dict(kops.TRACE_COUNTS)}")
    if on_tpu and not failed:
        want = (("hla2_fwd_fused", "hla2_bwd_fused")
                if args.chips == 4 else
                ("hla2_prefill", "hla2_decode_step", "hla2_fwd_fused",
                 "hla2_bwd_fused"))
        missing = [k for k in want if not kops.TRACE_COUNTS[k]]
        if missing:
            log(f"kernel paths never traced: {missing}")
            failed.append("kernels")
    if failed:
        print(f"chip_smoke: failed phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Training cells: the program's compiled train step, driven from the seed.

Set-up builds one object, the compiled step with its parameters and AdamW
state, and drives it through its first three steps on distinct rows; those
steps are the warm-up and give the numbers the reference is compared with:
each step's loss, the first clipped gradient's per-leaf norms (read back
from AdamW's first moment, ``mu / (1 - beta1)``), and the per-leaf norms of
the parameters' change over the three steps.  The same object then runs
the measured window.  After the window the program's state is freed and
the float32 reference repeats the three steps from the seed.
"""

from __future__ import annotations

import time

from bench import common, trace_reduce
from bench import traffic as traffic_mod


def _norms(tree_to_named):
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda t: {
        k: jnp.sqrt(jnp.sum(jnp.square(v.astype(jnp.float32))))
        for k, v in tree_to_named(t).items()})


def _host(d):
    import jax

    return {k: float(v) for k, v in jax.device_get(d).items()}


def _change_norms(ref, c):
    """Per-leaf norms of named weights less the seed's starting weights."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda W, k: {
        n: jnp.sqrt(jnp.sum((W[n].astype(jnp.float32) - W0) ** 2))
        for n, W0 in ref.make_weights(c, k).items()})


def reference_readings(c, t, seed, lowp=None):
    """The reference's three steps from the seed: (losses, first clipped
    gradient's per-leaf norms, per-leaf norms of the change)."""
    import jax
    import jax.numpy as jnp

    ref, _ = common.arch(c)
    o = dict(t["optimizer"])
    key = ref.seed_key(seed)
    rows = traffic_mod.train_batches(t, seed, c["vocab"],
                                     t["distinct_batches"])

    @jax.jit
    def start(k):
        W = ref.make_weights(c, k)
        z = jax.tree.map(jnp.zeros_like, W)
        return W, z, jax.tree.map(jnp.zeros_like, W)

    def one(W, m, v, step, tok, lab):
        with jax.default_matmul_precision("highest"):
            loss, grads = jax.value_and_grad(
                lambda w: ref.loss(c, w, tok, lab, lowp=lowp))(W)
            W, m, v, g = ref.adamw_step(W, m, v, grads, step, o)
        norms = {k: jnp.sqrt(jnp.sum(x * x)) for k, x in g.items()}
        return W, m, v, loss, norms

    step_fn = jax.jit(one, donate_argnums=(0, 1, 2))
    W, m, v = start(key)
    losses, gnorm = [], None
    for s in range(3):
        W, m, v, loss, norms = step_fn(W, m, v, jnp.float32(s + 1),
                                       rows[s, :, :-1], rows[s, :, 1:])
        losses.append(float(loss))
        if s == 0:
            gnorm = _host(norms)
    del m, v
    return losses, gnorm, _host(_change_norms(ref, c)(W, key))


def compare(prog, refr, limits):
    """The numbers compared, each with its limit."""
    (lp, gp, dp), (lr, gr, dr) = prog, refr
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(lp, lr))
    grad_gap, _ = common.gap_worst(gp, gr)
    med = sorted(gr.values())[len(gr) // 2]
    still = [k for k, g in gr.items() if g < 1e-3 * med]
    upd_gap, _ = common.gap_worst(dp, dr, skip=still)
    out = {}
    for name, val in (("loss_gap", loss_gap), ("grad_norm_gap", grad_gap),
                      ("update_norm_gap", upd_gap)):
        if name in limits:
            out[name] = {"value": val, "limit": limits[name]}
    return out


def run(ctx: common.Context) -> dict:
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.distributed import steps as steps_mod
    from repro.launch.mesh import make_mesh
    from repro.optim import adamw

    c, t = ctx.cfg, ctx.traffic
    ref, prog = common.arch(c)
    devs = common.devices(ctx.cell["chips"], ctx.require_chip)
    pcfg = prog.program_config(c)
    mesh = make_mesh((1, len(devs)), ("data", "model"))
    o = dict(t["optimizer"])
    opt_cfg = adamw.OptConfig(**{**o, "betas": tuple(o["betas"])})
    B, n = t["batch"], t["seq_len"]
    K = t["distinct_batches"]
    key = ref.seed_key(ctx.seed)
    rows = traffic_mod.train_batches(t, ctx.seed, c["vocab"], K)
    pshard, opt_shard = steps_mod.make_shardings(pcfg, mesh)
    data = NamedSharding(mesh, P())
    with mesh:
        params = jax.jit(lambda k: prog.to_program(ref.make_weights(c, k)),
                         out_shardings=pshard)(key)
        prog.check_tree(pcfg, params)
        opt_state = jax.jit(adamw.init_opt_state,
                            out_shardings=opt_shard)(params)
        step = jax.jit(steps_mod.make_train_step(
            pcfg, opt_cfg, grad_shardings=pshard), donate_argnums=(0, 1))
        feed = [{"tokens": jax.device_put(rows[i, :, :-1], data),
                 "labels": jax.device_put(rows[i, :, 1:], data)}
                for i in range(K)]
        named = _norms(prog.from_program)

        # warm-up: the first three steps, read for the comparison
        losses, gnorm = [], None
        for s in range(3):
            params, opt_state, m = step(params, opt_state, feed[s])
            losses.append(float(m["loss"]))
            if s == 0:
                b1 = opt_cfg.betas[0]
                gnorm = {k: v / (1 - b1)
                         for k, v in _host(named(opt_state.mu)).items()}
        dnorm = _host(_change_norms(ref, c)(prog.from_program(params), key))
        jax.block_until_ready((params, opt_state))

        counter = common.CompileCounter()
        counter.active = True
        t0 = time.perf_counter()
        setup_s = t0 - ctx.t_start
        end = t0 + ctx.seconds
        trace_s = min(ctx.seconds, t.get("trace_seconds", ctx.seconds))
        steps, nonfinite, i = 0, 0, 3
        tr: dict = {}
        last = None

        def run_until(deadline):
            nonlocal params, opt_state, steps, nonfinite, i, last
            while True:
                with jax.profiler.TraceAnnotation("bench.train_step"):
                    params, opt_state, m = step(params, opt_state,
                                                feed[i % K])
                i += 1
                steps += 1
                if last is not None:
                    nonfinite += not np.isfinite(float(last["loss"]))
                last = m
                if time.perf_counter() >= deadline:
                    break
            nonfinite += not np.isfinite(float(last["loss"]))
            last = None
            jax.block_until_ready((params, opt_state))

        if ctx.trace:
            run_until(end - trace_s)
        steps_before = steps
        with common.traced(ctx.trace, tr):
            run_until(end)
        t1 = time.perf_counter()
        counter.active = False
        steps_traced = steps - steps_before
        device = common.device_info(devs)
        del params, opt_state, feed, step
    common.free_device_memory()

    refr = reference_readings(c, t, ctx.seed)
    prog_readings = (losses, gnorm, dnorm)
    checks = compare(prog_readings, refr, ctx.limits)
    window_s = t1 - t0
    metrics = {
        "train_tok_s": {"value": steps * B * n / window_s,
                        "unit": "tokens/s"},
        "setup_s": {"value": setup_s, "unit": "s"},
    }
    obs = {"kind": "train", "cfg": c, "traffic": t, "chips": len(devs),
           "device_kind": device["kind"], "tokens_per_step": B * n,
           "steps_traced": steps_traced, "trace": None}
    breakdown = None
    extra = {"compiles_in_window": counter.n}
    if ctx.trace:
        summ = trace_reduce.reduce_trace(
            tr["dir"], attribute=["bench.", "train.", "engine."])
        common.remove_tree(tr["dir"])
        obs["trace"] = summ
        device["busy_s"] = summ.busy_ns / 1e9
        device["window_s"] = summ.busy_window_ns / 1e9
        breakdown = {"device_ops": summ.top_ops, "idle_gaps": summ.idle_gaps}
        extra["longest_idle_gaps"] = summ.longest_gaps
    return {
        "metrics": metrics, "obs": obs, "checks": checks,
        "attempted": steps, "failed": nonfinite, "device": device,
        "breakdown": breakdown,
        "extra": extra,
        "readings": {"program": prog_readings, "reference": refr},
    }

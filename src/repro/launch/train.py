"""End-to-end training launcher with fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch hla-1b --reduced \
        --steps 200 --batch 8 --seq 256 --ckpt-dir /tmp/ckpt

Uses the real mesh (all visible devices) or ``--host-devices N`` for a CPU
simulation mesh; checkpoints/restarts via runtime.ft (auto-resume), data
from the deterministic synthetic stream.
"""

import os

# must run at import, before jax initializes its backend: XLA locks the
# host device count on first use
_hd = os.environ.get("HOST_DEVICES")
if _hd:
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={_hd} "
        + os.environ.get("XLA_FLAGS", "")
    )

import argparse  # noqa: E402
import functools  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from ..configs import get_config  # noqa: E402
from ..data.pipeline import DataConfig, SyntheticStream  # noqa: E402
from ..distributed import sharding as shd  # noqa: E402
from ..distributed import steps as steps_mod  # noqa: E402
from ..models.param import init_params  # noqa: E402
from ..obs import JsonlSink, Obs, profile_capture, write_metrics  # noqa: E402
from ..optim import adamw  # noqa: E402
from ..runtime.compile_cache import enable_compile_cache  # noqa: E402
from ..runtime.faults import FaultPlan, FaultSpec  # noqa: E402
from ..runtime.ft import FaultTolerantLoop  # noqa: E402
from .mesh import make_mesh, mesh_summary  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="hla-1b")
    ap.add_argument("--mixer", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default="zipf")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--fail-at-step", type=int, default=None,
                    help="inject a train.step fault at this step "
                         "(runtime.faults; exercises restart/resume)")
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="dump the final metrics registry snapshot "
                         "(repro.obs.metrics/v1 JSON) on exit")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="stream span/event records (repro.obs.events/v1 "
                         "JSONL): train.step spans, ckpt.save spans, "
                         "resume events, fired faults")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the whole run "
                         "into DIR; profile.start/stop anchors tie the "
                         "trace's clock to the obs stream's")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_config(args.arch, reduced=args.reduced, mixer=args.mixer)
    mesh = make_mesh()
    print(f"[train] {cfg.name} on {mesh_summary(mesh)}")

    specs = steps_mod.model_specs(cfg)
    # one sharding source of truth: params + ZeRO-1 optimizer moments from
    # distributed.steps.make_shardings (what dryrun lowers against too)
    pshard, opt_shard = steps_mod.make_shardings(cfg, mesh)
    opt_cfg = adamw.OptConfig(
        lr=args.lr, total_steps=args.steps, warmup_steps=max(args.steps // 20, 5)
    )
    with mesh:
        params = jax.jit(
            functools.partial(init_params, specs), out_shardings=pshard
        )(jax.random.key(args.seed))
        opt_state = jax.jit(
            adamw.init_opt_state, out_shardings=opt_shard
        )(params)
        # params and optimizer state are donated: the step updates them in
        # place, so the device holds one copy of each, not two
        step_fn = jax.jit(
            steps_mod.make_train_step(
                cfg, opt_cfg, microbatches=args.microbatches,
                grad_shardings=pshard,
            ),
            donate_argnums=(0, 1),
        )

        stream = SyntheticStream(
            DataConfig(cfg.vocab, args.seq, args.batch, seed=args.seed,
                       kind=args.data)
        )

        def place(batch):
            return {
                k: jax.device_put(
                    v, shd.batch_sharding(mesh, v.shape)
                )
                for k, v in batch.items()
            }

        faults = None
        if args.fail_at_step is not None:
            faults = FaultPlan(FaultSpec("train.step", at=args.fail_at_step))
        obs = Obs()
        sink = None
        if args.events_out:
            sink = JsonlSink(args.events_out)
            obs.attach(sink)
        loop = FaultTolerantLoop(
            step_fn, stream, args.ckpt_dir, ckpt_every=args.ckpt_every,
            metrics_path=args.metrics, faults=faults,
            place_batch=place, obs=obs,
        )
        with profile_capture(args.profile_dir, obs=obs):
            params, opt_state, last = loop.run(
                params, opt_state, args.steps
            )
    step_s = obs.registry.get("train_step_seconds")
    p50 = step_s.quantile(0.5) or 0.0
    p99 = step_s.quantile(0.99) or 0.0
    toks = obs.registry.get("train_tokens_total").total()
    total_s = step_s.sum() or 1e-9
    print(
        f"[train] finished at step {last} | step p50 {p50:.3f}s "
        f"p99 {p99:.3f}s | {toks / total_s:.0f} tok/s | "
        f"loss {obs.registry.get('train_loss').value():.4f}"
    )
    if sink is not None:
        sink.close()
        print(f"[train] events -> {args.events_out}")
    if args.metrics_out:
        write_metrics(obs.snapshot(), args.metrics_out)
        print(f"[train] metrics -> {args.metrics_out}")
    return last


if __name__ == "__main__":
    main()

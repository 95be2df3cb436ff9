#!/usr/bin/env python3
"""Compile each cell's programs at the cell's shapes for a described TPU
v5e, without a chip, and print the bytes each device would hold.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--cells train-8l-4k,...]

Programs compiled, with arguments as shapes only (nothing is allocated):

* a training cell: the train step of ``distributed/steps.py`` with its
  parameters and AdamW state donated, on the cell's chips (a 1 x chips
  mesh, heads over ``model``);
* a serving cell: the whole-prompt prefill at the longest prompt the mix
  sends cold, a prefill resumed from a state at the longest resumed
  segment, and a block of decode steps over all slots.

``--four-chip`` adds the published 24-layer model trained on 4 x 4,096
tokens over a 1 x 4 mesh (a cell for later).  Each line is one JSON
object with the program, its shapes and ``memory_analysis()``'s bytes.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def _mem(compiled) -> dict:
    m = compiled.memory_analysis()
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes",
            "generated_code_size_in_bytes")
    out = {k: int(getattr(m, k, 0)) for k in keys}
    out["total_bytes"] = (out["argument_size_in_bytes"]
                          + out["output_size_in_bytes"]
                          - out["alias_size_in_bytes"]
                          + out["temp_size_in_bytes"])
    return out


def train(c, t, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from bench.models import hla2_lm
    from repro.distributed import steps
    from repro.optim import adamw

    import numpy as np

    pcfg = hla2_lm.program_config(c)
    mesh = Mesh(np.array(devices).reshape(1, len(devices)), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    o = dict(t["optimizer"])
    opt = adamw.OptConfig(**{**o, "betas": tuple(o["betas"])})
    with mesh:
        params, opt_state = steps.abstract_train_args(pcfg, mesh)
        ps, _ = steps.make_shardings(pcfg, mesh)
        tok = jax.ShapeDtypeStruct((t["batch"], t["seq_len"]), jnp.int32,
                                   sharding=NamedSharding(mesh, P()))
        fn = jax.jit(steps.make_train_step(pcfg, opt, grad_shardings=ps),
                     donate_argnums=(0, 1))
        compiled = fn.lower(params, opt_state,
                            {"tokens": tok, "labels": tok}).compile()
    return [("train_step", {"batch": t["batch"], "seq_len": t["seq_len"],
                            "chips": len(devices)}, compiled)]


def serve(c, t, devices):
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    from bench.models import hla2_lm
    from repro.distributed import steps
    from repro.models import lm
    from repro.models.param import abstract_params

    pcfg = hla2_lm.program_config(c)
    one = SingleDeviceSharding(devices[0])
    sds = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    params = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                          abstract_params(steps.model_specs(pcfg)))
    slots = t["slots"]
    states = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lm.lm_init_states(pcfg, slots, t["max_len"])))
    one_state = jax.tree.map(
        lambda a: sds(a.shape, a.dtype),
        jax.eval_shape(lambda: lm.lm_init_states(pcfg, 1, t["max_len"])))
    out = []
    if t["kind"] == "sessions":
        cold = t["doc_len"]
        resumed = t["user_len"][1] + 2 * t["cache_granularity"]
    else:
        cold = t["prompt_len"][1]
        resumed = 0
    fn = jax.jit(lambda p, x: lm.lm_prefill(p, x, pcfg))
    out.append(("prefill", {"tokens": cold},
                fn.lower(params, sds((1, cold), jnp.int32)).compile()))
    if resumed:
        fn = jax.jit(lambda p, x, pos, s: lm.lm_prefill(
            p, x, pcfg, states=s, positions=pos))
        out.append(("prefill_from_state", {"tokens": resumed}, fn.lower(
            params, sds((1, resumed), jnp.int32),
            sds((1, resumed), jnp.int32), one_state).compile()))

    def block(p, s, tok, pos):
        def body(carry, _):
            s, tok, pos = carry
            logits, s, _ = lm.lm_apply(p, tok, pcfg, states=s, positions=pos,
                                       mode="decode")
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            return (s, tok, pos + 1), tok[:, 0]

        (s, tok, pos), toks = jax.lax.scan(body, (s, tok, pos),
                                           length=t["block"])
        return s, toks

    fn = jax.jit(block, donate_argnums=(1,))
    out.append(("decode_block", {"slots": slots, "steps": t["block"]},
                fn.lower(params, states, sds((slots, 1), jnp.int32),
                         sds((slots, 1), jnp.int32)).compile()))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cells", default="")
    ap.add_argument("--four-chip", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    from bench import run
    from repro.models import mixer

    # the mixer picks its kernels by asking for the default backend, which
    # is the CPU here: take the chip's path for the described chip
    mixer._pallas_enabled = lambda hc: hc.use_pallas

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    spec = run.load_spec()
    names = [n for n in args.cells.split(",") if n] or [
        w["name"] for w in spec["workloads"]]
    jobs = []
    for name in names:
        r = run.resolve(spec, name)
        jobs.append((name, r["cfg"], r["traffic"], r["cell"]["chips"]))
    if args.four_chip:
        with open(os.path.join(ROOT, "bench", "configs",
                               "sheared-llama-1.3b-hla2.json")) as f:
            c = json.load(f)
        with open(os.path.join(ROOT, "bench", "traffic",
                               "train-2x4k.json")) as f:
            t = dict(json.load(f), batch=4)
        jobs.append(("train-24l-4chip (rehearsal only)", c, t, 4))
    for name, c, t, chips in jobs:
        devs = list(topo.devices[:chips])
        build = train if t["loop"] == "train" else serve
        for prog, shapes, compiled in build(c, t, devs):
            print(json.dumps({"cell": name, "program": prog, **shapes,
                              **_mem(compiled),
                              "kernel": "tpu_custom_call" in
                              compiled.as_text()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

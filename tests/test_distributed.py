"""Distributed tests — each spawns a fresh python with 8 host devices
(XLA_FLAGS is locked at jax init, so the main pytest process stays at 1).
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(body, devices=8, timeout=600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_ENABLE_X64", None)
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout


pytestmark = pytest.mark.subprocess


def test_pjit_train_matches_single_device():
    """3 training steps on a 2x4 mesh == single-device run (same seeds)."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from repro.configs import get_config
        from repro.distributed import steps as steps_mod, sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models.param import init_params
        from repro.optim import adamw
        from repro.data.pipeline import DataConfig, SyntheticStream

        cfg = get_config("hla-1b", reduced=True)
        specs = steps_mod.model_specs(cfg)
        oc = adamw.OptConfig(lr=1e-3, warmup_steps=0, total_steps=10)
        stream = SyntheticStream(DataConfig(cfg.vocab, 32, 8, seed=1))

        def run(mesh):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                opt = adamw.init_opt_state(params)
                step = jax.jit(steps_mod.make_train_step(cfg, oc))
                losses = []
                for s in range(3):
                    b = {k: jnp.asarray(v) for k, v in stream.batch(s).items()}
                    params, opt, m = step(params, opt, b)
                    losses.append(float(m["loss"]))
            return losses, params

        mesh8 = make_mesh((2, 4), ("data", "model"))
        l8, p8 = run(mesh8)
        mesh1 = make_mesh((1, 1), ("data", "model"))
        l1, p1 = run(mesh1)
        # float reassociation across 8-way DP reductions + contention-dependent
        # XLA scheduling: loose tolerances (exactness is covered by the
        # single-process equivalence tests)
        np.testing.assert_allclose(l8, l1, rtol=5e-3)
        for a, b in zip(jax.tree.leaves(p8), jax.tree.leaves(p1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-3, rtol=5e-2)
        print("OK")
    """)
    assert "OK" in out


def test_train_grad_agreement_single_step():
    """Tight single-step gradient agreement — catches sharding-dependent
    numerics (RNG partitioning, reduction reassociation, accumulation
    semantics) far below the 3-step-loss level:

    * mesh (2, 4) vs (1, 1) gradients agree to <= 1e-5;
    * microbatched accumulation (4 microbatches, global-count CE
      normalizer) matches the unmicrobatched step to <= 1e-5.
    """
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from repro.configs import get_config
        from repro.distributed import steps as steps_mod, sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models.param import init_params
        from repro.optim import adamw
        from repro.data.pipeline import DataConfig, SyntheticStream

        cfg = get_config("hla-1b", reduced=True)
        specs = steps_mod.model_specs(cfg)
        stream = SyntheticStream(DataConfig(cfg.vocab, 32, 8, seed=2))
        batch = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}
        # uneven masking across microbatch boundaries: the exactness of
        # the global-count normalizer is what's under test
        lab = np.asarray(batch["labels"]).copy()
        lab[:3, :11] = -1
        batch["labels"] = jnp.asarray(lab)

        def grads_on(mesh):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                gfn = jax.jit(lambda p, b: jax.value_and_grad(
                    steps_mod._loss_fn, has_aux=True)(p, b, cfg)[1])
                return jax.tree.map(np.asarray, gfn(params, batch))

        g8 = grads_on(make_mesh((2, 4), ("data", "model")))
        g1 = grads_on(make_mesh((1, 1), ("data", "model")))
        for a, b in zip(jax.tree.leaves(g8), jax.tree.leaves(g1)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)

        # microbatch accumulation == single batch (same mesh)
        oc = adamw.OptConfig(lr=1e-3, warmup_steps=0, total_steps=10)
        mesh = make_mesh((2, 4), ("data", "model"))
        def one_step(microbatches):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                opt = adamw.init_opt_state(params)
                step = jax.jit(steps_mod.make_train_step(
                    cfg, oc, microbatches=microbatches, grad_shardings=ps))
                params, opt, m = step(params, opt, batch)
                return float(m["loss"]), jax.tree.map(np.asarray, params)
        l1_, p1_ = one_step(1)
        l4_, p4_ = one_step(4)
        assert abs(l1_ - l4_) < 1e-5, (l1_, l4_)
        for a, b in zip(jax.tree.leaves(p4_), jax.tree.leaves(p1_)):
            np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_train_step_runs_fused_kernels():
    """With use_pallas (forced into interpret mode off-TPU) the sharded
    train step traces the fused Pallas forward AND backward — not the jnp
    fallback — and matches the jnp path numerically."""
    out = run_py("""
        import dataclasses, functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import steps as steps_mod, sharding as shd
        from repro.kernels import ops as kops
        from repro.launch.mesh import make_mesh
        from repro.models.param import init_params
        from repro.optim import adamw
        from repro.data.pipeline import DataConfig, SyntheticStream

        cfg = get_config("hla-1b", reduced=True)
        cfgp = cfg.replace(
            hla=dataclasses.replace(cfg.hla, force_pallas=True, chunk=16)
        )
        oc = adamw.OptConfig(lr=1e-3, warmup_steps=0, total_steps=10)
        stream = SyntheticStream(DataConfig(cfg.vocab, 32, 8, seed=1))
        batch = {k: jnp.asarray(v) for k, v in stream.batch(0).items()}
        mesh = make_mesh((2, 4), ("data", "model"))

        def one_step(c):
            specs = steps_mod.model_specs(c)
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                opt = adamw.init_opt_state(params)
                step = jax.jit(steps_mod.make_train_step(
                    c, oc, grad_shardings=ps))
                params, opt, m = step(params, opt, batch)
                return float(m["loss"]), jax.tree.map(np.asarray, params)

        kops.TRACE_COUNTS.clear()
        lp, pp = one_step(cfgp)
        assert kops.TRACE_COUNTS["hla2_fwd_fused"] > 0, kops.TRACE_COUNTS
        assert kops.TRACE_COUNTS["hla2_bwd_fused"] > 0, kops.TRACE_COUNTS
        lj, pj = one_step(cfg)  # jnp fallback reference
        assert abs(lp - lj) < 1e-4, (lp, lj)
        for a, b in zip(jax.tree.leaves(pp), jax.tree.leaves(pj)):
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-3)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_serving_matches_single_device():
    """The sharded engine (params + slot states on a (2, 4) mesh, slots on
    "data", heads on "model") samples exactly the tokens the single-device
    engine does, with matching final slot states — and the pool's states
    carry the explicit shardings rather than a replicated tree."""
    out = run_py("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.models.param import init_params
        from repro.serving import Engine, GenRequest, SamplingConfig

        cfg = get_config("hla-1b", reduced=True)
        specs = lm.lm_specs(cfg)
        mk_reqs = lambda: [
            GenRequest(
                rid=i,
                prompt=np.random.RandomState(100 + i).randint(
                    2, cfg.vocab, 12),
                max_new=8,
            )
            for i in range(5)
        ]

        def run(mesh, use_mesh):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                eng = Engine(
                    cfg, params, slots=2, max_len=40,
                    sampling=SamplingConfig(method="temperature",
                                            temperature=0.8),
                    block=4, seed=7, mesh=mesh if use_mesh else None,
                )
                res = eng.run(mk_reqs())
                states = jax.tree.map(np.asarray, eng.pool.states)
            return res, states, eng

        mesh8 = make_mesh((2, 4), ("data", "model"))
        r8, s8, e8 = run(mesh8, True)
        spec = jax.tree.leaves(e8.pool.states)[0].sharding.spec
        assert tuple(spec) == (None, "data", "model"), spec
        r1, s1, _ = run(make_mesh((1, 1), ("data", "model")), False)
        for a, b in zip(r8, r1):
            assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        for a, b in zip(jax.tree.leaves(s8), jax.tree.leaves(s1)):
            np.testing.assert_allclose(a, b, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_multipod_mesh_axes_and_dryrun_cli():
    """Reduced dry-run through the real CLI on a 2x2x2 pod mesh."""
    env = dict(os.environ)
    env["DRYRUN_DEVICES"] = "8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    out = os.path.join("/tmp", "dryrun_cli_test.json")
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "hla-1b",
         "--shape", "train_4k", "--mesh", "2x2x2", "--json", out],
        capture_output=True, text=True, timeout=900, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    with open(out) as f:
        res = json.load(f)
    assert res["mesh"] == {"pod": 2, "data": 2, "model": 2}
    assert res["cost"]["flops"] > 0
    assert res["roofline"]["bottleneck"] in (
        "compute_s", "memory_s", "collective_s"
    )


def test_elastic_checkpoint_reshard():
    """Save on a (4, 2) mesh; restore onto (2, 2) — different device count."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools, tempfile
        from repro.configs import get_config
        from repro.distributed import steps as steps_mod, sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models.param import init_params
        from repro.checkpoint.manager import CheckpointManager

        cfg = get_config("hla-1b", reduced=True)
        specs = steps_mod.model_specs(cfg)
        d = tempfile.mkdtemp()
        mesh_a = make_mesh((4, 2), ("data", "model"))
        with mesh_a:
            ps = shd.param_shardings(specs, mesh_a)
            params = jax.jit(functools.partial(init_params, specs),
                             out_shardings=ps)(jax.random.key(3))
            mgr = CheckpointManager(d, async_save=False)
            mgr.save(5, params, block=True)

        mesh_b = make_mesh((2, 2), ("data", "model"))  # elastic: fewer devices
        with mesh_b:
            ps_b = shd.param_shardings(specs, mesh_b)
            restored, manifest = CheckpointManager(d).restore(
                params, shardings=ps_b
            )
        assert manifest["step"] == 5
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # restored arrays live on the new mesh's devices
        leaf = jax.tree.leaves(restored)[0]
        assert len(leaf.sharding.device_set) <= 4
        print("OK")
    """)
    assert "OK" in out


def test_int8_error_feedback_allreduce():
    """Compressed all-reduce ~ exact mean; error feedback shrinks bias
    across repeated rounds on the same direction."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.distributed.compression import int8_allreduce_mean

        mesh = make_mesh((8,), ("data",))
        x = np.random.RandomState(0).randn(8, 4096).astype(np.float32)

        @functools.partial(jax.shard_map, mesh=mesh,
                           in_specs=(P("data"), P("data")),
                           out_specs=(P("data"), P("data")))
        def run(xs, es):
            red, e = int8_allreduce_mean(xs[0], "data", es[0])
            return red[None], e[None]

        exact = x.mean(0)
        err = jnp.zeros((8, 4096), jnp.float32)
        red, err = run(jnp.asarray(x), err)
        red0 = np.asarray(red[0])
        rel = np.abs(red0 - exact).max() / np.abs(exact).max()
        assert rel < 0.05, rel
        # error feedback: accumulated estimate over rounds converges
        acc = np.zeros_like(exact)
        est = np.zeros_like(exact)
        for r in range(8):
            red, err = run(jnp.asarray(x), err)
            acc += x.mean(0)
            est += np.asarray(red[0])
        rel2 = np.abs(est - acc).max() / np.abs(acc).max()
        assert rel2 < 0.02, rel2
        print("OK", rel, rel2)
    """)
    assert "OK" in out


def test_pipeline_parallel_matches_serial():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.distributed.pipeline_par import pipelined_forward

        mesh = make_mesh((4,), ("pipe",))
        L, M, mb, n, d = 8, 4, 2, 8, 16
        rng = np.random.RandomState(0)
        Ws = jnp.asarray(rng.randn(L, d, d) * (d ** -0.5), jnp.float32)
        xs = jnp.asarray(rng.randn(M, mb, n, d), jnp.float32)

        def layer(w, x):
            return jnp.tanh(x @ w)

        out = pipelined_forward(layer, Ws, xs, mesh)

        ref = xs
        for i in range(L):
            ref = layer(Ws[i], ref)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

        # gradients flow through ppermute (GPipe backward for free)
        def loss_pp(Ws):
            return jnp.sum(pipelined_forward(layer, Ws, xs, mesh) ** 2)

        def loss_ref(Ws):
            h = xs
            for i in range(L):
                h = layer(Ws[i], h)
            return jnp.sum(h ** 2)

        g_pp = jax.grad(loss_pp)(Ws)
        g_ref = jax.grad(loss_ref)(Ws)
        np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref),
                                   atol=1e-4, rtol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_train_cli_failure_restart(tmp_path):
    """launch.train with an injected failure, then a restart that resumes."""
    env = dict(os.environ)
    env["HOST_DEVICES"] = "4"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    ck = str(tmp_path / "ck")
    args = [sys.executable, "-m", "repro.launch.train", "--arch", "hla-1b",
            "--reduced", "--steps", "12", "--batch", "4", "--seq", "32",
            "--ckpt-dir", ck, "--ckpt-every", "4"]
    p1 = subprocess.run(args + ["--fail-at-step", "9"], capture_output=True,
                        text=True, timeout=900, env=env, cwd=REPO)
    assert p1.returncode != 0
    assert "injected fault at point 'train.step'" in p1.stderr
    p2 = subprocess.run(args, capture_output=True, text=True, timeout=900,
                        env=env, cwd=REPO)
    assert p2.returncode == 0, p2.stderr[-2000:]
    assert "resumed from step 7" in p2.stdout
    assert "finished at step 11" in p2.stdout


def test_sharded_serving_hla3_matches_single_device():
    """hla3 (exact third-order) serves under a mesh: its composite
    (LinAttn o HLA2) decode state is declared in the per-variant
    state-axes registry, so pool states come up explicitly sharded and the
    sampled tokens match the single-device engine exactly."""
    out = run_py("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.models.param import init_params
        from repro.serving import Engine, GenRequest

        cfg = get_config("hla-1b", reduced=True).replace(mixer="hla3")
        specs = lm.lm_specs(cfg)
        mk_reqs = lambda: [
            GenRequest(
                rid=i,
                prompt=np.random.RandomState(40 + i).randint(
                    2, cfg.vocab, 10),
                max_new=8,
            )
            for i in range(4)
        ]

        def run(mesh, use_mesh):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                eng = Engine(cfg, params, slots=2, max_len=40, block=4,
                             seed=5, mesh=mesh if use_mesh else None)
                res = eng.run(mk_reqs())
                states = jax.tree.map(np.asarray, eng.pool.states)
            return res, states, eng

        mesh8 = make_mesh((2, 4), ("data", "model"))
        r8, s8, e8 = run(mesh8, True)
        # every hla3 state leaf is explicitly placed (slots->data,
        # heads->model), incl. the inner LinAttn and outer HLA2 legs
        for leaf in jax.tree.leaves(e8.pool.states):
            assert tuple(leaf.sharding.spec)[:3] == (None, "data", "model"), (
                leaf.shape, leaf.sharding.spec)
        r1, s1, _ = run(make_mesh((1, 1), ("data", "model")), False)
        for a, b in zip(r8, r1):
            assert a.tokens == b.tokens, (a.rid, a.tokens, b.tokens)
        for a, b in zip(jax.tree.leaves(s8), jax.tree.leaves(s1)):
            np.testing.assert_allclose(a, b, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_sharded_spec_decode_matches_single_device():
    """Speculative serving on a (2, 4) mesh: target pool AND draft-model
    pool states placed via the per-module *_state_axes scheme, the fused
    verify/rollback round shard_map-dispatched — and the greedy streams
    equal (a) the single-device speculative engine's and (b) plain
    non-speculative greedy decode."""
    out = run_py("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.models.param import init_params
        from repro.serving import Engine, GenRequest, SpecConfig

        cfg = get_config("hla-1b", reduced=True)
        specs = lm.lm_specs(cfg)
        mk_reqs = lambda: [
            GenRequest(
                rid=i,
                prompt=np.random.RandomState(60 + i).randint(
                    2, cfg.vocab, 12),
                max_new=10,
            )
            for i in range(4)
        ]

        def run(mesh, use_mesh, spec):
            with mesh:
                ps = shd.param_shardings(specs, mesh)
                params = jax.jit(functools.partial(init_params, specs),
                                 out_shardings=ps)(jax.random.key(0))
                eng = Engine(cfg, params, slots=2, max_len=48, block=4,
                             seed=9, mesh=mesh if use_mesh else None,
                             spec=spec)
                res = eng.run(mk_reqs())
            return res, eng

        mesh1 = make_mesh((1, 1), ("data", "model"))
        mesh8 = make_mesh((2, 4), ("data", "model"))
        spec = lambda: SpecConfig(k=3, drafter="lm", draft_arch="hla-1b")
        r_plain, _ = run(mesh1, False, None)
        r1, _ = run(mesh1, False, spec())
        r8, e8 = run(mesh8, True, spec())
        # draft pool states are explicitly sharded like the target's
        for leaf in jax.tree.leaves(e8.drafter.pool.states):
            assert tuple(leaf.sharding.spec)[:3] == (None, "data", "model"), (
                leaf.shape, leaf.sharding.spec)
        for a, b, c in zip(r8, r1, r_plain):
            assert a.tokens == b.tokens, ("mesh", a.rid, a.tokens, b.tokens)
            assert a.tokens == c.tokens, ("spec", a.rid, a.tokens, c.tokens)
        assert e8.stats["spec_rounds"] > 0
        print("OK")
    """)
    assert "OK" in out

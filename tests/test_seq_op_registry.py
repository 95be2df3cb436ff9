"""SequenceOp registry conformance suite (DESIGN.md §11).

Parametrized over EVERY registered operator — a new op (registered via the
public ``seq_op.register_op``) is automatically held to the same
contracts the trainer, the serving engine, the speculative verifier and
the sharder rely on:

* ``state_axes`` tree matches ``init_state`` leaf-for-leaf (structure AND
  per-leaf rank) — the exact drift that crashed hla3_paper serving;
* ``forward(want_state=True)`` then ``step`` over the tail reproduces
  ``forward`` over the concatenated sequence (the paper's Section-4
  chunkwise == serial identity, required for prefill -> decode hand-off);
* the ``streaming`` capability flag is consistent with ``step``
  availability;
* duplicate / unknown registration fails loudly with the registry listing
  and a closest-match hint.

Plus the end-to-end proof for the registry's worked example: the ``gla``
operator trains, prefills, continuously-batch decodes and (subprocess
lane) serves sharded — with zero edits to lm.py / engine.py / steps.py.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm, seq_op
from repro.models.config import MambaConfig
from repro.models.param import init_params, is_axes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

ALL_OPS = seq_op.registered_op_names()
STREAMING_OPS = seq_op.streaming_op_names()


def _cfg_for(name):
    base = get_config("hla-1b", reduced=True)
    if name == "attn":
        return base.replace(mixer="softmax")
    if name == "mamba":
        return base.replace(
            mixer="mamba", mamba=MambaConfig(d_state=8, d_conv=4, expand=2)
        )
    return base.replace(mixer=name)


def _sub_params(op, cfg, seed=0):
    return init_params(op.specs(cfg), jax.random.key(seed))


# --------------------------------------------------------------------------
# registry mechanics
# --------------------------------------------------------------------------


def test_all_eight_plus_gla_registered():
    """The eight ported operators AND the register_op-only gla."""
    assert set(ALL_OPS) >= {
        "hla2", "ahla", "hla3", "hla3_paper", "linattn",
        "attn", "mamba", "rwkv6", "gla",
    }


def test_duplicate_registration_raises():
    op = seq_op.get_op("hla2")
    with pytest.raises(seq_op.SequenceOpError, match="already registered"):
        seq_op.register_op(op)


def test_unknown_op_lists_registry_and_suggests():
    with pytest.raises(seq_op.SequenceOpError) as ei:
        seq_op.get_op("hla2x")
    msg = str(ei.value)
    assert "hla2" in msg and "registered ops" in msg
    # a config typo fails through the same path with the same hint
    cfg = get_config("hla-1b", reduced=True).replace(mixer="rwkv7")
    with pytest.raises(seq_op.SequenceOpError, match="rwkv6"):
        seq_op.op_for(cfg)


def test_streaming_flag_consistent_with_step():
    for name in ALL_OPS:
        op = seq_op.get_op(name)
        if op.streaming:
            assert op.step is not None, name
    # the built-in KV-cache op is the canonical non-streaming example
    # (user-registered non-streaming ops are equally legitimate)
    assert not seq_op.get_op("attn").streaming


def test_streaming_registration_requires_step():
    with pytest.raises(seq_op.SequenceOpError, match="step"):
        seq_op.SequenceOp(
            name="bogus", specs=lambda cfg: {},
            forward=lambda *a, **k: None,
            init_state=lambda *a, **k: None,
            state_axes=lambda cfg: None,
            streaming=True,
        )


# --------------------------------------------------------------------------
# state-tree contracts
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", ALL_OPS)
def test_state_axes_match_init_state(name):
    """state_axes tree mirrors init_state leaf-for-leaf: same structure,
    per-leaf axes length == leaf rank (the sharding source-of-truth
    contract ``distributed.steps.state_specs`` and the pool rely on)."""
    op = seq_op.get_op(name)
    cfg = _cfg_for(name)
    axes = op.state_axes(cfg)
    state = jax.eval_shape(lambda: op.init_state(cfg, 2, max_len=16))

    def chk(ax, leaf):
        assert is_axes(ax), (name, ax)
        assert len(ax) == leaf.ndim, (name, tuple(ax), leaf.shape)

    # tree.map raises on structural drift between the two trees
    jax.tree.map(chk, axes, state, is_leaf=is_axes)


@pytest.mark.parametrize("name", ALL_OPS)
def test_state_ndims_match_init_state(name):
    op = seq_op.get_op(name)
    cfg = _cfg_for(name)
    nd = op.resolve_state_ndims(cfg)
    state = jax.eval_shape(lambda: op.init_state(cfg, 2, max_len=16))
    jax.tree.map(
        lambda r, leaf: (_ for _ in ()).throw(
            AssertionError((name, r, leaf.shape))
        ) if r != leaf.ndim else None,
        nd, state,
    )


# --------------------------------------------------------------------------
# forward/step agreement (the streaming identity)
# --------------------------------------------------------------------------


@pytest.mark.parametrize("name", STREAMING_OPS)
def test_forward_then_step_matches_forward(name):
    """prefix forward(want_state=True) + per-token steps == one forward
    over the whole sequence, <= 1e-4."""
    op = seq_op.get_op(name)
    cfg = _cfg_for(name)
    rng = np.random.RandomState(0)
    B, n, t = 2, 16, 7
    x = jnp.asarray(rng.randn(B, n, cfg.d_model) * 0.1, jnp.float32)
    p = _sub_params(op, cfg)

    y_full, _ = op.forward(p, x, cfg, want_state=True)

    y1, st = op.forward(p, x[:, :t], cfg, want_state=True)
    pieces = [np.asarray(y1, np.float32)]
    for j in range(t, n):
        yj, st = op.step(
            p, x[:, j:j + 1], st, cfg,
            positions=jnp.full((B, 1), j, jnp.int32),
        )
        pieces.append(np.asarray(yj, np.float32))
    y_cat = np.concatenate(pieces, axis=1)
    np.testing.assert_allclose(
        y_cat, np.asarray(y_full, np.float32), atol=1e-4, rtol=1e-4,
    )


def test_attn_cache_step_matches_forward():
    """The non-streaming op's cache-based step agrees with the cacheless
    forward (looser tol: the KV cache stores bf16)."""
    op = seq_op.get_op("attn")
    cfg = _cfg_for("attn")
    rng = np.random.RandomState(1)
    B, n, t = 2, 12, 5
    x = jnp.asarray(rng.randn(B, n, cfg.d_model) * 0.1, jnp.float32)
    p = _sub_params(op, cfg)

    y_full, _ = op.forward(p, x, cfg)

    st = op.init_state(cfg, B, max_len=n)
    y1, st = op.forward(
        p, x[:, :t], cfg, state=st, want_state=True,
        positions=jnp.arange(t)[None],
    )
    pieces = [np.asarray(y1, np.float32)]
    for j in range(t, n):
        yj, st = op.step(
            p, x[:, j:j + 1], st, cfg,
            positions=jnp.full((B, 1), j, jnp.int32),
        )
        pieces.append(np.asarray(yj, np.float32))
    y_cat = np.concatenate(pieces, axis=1)
    np.testing.assert_allclose(
        y_cat, np.asarray(y_full, np.float32), atol=2e-2, rtol=2e-2,
    )


@pytest.mark.parametrize("name", STREAMING_OPS)
def test_forward_resumes_from_carry(name):
    """forward(state=mid_carry) == the tail of one full forward — the
    incremental-prefill / speculative-verify contract."""
    op = seq_op.get_op(name)
    cfg = _cfg_for(name)
    rng = np.random.RandomState(2)
    B, n, t = 2, 16, 8
    x = jnp.asarray(rng.randn(B, n, cfg.d_model) * 0.1, jnp.float32)
    p = _sub_params(op, cfg)

    y_full, st_full = op.forward(p, x, cfg, want_state=True)
    _, st1 = op.forward(p, x[:, :t], cfg, want_state=True)
    y2, st2 = op.forward(p, x[:, t:], cfg, state=st1, want_state=True)
    np.testing.assert_allclose(
        np.asarray(y2, np.float32),
        np.asarray(y_full[:, t:], np.float32), atol=1e-4, rtol=1e-4,
    )
    for a, b in zip(jax.tree.leaves(st_full), jax.tree.leaves(st2)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-4, rtol=1e-3,
        )


# --------------------------------------------------------------------------
# gla end-to-end: train / prefill / continuous batching / sharding
# --------------------------------------------------------------------------


def test_gla_trains_with_finite_grads():
    cfg = _cfg_for("gla")
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(1, cfg.vocab, (2, 24)))
    labels = jnp.asarray(rng.randint(1, cfg.vocab, (2, 24)))
    (loss, _), grads = jax.value_and_grad(
        lambda p: lm.lm_loss(p, toks, labels, cfg), has_aux=True
    )(params)
    assert np.isfinite(float(loss))
    gnorm = sum(
        float(jnp.sum(g.astype(jnp.float32) ** 2))
        for g in jax.tree.leaves(grads)
    )
    assert np.isfinite(gnorm) and gnorm > 0.0


def test_gla_serving_end_to_end():
    """Engine (prefill admission -> continuous-batching block decode) over
    gla matches token-for-token a reference greedy loop of plain
    lm_prefill + per-token lm_apply decode steps."""
    from repro.serving import Engine, GenRequest

    cfg = _cfg_for("gla")
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    rng = np.random.RandomState(4)
    prompts = [rng.randint(2, cfg.vocab, 10) for _ in range(3)]
    max_new = 8

    eng = Engine(cfg, params, slots=2, max_len=40, block=4, seed=0)
    results = eng.run([
        GenRequest(rid=i, prompt=p, max_new=max_new)
        for i, p in enumerate(prompts)
    ])

    for i, prompt in enumerate(prompts):
        toks = jnp.asarray(np.asarray(prompt, np.int32)[None])
        lg, st = lm.lm_prefill(params, toks, cfg)
        out = [int(jnp.argmax(lg[0]))]
        pos = len(prompt)
        while len(out) < max_new:
            lg, st, _ = lm.lm_apply(
                params, jnp.asarray([[out[-1]]], jnp.int32), cfg,
                states=st, positions=jnp.asarray([[pos]]), mode="decode",
            )
            out.append(int(jnp.argmax(lg[0, -1])))
            pos += 1
        assert results[i].tokens == out, (i, results[i].tokens, out)


def test_gla_rejected_nowhere():
    """gla is spec-decodable: the speculative engine path accepts it and
    greedy spec decode equals plain greedy (the §10 exactness contract)."""
    from repro.serving import Engine, GenRequest, SpecConfig

    cfg = _cfg_for("gla")
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    rng = np.random.RandomState(5)
    # repetitive prompt so the n-gram drafter gets some acceptance
    prompt = np.tile(rng.randint(2, cfg.vocab, 4), 5)
    reqs = lambda: [GenRequest(rid=0, prompt=prompt, max_new=10)]  # noqa: E731

    plain = Engine(cfg, params, slots=1, max_len=64, block=4, seed=0)
    r_plain = plain.run(reqs())
    spec = Engine(cfg, params, slots=1, max_len=64, block=4, seed=0,
                  spec=SpecConfig(drafter="ngram", k=3))
    r_spec = spec.run(reqs())
    assert r_plain[0].tokens == r_spec[0].tokens


@pytest.mark.subprocess
def test_gla_sharded_serving_matches_single_device():
    """gla serves on a (2, 4) mesh — pool states placed by its registered
    state_axes (slots on data, heads on model) — and samples exactly the
    single-device engine's tokens.  Zero gla-specific code in lm.py,
    engine.py or distributed/steps.py."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("JAX_ENABLE_X64", None)
    body = textwrap.dedent("""
        import functools
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import lm
        from repro.models.param import init_params
        from repro.serving import Engine, GenRequest

        cfg = get_config("hla-1b", reduced=True).replace(mixer="gla")
        specs = lm.lm_specs(cfg)
        mk_reqs = lambda: [
            GenRequest(
                rid=i,
                prompt=np.random.RandomState(70 + i).randint(
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
                             seed=3, mesh=mesh if use_mesh else None)
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
    proc = subprocess.run(
        [sys.executable, "-c", body],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "OK" in proc.stdout


# --------------------------------------------------------------------------
# engine capability gating
# --------------------------------------------------------------------------


def test_engine_rejects_non_streaming_op():
    """A KV-cache op serves per slot, but not with a prefix cache or
    speculative decoding."""
    from repro.serving import Engine, PrefixCache, SpecConfig

    cfg = _cfg_for("attn")
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    Engine(cfg, params, slots=2, max_len=32)
    with pytest.raises(ValueError, match="KV caches"):
        Engine(cfg, params, slots=2, max_len=32,
               cache=PrefixCache(granularity=8, budget_bytes=1 << 20))
    with pytest.raises(ValueError, match="KV caches"):
        Engine(cfg, params, slots=2, max_len=32, spec=SpecConfig(k=2))

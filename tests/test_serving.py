"""Serving subsystem: state pool, engine, sampling + scan-impl equivalence."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.models.param import init_params
from repro.serving import Engine, GenRequest, SamplingConfig, StatePool, sample


def _params(cfg, seed=0):
    return init_params(lm.lm_specs(cfg), jax.random.key(seed))


def test_scan_impl_matches_chunkwise(rng):
    """mixer impl='scan' (paper Blelloch path) == impl='chunkwise'."""
    cfg_c = get_config("hla-1b", reduced=True)
    cfg_s = cfg_c.replace(hla=dataclasses.replace(cfg_c.hla, impl="scan"))
    specs = lm.lm_specs(cfg_c)
    params = init_params(specs, jax.random.key(0))
    tokens = jnp.asarray(rng.randint(0, cfg_c.vocab, (2, 16)))
    lc, _, _ = lm.lm_apply(params, tokens, cfg_c)
    ls, _, _ = lm.lm_apply(params, tokens, cfg_s)
    np.testing.assert_allclose(
        np.asarray(lc, np.float32), np.asarray(ls, np.float32),
        atol=1e-3, rtol=1e-3,
    )


def test_lm_prefill_incremental_matches_full(rng):
    """Prefill resumed from a mid-prompt carry == one-shot prefill."""
    cfg = get_config("hla-1b", reduced=True)
    params = _params(cfg)
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (1, 12)))
    lg_full, st_full = lm.lm_prefill(params, toks, cfg)
    _, st1 = lm.lm_prefill(params, toks[:, :7], cfg)
    lg2, st2 = lm.lm_prefill(
        params, toks[:, 7:], cfg, states=st1,
        positions=jnp.arange(7, 12)[None],
    )
    np.testing.assert_allclose(
        np.asarray(lg2, np.float32), np.asarray(lg_full, np.float32),
        atol=1e-3, rtol=1e-3,
    )
    for ref, got in zip(jax.tree.leaves(st_full), jax.tree.leaves(st2)):
        np.testing.assert_allclose(
            np.asarray(ref, np.float32), np.asarray(got, np.float32),
            atol=1e-4, rtol=1e-3,
        )


# --------------------------------------------------------------------------
# StatePool: structural slot-axis detection
# --------------------------------------------------------------------------


def test_state_pool_slot_axis_regression():
    """Regression for the old serve.py restore heuristic.

    The legacy loop restored other slots with ``leaf.shape[1] == slots`` —
    tree surgery keyed on a *coincidence of extents*.  Leaf ``a`` below has
    its slot axis at 0 while its axis-1 extent equals the slot count, so
    the heuristic picks the wrong axis and cross-contaminates slots.  The
    pool derives axes structurally (slots vs slots+1 probe) instead.
    """
    slots = 3

    def make(n):
        return {
            "a": jnp.zeros((n, slots)),  # slot axis 0; shape[1] == slots!
            "b": jnp.zeros((4, n, slots)),  # slot axis 1
            "shared": jnp.zeros((5,)),  # no slot axis
        }

    pool = StatePool(make, slots)
    assert pool.slot_axes == [0, 1, None]

    # the legacy heuristic would have chosen axis 1 for leaf "a"
    legacy_axis = 1 if make(slots)["a"].shape[1] == slots else None
    assert legacy_axis != pool.slot_axes[0]

    ones = jax.tree.map(jnp.ones_like, pool.empty_slot_state())
    pool.write_slot(1, ones)
    a = np.asarray(pool.states["a"])
    b = np.asarray(pool.states["b"])
    # only slot 1's data changed, along the *structural* axis
    assert (a[1] == 1).all() and (a[[0, 2]] == 0).all()
    assert (b[:, 1] == 1).all() and (b[:, [0, 2]] == 0).all()
    assert (np.asarray(pool.states["shared"]) == 0).all()

    # round-trip + eviction
    got = pool.read_slot(1)
    assert (np.asarray(got["a"]) == 1).all()
    pool.reset_slot(1)
    assert (np.asarray(pool.states["a"]) == 0).all()


def test_state_pool_is_built_by_one_program():
    """The pool, and the zeros a reset writes, each come out of one traced
    program: built op by op, a stacked template holds a layer's leaf, its
    copy and its broadcast at once, which overran the chip's memory with
    a hybrid stack's KV caches."""
    concrete = []

    def make(n):
        x = jnp.zeros((2, n, 4))
        concrete.append(not isinstance(x, jax.core.Tracer))
        return {"s": jnp.broadcast_to(x[None], (3,) + x.shape)}

    pool = StatePool(make, 3)
    pool.write_slot(1, {"s": jnp.ones((3, 2, 1, 4))})
    pool.reset_slot(1)
    assert concrete and not any(concrete)
    assert pool.states["s"].shape == (3, 2, 3, 4)
    assert not np.asarray(pool.states["s"]).any()


def test_state_pool_lm_states():
    """Pool over real stacked LM decode states; KV lengths are per slot."""
    cfg = get_config("hla-1b", reduced=True)
    pool = StatePool(lambda n: lm.lm_init_states(cfg, n, 32), slots=4)
    # every HLA2 state leaf is (layers, slot, head, ...) -> slot axis 1
    assert all(ax == 1 for ax in pool.slot_axes)

    cfg_sm = cfg.replace(mixer="softmax")
    pool_sm = StatePool(lambda n: lm.lm_init_states(cfg_sm, n, 32), slots=4)
    # KVCache.length is (layers, slot): every leaf has its slot axis at 1
    assert all(ax == 1 for ax in pool_sm.slot_axes)


# --------------------------------------------------------------------------
# Engine: continuous batching
# --------------------------------------------------------------------------


def test_engine_recycled_slot_reproduces(rng):
    """Same prompt re-admitted into a recycled slot regenerates exactly."""
    cfg = get_config("hla-1b", reduced=True)
    engine = Engine(cfg, _params(cfg), slots=1, max_len=32, block=4)
    prompt = rng.randint(2, cfg.vocab, 5)
    reqs = [
        GenRequest(rid=0, prompt=prompt, max_new=6),
        GenRequest(rid=1, prompt=rng.randint(2, cfg.vocab, 5), max_new=6),
        GenRequest(rid=2, prompt=prompt, max_new=6),
    ]
    r0, r1, r2 = engine.run(reqs)
    assert len(r0.tokens) == 6 and len(r1.tokens) == 6
    assert r0.tokens == r2.tokens  # slot reset/overwrite is complete


def test_engine_admission_never_perturbs_live_slots(rng):
    """A mid-stream admission must not change a live slot's continuation."""
    cfg = get_config("hla-1b", reduced=True)
    params = _params(cfg)
    prompt_a = rng.randint(2, cfg.vocab, 5)
    prompt_b = rng.randint(2, cfg.vocab, 5)

    # reference: A decodes alone
    solo = Engine(cfg, params, slots=2, max_len=32, block=4)
    (ra,) = solo.run([GenRequest(rid=0, prompt=prompt_a, max_new=12)])

    # A decodes one block, then B is admitted into the other slot
    eng = Engine(cfg, params, slots=2, max_len=32, block=4)
    eng.admit(0, GenRequest(rid=0, prompt=prompt_a, max_new=12))
    eng.step_block()
    eng.admit(1, GenRequest(rid=1, prompt=prompt_b, max_new=8))
    while eng.active.any():
        eng.step_block()
    assert eng.results[0].tokens == ra.tokens
    assert len(eng.results[1].tokens) == 8


def test_engine_ragged_prompts_and_throughput_stats(rng):
    cfg = get_config("hla-1b", reduced=True)
    engine = Engine(cfg, _params(cfg), slots=2, max_len=64, block=4)
    reqs = [
        GenRequest(rid=i, prompt=rng.randint(2, cfg.vocab, ln), max_new=5)
        for i, ln in enumerate([3, 9, 9])
    ]
    results = engine.run(reqs)
    assert [len(r.tokens) for r in results] == [5, 5, 5]
    assert engine.stats["generated_tokens"] == 15
    assert len(engine.stats["ttft_s"]) == 3
    assert engine.stats["decode_s"] > 0


def test_engine_rejects_kv_cache_archs():
    """KV-cache stacks serve per slot, but without a prefix cache or
    speculative decoding: both are refused at construction."""
    from repro.serving import PrefixCache, SpecConfig

    cfg = get_config("hla-1b", reduced=True).replace(mixer="softmax")
    with pytest.raises(ValueError, match="prefix cache"):
        Engine(cfg, None, slots=2, max_len=16,
               cache=PrefixCache(granularity=8, budget_bytes=1 << 20))
    with pytest.raises(ValueError, match="speculative"):
        Engine(cfg, None, slots=2, max_len=16, spec=SpecConfig(k=2))


# --------------------------------------------------------------------------
# Sampling
# --------------------------------------------------------------------------


def test_sampling_greedy_and_seeded(rng):
    logits = jnp.asarray(rng.randn(4, 32), jnp.float32)
    key = jax.random.key(0)
    g = sample(logits, key, SamplingConfig(method="greedy"))
    assert (np.asarray(g) == np.argmax(np.asarray(logits), -1)).all()

    t1 = sample(logits, key, SamplingConfig(method="temperature", temperature=0.8))
    t2 = sample(logits, key, SamplingConfig(method="temperature", temperature=0.8))
    assert (np.asarray(t1) == np.asarray(t2)).all()  # same seed, same draw

    tk = sample(logits, key, SamplingConfig(method="top_k", top_k=2))
    top2 = np.argsort(np.asarray(logits), -1)[:, -2:]
    assert all(int(t) in top2[i] for i, t in enumerate(np.asarray(tk)))

    with pytest.raises(ValueError):
        sample(logits, key, SamplingConfig(method="top_k", top_k=0))

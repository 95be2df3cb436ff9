"""Speculative decoding: exactness, rollback, drafters, sampling laws.

The load-bearing property (DESIGN.md §10): speculative GREEDY decode is
token-for-token identical to plain greedy decode — for every streaming
mixer variant, regardless of what the drafter proposes, where rejections
land, or how ragged the prompt lengths are.  Acceptance only ever changes
*how many* target calls are made, never *which tokens* come out.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.models import mixer as mixer_mod
from repro.models.param import init_params
from repro.serving import (
    Engine,
    GenRequest,
    SamplingConfig,
    SpecConfig,
    StatePool,
)
from repro.serving.spec import HLADrafter, NGramDrafter
from repro.serving.spec.drafters import Drafter

VARIANTS = ("hla2", "ahla", "hla3", "linattn")


def _cfg(mixer="hla2", decay="learned", normalize=False):
    base = get_config("hla-1b", reduced=True).replace(mixer=mixer)
    return base.replace(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
        hla=dataclasses.replace(
            base.hla, decay=decay, normalize=normalize, chunk=16
        ),
    )


def _params(cfg, seed=0):
    return init_params(lm.lm_specs(cfg), jax.random.key(seed))


def _requests(cfg, rng, lens=(5, 11, 7), max_new=10):
    return [
        GenRequest(rid=i, prompt=rng.randint(2, cfg.vocab, ln),
                   max_new=max_new)
        for i, ln in enumerate(lens)
    ]


def _run_pair(cfg, seed, spec):
    """(plain greedy results, speculative greedy results, spec engine)."""
    params = _params(cfg)
    reqs = lambda: _requests(cfg, np.random.RandomState(seed))  # noqa: E731
    plain = Engine(cfg, params, slots=2, max_len=96, block=4)
    rp = plain.run(reqs())
    eng = Engine(cfg, params, slots=2, max_len=96, block=4, spec=spec)
    rs = eng.run(reqs())
    return rp, rs, eng


# --------------------------------------------------------------------------
# exactness: spec greedy == plain greedy
# --------------------------------------------------------------------------


@pytest.mark.parametrize("variant", VARIANTS)
@pytest.mark.parametrize("decay", ["none", "learned"])
@pytest.mark.parametrize("normalize", [False, True])
def test_spec_greedy_exact(variant, decay, normalize):
    """Token-for-token equality across variants x gamma x normalize, with
    ragged prompt lengths and natural mid-stream rejections (the n-gram
    drafter misses until the model's continuation turns repetitive)."""
    rng = np.random.RandomState(0)
    cfg = _cfg(variant, decay, normalize)
    params = _params(cfg)
    reqs = lambda: _requests(cfg, np.random.RandomState(1))  # noqa: E731
    plain = Engine(cfg, params, slots=2, max_len=96, block=4)
    rp = plain.run(reqs())
    eng = Engine(cfg, params, slots=2, max_len=96, block=4,
                 spec=SpecConfig(k=3, drafter="ngram"))
    rs = eng.run(reqs())
    for a, b in zip(rp, rs):
        assert a.tokens == b.tokens, (variant, decay, normalize, a.rid)
    assert eng.stats["spec_rounds"] > 0


class _WrongDrafter(Drafter):
    """Adversarial: always proposes token 1 — near-guaranteed rejections."""

    def admit(self, slot, tokens):
        pass

    def commit(self, slot, tokens):
        pass

    def propose(self, slot_ids, k):
        return np.ones((len(slot_ids), k), np.int32), None


def test_spec_exact_under_constant_rejection():
    """Even a drafter that is (almost) always wrong must leave the output
    stream untouched — every round then exercises snapshot + rollback +
    accepted-prefix replay."""
    cfg = _cfg("hla2")
    rp, rs, eng = _run_pair(cfg, 2, SpecConfig(k=4, drafter=_WrongDrafter()))
    for a, b in zip(rp, rs):
        assert a.tokens == b.tokens
    assert eng.stats["spec_replays"] > 0
    # with drafts this bad, nearly every round rolls back
    assert eng.stats["spec_accepted"] <= eng.stats["spec_drafted"] // 2


def test_spec_exact_lm_drafter_and_self_draft_acceptance():
    """A random draft LM must not perturb outputs; drafting with the
    TARGET's own params accepts everything (q == p pointwise), which also
    pins the accept rule's direction."""
    cfg = _cfg("hla2")
    params = _params(cfg)
    reqs = lambda: _requests(cfg, np.random.RandomState(3), max_new=8)  # noqa: E731
    plain = Engine(cfg, params, slots=2, max_len=96, block=4)
    rp = plain.run(reqs())

    # a draft LM with its own (random) params and pool slots
    drafter = HLADrafter(_cfg("hla2"), None, slots=2, max_len=96, k=3,
                         seed=9)
    eng = Engine(cfg, params, slots=2, max_len=96, block=4,
                 spec=SpecConfig(k=3, drafter=drafter))
    rs = eng.run(reqs())
    for a, b in zip(rp, rs):
        assert a.tokens == b.tokens

    self_draft = HLADrafter(cfg, params, slots=2, max_len=96, k=3)
    eng2 = Engine(cfg, params, slots=2, max_len=96, block=4,
                  spec=SpecConfig(k=3, drafter=self_draft))
    rs2 = eng2.run(reqs())
    for a, b in zip(rp, rs2):
        assert a.tokens == b.tokens
    assert eng2.stats["spec_accepted"] == eng2.stats["spec_drafted"]
    assert eng2.stats["spec_replays"] == 0


def test_spec_greedy_exact_rwkv6():
    """rwkv6 rides the same verify path (jnp chunkwise prefill via the
    layer dispatch).  Also a regression for the init-state dtype bug:
    ``rwkv6_init_state`` hardcoded bf16 token-shift leaves, so ANY
    fp32-activation rwkv6 config crashed the decode scan (carry-in dtype
    != carry-out) — serving never worked for the reduced config."""
    from repro.configs import get_config

    cfg = get_config("rwkv6-7b", reduced=True)
    params = _params(cfg)
    reqs = lambda: _requests(cfg, np.random.RandomState(6), max_new=8)  # noqa: E731
    plain = Engine(cfg, params, slots=2, max_len=96, block=4)
    rp = plain.run(reqs())
    eng = Engine(cfg, params, slots=2, max_len=96, block=4,
                 spec=SpecConfig(k=3, drafter="ngram"))
    rs = eng.run(reqs())
    for a, b in zip(rp, rs):
        assert a.tokens == b.tokens


def test_spec_continuous_batching_mid_admission():
    """A slot admitted mid-stream must not change a live slot's
    speculative continuation (the plain-engine isolation property)."""
    cfg = _cfg("hla2")
    params = _params(cfg)
    rng = np.random.RandomState(4)
    pa, pb = rng.randint(2, cfg.vocab, 6), rng.randint(2, cfg.vocab, 9)
    spec = lambda: SpecConfig(k=3, drafter="ngram")  # noqa: E731

    solo = Engine(cfg, params, slots=2, max_len=96, block=4, spec=spec())
    (ra,) = solo.run([GenRequest(rid=0, prompt=pa, max_new=12)])

    eng = Engine(cfg, params, slots=2, max_len=96, block=4, spec=spec())
    eng.admit(0, GenRequest(rid=0, prompt=pa, max_new=12))
    eng.step_block()
    eng.admit(1, GenRequest(rid=1, prompt=pb, max_new=8))
    while eng.active.any():
        eng.step_block()
    assert eng.results[0].tokens == ra.tokens
    assert len(eng.results[1].tokens) == 8


# --------------------------------------------------------------------------
# speculative sampling (distribution-preserving path)
# --------------------------------------------------------------------------


def test_spec_sampling_seeded_and_committed_are_valid():
    """Non-greedy spec decode: deterministic per seed, commits the right
    counts, and full self-draft acceptance when q == p."""
    cfg = _cfg("hla2")
    params = _params(cfg)
    scfg = SamplingConfig(method="top_p", temperature=0.9, top_p=0.9)
    reqs = lambda: _requests(cfg, np.random.RandomState(5), max_new=8)  # noqa: E731

    def run(seed):
        eng = Engine(cfg, params, slots=2, max_len=96, block=4, seed=seed,
                     sampling=scfg, spec=SpecConfig(k=3, drafter="ngram"))
        return eng.run(reqs())

    r1, r2 = run(11), run(11)
    for a, b in zip(r1, r2):
        assert a.tokens == b.tokens  # same seed, same stream
        assert len(a.tokens) == 8
        assert all(0 <= t < cfg.vocab for t in a.tokens)

    # q == p => min(1, p/q) == 1: acceptance is total even when sampling
    drafter = HLADrafter(cfg, params, slots=2, max_len=96, k=3,
                         sampling=scfg, seed=0)
    assert drafter.emits_probs
    eng = Engine(cfg, params, slots=2, max_len=96, block=4, seed=11,
                 sampling=scfg, spec=SpecConfig(k=3, drafter=drafter))
    eng.run(reqs())
    assert eng.stats["spec_accepted"] == eng.stats["spec_drafted"]


def test_spec_greedy_engine_with_sampling_drafter():
    """A probs-emitting drafter (sampling draft law) under a GREEDY
    engine: q rides along but greedy acceptance ignores it, and the
    output must still equal plain greedy exactly.  Regression: the
    greedy verify closure used to reject the trailing q argument."""
    cfg = _cfg("hla2")
    params = _params(cfg)
    reqs = lambda: _requests(cfg, np.random.RandomState(8), max_new=8)  # noqa: E731
    plain = Engine(cfg, params, slots=2, max_len=96, block=4)
    rp = plain.run(reqs())
    drafter = HLADrafter(
        cfg, params, slots=2, max_len=96, k=3,
        sampling=SamplingConfig(method="temperature", temperature=0.8),
    )
    assert drafter.emits_probs
    eng = Engine(cfg, params, slots=2, max_len=96, block=4,
                 spec=SpecConfig(k=3, drafter=drafter))
    rs = eng.run(reqs())
    for a, b in zip(rp, rs):
        assert a.tokens == b.tokens


def test_spec_rejects_per_request_sampling_override():
    cfg = _cfg("hla2")
    eng = Engine(cfg, _params(cfg), slots=1, max_len=32,
                 spec=SpecConfig(k=2, drafter="ngram"))
    req = GenRequest(rid=0, prompt=np.array([3, 4, 5]), max_new=4,
                     sampling=SamplingConfig(method="temperature"))
    with pytest.raises(ValueError, match="ONE sampling law"):
        eng.admit(0, req)


# --------------------------------------------------------------------------
# n-gram drafter
# --------------------------------------------------------------------------


def test_ngram_drafter_prompt_lookup():
    d = NGramDrafter(max_n=3, min_n=1)
    d.admit(0, [1, 2, 3, 4, 9, 1, 2, 3])
    drafts, q = d.propose([0], 3)
    assert q is None
    # trailing [1,2,3] matched at the start -> continuation [4, 9, 1]
    assert drafts.tolist() == [[4, 9, 1]]
    d.commit(0, [4, 9])
    (drafts2,), _ = d.propose([0], 4)
    # trailing [3,4,9] now matches the earlier [3,4,9] -> [1,2,3,4]
    assert drafts2.tolist() == [1, 2, 3, 4]
    # no match for a fresh unrepeated context: repeat-last fallback
    d.admit(1, [7, 8])
    (drafts3,), _ = d.propose([1], 2)
    assert drafts3.tolist() == [8, 8]
    d.evict(0)
    d.evict(1)


# --------------------------------------------------------------------------
# StatePool snapshot / restore
# --------------------------------------------------------------------------


def test_state_pool_snapshot_restore_roundtrip_property():
    """Property, over random templates: for any slot, restore(snapshot)
    is the identity on the pool — the rollback primitive — and never
    perturbs other slots."""
    rng = np.random.RandomState(0)
    for trial in range(5):
        slots = int(rng.randint(1, 5))
        shapes = [
            (int(rng.randint(1, 4)),) if rng.rand() < 0.3 else ()
            for _ in range(3)
        ]

        def make(n, shapes=shapes):
            return {
                f"leaf{i}": jnp.zeros(sh[:1] + (n,) + sh[1:])
                for i, sh in enumerate(shapes)
            }

        pool = StatePool(make, slots)
        # randomize the pool, then overwrite arbitrary slots
        pool.states = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape)), pool.states
        )
        slot = int(rng.randint(slots))
        snap = pool.snapshot_slot(slot)
        before = jax.tree.map(np.asarray, pool.states)
        garbage = jax.tree.map(
            lambda x: jnp.asarray(rng.randn(*x.shape)),
            pool.empty_slot_state(),
        )
        pool.write_slot(slot, garbage)
        pool.restore_slot(slot, snap)
        after = jax.tree.map(np.asarray, pool.states)
        for a, b in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
            np.testing.assert_array_equal(a, b)


def test_state_pool_snapshot_restore_lm_states():
    cfg = _cfg("hla2")
    pool = StatePool(lambda n: lm.lm_init_states(cfg, n, 32), slots=3)
    pool.states = jax.tree.map(
        lambda x: jnp.asarray(np.random.RandomState(0).randn(*x.shape),
                              x.dtype),
        pool.states,
    )
    snap = pool.snapshot_slot(1)
    pool.reset_slot(1)
    pool.restore_slot(1, snap)
    got = pool.snapshot_slot(1)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_verify_snapshot_restore_host_level_rollback(rng):
    """The documented subsystem flow, driven by hand through the
    host-level primitives: snapshot_slot -> make_verify (one
    chunk-parallel call) -> on rejection restore_slot + make_replay of
    the accepted prefix.  The rolled-back slot state must equal stepping
    the accepted tokens through plain decode, to float32 rounding."""
    from repro.serving.sampling import SamplingConfig
    from repro.serving.spec import make_replay, make_verify

    cfg = _cfg("hla2")
    params = _params(cfg)
    k, slots = 4, 2
    pool = StatePool(lambda n: lm.lm_init_states(cfg, n, 64), slots)
    prompts = [rng.randint(2, cfg.vocab, 6), rng.randint(2, cfg.vocab, 9)]
    last, pos = [], []
    for s, p in enumerate(prompts):
        lg, st = lm.lm_prefill(params, jnp.asarray(p[None]), cfg)
        pool.write_slot(s, st)
        last.append(int(jnp.argmax(lg[0])))
        pos.append(len(p))
    positions = jnp.asarray(np.asarray(pos)[:, None], jnp.int32)

    verify = jax.jit(make_verify(cfg, SamplingConfig()))
    replay = jax.jit(make_replay(cfg))
    drafts = jnp.asarray(rng.randint(2, cfg.vocab, (slots, k)), jnp.int32)
    tok_block = jnp.concatenate(
        [jnp.asarray(np.asarray(last)[:, None], jnp.int32), drafts], 1
    )
    snaps = [pool.snapshot_slot(s) for s in range(slots)]
    packed, full_states = verify(
        params, pool.states, tok_block, positions, jax.random.key(0)
    )
    packed = np.asarray(packed)
    pool.states = full_states
    for s in range(slots):
        m = int(packed[s, 0])
        if m == k:
            continue
        fixed, _ = replay(
            params, snaps[s], tok_block[s:s + 1], positions[s:s + 1],
            jnp.asarray([m + 1]),
        )
        pool.restore_slot(s, fixed)
        # oracle: plain decode steps over the accepted prefix
        st, p = snaps[s], positions[s:s + 1]
        for j in range(m + 1):
            _, st, _ = lm.lm_apply(
                params, tok_block[s:s + 1, j:j + 1], cfg, states=st,
                positions=p, mode="decode",
            )
            p = p + 1
        got = pool.snapshot_slot(s)
        # the replay is jitted and the oracle steps eagerly: XLA may
        # reassociate the fused jitted reductions, so the two agree to a
        # few float32 ulps (1 ulp of a state entry near 10 is 9.5e-7),
        # not bit for bit
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(got)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6
            )
    # random drafts against a random model: rejections must have occurred
    assert any(int(packed[s, 0]) < k for s in range(slots))


# --------------------------------------------------------------------------
# state-axes registry (hla3 / hla3_paper registration)
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "variant", ("hla2", "ahla", "hla3", "hla3_paper", "linattn")
)
def test_mixer_state_axes_registered_and_consistent(variant):
    """Every variant has an EXPLICIT state-axes declaration whose tree
    structure and leaf ranks match ``mixer_init_state`` — the contract
    ``distributed.steps.state_specs`` and the serving pool rely on."""
    from repro.models.param import Axes, is_axes

    cfg = _cfg(variant, decay="none")
    axes = mixer_mod.mixer_state_axes(cfg)
    state = jax.eval_shape(lambda: mixer_mod.mixer_init_state(cfg, 2))

    def chk(ax, leaf):
        assert isinstance(ax, Axes)
        assert len(ax) == leaf.ndim, (variant, tuple(ax), leaf.shape)
        assert tuple(ax)[:2] == ("batch", "q_heads")

    # tree.map raises if the declared tree's structure drifts from the
    # init-state tree — the exact failure mode that broke hla3_paper
    jax.tree.map(chk, axes, state, is_leaf=is_axes)


def test_hla3_paper_prefill_decode_state_consistency(rng):
    """hla3_paper decode now runs in chunk-state space: prefill-then-step
    must continue the same stream a pure chunkwise pass produces (this was
    a tree-structure crash before the registration fix)."""
    cfg = _cfg("hla3_paper", decay="none")
    params = _params(cfg)
    toks = jnp.asarray(rng.randint(0, cfg.vocab, (1, 9)))
    # one-shot prefill over all 9 == prefill 6 then 3 decode steps
    lg_full, st_full = lm.lm_prefill(params, toks, cfg)
    _, st = lm.lm_prefill(params, toks[:, :6], cfg)
    for j in range(6, 9):
        lg, st, _ = lm.lm_apply(
            params, toks[:, j:j + 1], cfg, states=st,
            positions=jnp.asarray([[j]]), mode="decode",
        )
    np.testing.assert_allclose(
        np.asarray(lg[:, -1], np.float32), np.asarray(lg_full, np.float32),
        atol=1e-4, rtol=1e-3,
    )
    for a, b in zip(jax.tree.leaves(st_full), jax.tree.leaves(st)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1e-4, rtol=1e-3,
        )


# --------------------------------------------------------------------------
# nucleus sampling
# --------------------------------------------------------------------------


def test_top_p_sampling_nucleus(rng):
    from repro.serving import probs, sample

    logits = jnp.asarray(rng.randn(4, 32) * 2, jnp.float32)
    key = jax.random.key(0)
    p = probs(logits, SamplingConfig(method="top_p", top_p=0.5))
    pn = np.asarray(p)
    np.testing.assert_allclose(pn.sum(-1), 1.0, atol=1e-5)
    full = np.asarray(probs(logits, SamplingConfig(method="temperature")))
    for row_p, row_f in zip(pn, full):
        kept = row_p > 0
        # the nucleus is a top-probability prefix with mass >= top_p
        assert row_f[kept].min() >= row_f[~kept].max()
        assert row_f[kept].sum() >= 0.5
        # and it is minimal: dropping its least-likely member goes below
        assert row_f[kept].sum() - row_f[kept].min() < 0.5
    # drawn tokens stay inside the nucleus
    toks = np.asarray(sample(logits, key, SamplingConfig(method="top_p",
                                                         top_p=0.5)))
    for i, t in enumerate(toks):
        assert pn[i, t] > 0
    # degenerate p -> argmax-only nucleus
    t1 = sample(logits, key, SamplingConfig(method="top_p", top_p=1e-9))
    assert (np.asarray(t1) == np.asarray(jnp.argmax(logits, -1))).all()
    with pytest.raises(ValueError):
        sample(logits, key, SamplingConfig(method="top_p", top_p=0.0))


def test_per_request_sampling_override_plain_mode(rng):
    """Per-request SamplingConfig in the plain block path: a greedy
    override inside a temperature-default engine reproduces the solo
    greedy stream."""
    cfg = _cfg("hla2")
    params = _params(cfg)
    pa, pb = rng.randint(2, cfg.vocab, 6), rng.randint(2, cfg.vocab, 6)

    solo = Engine(cfg, params, slots=2, max_len=64, block=4)
    (ra,) = solo.run([GenRequest(rid=0, prompt=pa, max_new=8)])

    eng = Engine(cfg, params, slots=2, max_len=64, block=4,
                 sampling=SamplingConfig(method="temperature",
                                         temperature=0.8))
    res = eng.run([
        GenRequest(rid=0, prompt=pa, max_new=8,
                   sampling=SamplingConfig(method="greedy")),
        GenRequest(rid=1, prompt=pb, max_new=8),
    ])
    assert res[0].tokens == ra.tokens
    assert len(res[1].tokens) == 8

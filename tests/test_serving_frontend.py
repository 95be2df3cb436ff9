"""Serving front-end: prefix/state cache, admission scheduler, async server.

The correctness centerpiece is ``test_cached_prefix_decode_exact``: a
cache-hit admission (resume from an O(1) state snapshot + prefill only
the uncached suffix) must produce token-for-token the same stream as a
cold-start engine, across streaming ops and ragged prefix splits — the
chunkwise carry identity made a serving feature (DESIGN.md §16).
"""

import asyncio
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import lm
from repro.models.param import init_params
from repro.runtime.faults import FaultPlan, FaultSpec
from repro.serving import (
    Engine,
    GenRequest,
    PrefixCache,
    SamplingConfig,
    Scheduler,
    SchedulerConfig,
    StatePool,
    state_bytes_for,
)
from repro.serving.cache import rolling_hashes, tree_bytes, tree_digest
from repro.serving.state_pool import tree_finite
from repro.serving.server import AsyncServer, collect


def _params(cfg, seed=0):
    return init_params(lm.lm_specs(cfg), jax.random.key(seed))


def _tree(nbytes, seed=0):
    """A fake host state snapshot of exactly ``nbytes`` bytes."""
    rng = np.random.RandomState(seed)
    return {"s": rng.randn(nbytes // 4).astype(np.float32)}


def _confirmed(cache, toks, **kw):
    """``lookup`` settled by ``confirm`` with the host state's digest (the
    engine digests on the device), falling through past corrupt entries."""
    while (hit := cache.lookup(toks, **kw)) is not None:
        if cache.confirm(hit, tree_digest(hit.state)):
            return hit
    return None


def _req(rid, **kw):
    """A scheduler-facing request stub (no prompt needed)."""
    kw.setdefault("deadline_s", None)
    kw.setdefault("priority", 1)
    kw.setdefault("tenant", "default")
    return types.SimpleNamespace(rid=rid, **kw)


class _Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------------------
# cache: keying, lookup, eviction, integrity
# --------------------------------------------------------------------------


def test_rolling_hash_prefix_consistency(rng):
    toks = rng.randint(0, 1000, 64)
    one_pass = rolling_hashes(toks, [8, 24, 64])
    for n, h in zip([8, 24, 64], one_pass):
        assert rolling_hashes(toks[:n], [n]) == [h]
    # content-sensitive: flipping one token inside the prefix changes it
    mut = toks.copy()
    mut[3] += 1
    assert rolling_hashes(mut, [8]) != rolling_hashes(toks, [8])


def test_cache_longest_prefix_lookup(rng):
    cache = PrefixCache(granularity=4, budget_bytes=1 << 20)
    toks = rng.randint(0, 100, 16)
    assert cache.lookup(toks) is None  # empty cache: miss
    cache.insert(toks[:4], _tree(64, 1))
    cache.insert(toks[:12], _tree(64, 2))
    hit = _confirmed(cache, toks)
    assert hit.prefix_len == 12 and hit.state["s"][0] == _tree(64, 2)["s"][0]
    # max_prefix caps the usable length (engine passes len(prompt) - 1)
    assert _confirmed(cache, toks, max_prefix=11).prefix_len == 4
    # a prompt diverging at token 5 only matches the 4-prefix
    other = toks.copy()
    other[5] += 1
    assert _confirmed(cache, other).prefix_len == 4
    assert cache.stats()["hits"] == 3


def test_cache_insert_rejects_misaligned_and_oversize():
    cache = PrefixCache(granularity=4, budget_bytes=256)
    assert not cache.insert(np.arange(6), _tree(64))  # 6 % 4 != 0
    assert not cache.insert(np.arange(4), _tree(512))  # > whole budget
    assert len(cache) == 0 and cache.bytes == 0


def test_cache_hash_collision_never_returns_wrong_state(rng):
    """A (length, hash) collision must be caught by the stored-token
    comparison — wrong tokens are a correctness bug, a miss is not."""
    cache = PrefixCache(granularity=4, budget_bytes=1 << 20)
    a = rng.randint(0, 100, 4)
    b = (a + 1) % 100
    cache.insert(a, _tree(64, 1))
    entry = next(iter(cache._entries.values()))
    # forge a collision: register a's entry under b's key as well
    forged_key = (4, (rolling_hashes(b, [4])[0] + cache._ns_seed())
                  % ((1 << 61) - 1))
    cache._entries[forged_key] = entry
    cache._lengths[4] += 1
    assert cache.lookup(b) is None  # token guard rejects the forgery
    assert cache.lookup(a).prefix_len == 4


def test_cache_eviction_respects_byte_budget():
    cache = PrefixCache(granularity=4, budget_bytes=200)
    for i in range(4):  # 80 bytes each: the 4th insert must evict
        cache.insert(np.arange(i * 4, i * 4 + 4), _tree(80, i))
    assert cache.bytes <= 200
    assert len(cache) == 2
    assert cache.stats()["evicted_bytes"] == 160.0
    # LRU: entries 0 and 1 went first; 2 and 3 survive
    assert cache.lookup(np.arange(0, 4)) is None
    assert cache.lookup(np.arange(8, 12)) is not None
    # a lookup refreshes recency: entry 2 now outlives a newer insert
    cache.insert(np.arange(100, 104), _tree(80, 9))
    assert cache.lookup(np.arange(8, 12)) is not None
    assert cache.lookup(np.arange(12, 16)) is None  # 3 was LRU, evicted


def test_cache_namespace_scopes_keys(rng):
    toks = rng.randint(0, 100, 4)
    a = PrefixCache(granularity=4, namespace="model-a")
    b = PrefixCache(granularity=4, namespace="model-b")
    a.insert(toks, _tree(64))
    assert a.lookup(toks) is not None
    assert b.lookup(toks) is None
    # same content, same namespace -> same key (cross-tenant sharing)
    a2 = PrefixCache(granularity=4, namespace="model-a")
    a2.insert(toks, _tree(64))
    assert next(iter(a2._entries)) == next(iter(a._entries))


def test_cache_checksum_drops_corrupt_entry(rng):
    """Injected corruption (``cache.corrupt``) and real bit rot both fail
    ``confirm`` against the stored digest: the entry is dropped and
    counted, the lookup degrades to a miss (cold prefill), never to
    wrong state."""
    plan = FaultPlan(FaultSpec(point="cache.corrupt", at=0))
    cache = PrefixCache(granularity=4, budget_bytes=1 << 20, faults=plan)
    toks = rng.randint(0, 100, 8)
    cache.insert(toks, _tree(64))
    # the lookup returns the hit unread; the digest of the state as it
    # was served settles it
    hit = cache.lookup(toks)  # corrupted on first probe
    assert plan.fired["cache.corrupt"] == 1
    assert not cache.confirm(hit, tree_digest(hit.state))
    assert len(cache) == 0
    assert cache.lookup(toks) is None
    assert cache.stats()["hits"] == 0
    assert cache.obs.registry.get(
        "cache_corrupt_dropped_total").total() == 1

    # organic corruption: mutate a leaf behind the cache's back
    cache2 = PrefixCache(granularity=4, budget_bytes=1 << 20)
    cache2.insert(toks, _tree(64))
    next(iter(cache2._entries.values())).state["s"][0] += 1.0
    assert _confirmed(cache2, toks) is None
    assert len(cache2) == 0
    assert cache2.obs.registry.get(
        "cache_corrupt_dropped_total").total() == 1


def _flip(leaf, word, bit):
    """A copy of ``leaf`` with one bit of its ``word``-th item flipped."""
    out = np.array(leaf)
    view = out.view(np.dtype(f"uint{8 * out.dtype.itemsize}")).reshape(-1)
    view[word] ^= view.dtype.type(1 << bit)
    return out


def _digest_tree(rng):
    return {
        "f32": rng.randn(3, 5, 7).astype(np.float32),
        "bf16": np.asarray(rng.randn(4, 9), dtype=jnp.bfloat16),
        "i32": rng.randint(-1000, 1000, (2, 11)).astype(np.int32),
        "more": [rng.randn(6).astype(np.float32),
                 np.asarray(rng.randn(2, 3), dtype=jnp.bfloat16)],
    }


@pytest.mark.parametrize("leaf", ["f32", "bf16", "i32"])
def test_tree_digest_sees_every_single_bit_flip(leaf, rng):
    """Flipping any one bit of the first, a middle or the last word of a
    leaf changes both lanes of the digest."""
    tree = _digest_tree(rng)
    base = np.asarray(tree_digest(tree))
    assert base.dtype == np.uint32 and base.shape == (2,)
    arr = tree[leaf]
    bits = 8 * arr.dtype.itemsize
    for word in (0, arr.size // 2, arr.size - 1):
        for bit in sorted({0, 1, bits // 2 - 1, bits // 2, bits - 2,
                           bits - 1}):
            flipped = dict(tree, **{leaf: _flip(arr, word, bit)})
            got = np.asarray(tree_digest(flipped))
            assert (got != base).all(), (leaf, word, bit)
    # a nested leaf's flips count too, and the leaf's index is mixed in:
    # the same words at another place in the tree read differently
    nested = dict(tree, more=[_flip(tree["more"][0], 5, 31),
                              tree["more"][1]])
    assert (np.asarray(tree_digest(nested)) != base).all()
    swapped = dict(tree, more=[tree["more"][0][::-1].copy(),
                               tree["more"][1]])
    assert (np.asarray(tree_digest(swapped)) != base).any()


@pytest.mark.parametrize("bit", [29, 30, 31])
def test_tree_digest_sees_paired_flips(bit, rng):
    """Flipping the same high bit of two float32 words — in one leaf, or
    across two leaves — changes both lanes: the per-word hash is not
    linear, so such pairs do not cancel (flipping bit 31 adds 2^31 to
    any odd multiple of the word)."""
    tree = _digest_tree(rng)
    base = np.asarray(tree_digest(tree))
    f32, more = tree["f32"], tree["more"][0]
    pairs = {
        "one leaf": dict(tree, f32=_flip(_flip(f32, 0, bit), 1, bit)),
        "far apart": dict(tree, f32=_flip(_flip(f32, 3, bit),
                                          f32.size - 1, bit)),
        "two leaves": dict(tree, f32=_flip(f32, 7, bit),
                           more=[_flip(more, 2, bit), tree["more"][1]]),
        "bits 30/31 across leaves": dict(
            tree, f32=_flip(f32, 7, 30),
            more=[_flip(more, 2, 31), tree["more"][1]]),
    }
    for name, flipped in pairs.items():
        assert (np.asarray(tree_digest(flipped)) != base).all(), name


def test_tree_digest_refuses_wide_and_bool_leaves():
    """A program without x64 would narrow an 8-byte host leaf before the
    digest read it, so 8-byte and bool leaves are refused, not
    half-read."""
    for leaf in (np.zeros(3, np.float64), np.zeros(3, np.int64),
                 np.zeros(3, bool)):
        with pytest.raises(TypeError):
            tree_digest({"s": leaf})


def test_tree_digest_host_equals_device_program(rng):
    """The digest of a numpy tree is the digest a jitted program computes
    of the same arrays on the device, alone or fused with other work."""
    tree = _digest_tree(rng)
    host = np.asarray(tree_digest(tree))
    dev = jax.device_put(tree)
    alone = jax.jit(lambda t: tree_digest(t))(dev)
    fused = jax.jit(lambda t: (tree_finite(t), tree_digest(t),
                               jax.tree.map(lambda x: x + 1, t)))(dev)
    np.testing.assert_array_equal(np.asarray(alone), host)
    np.testing.assert_array_equal(np.asarray(fused[1]), host)
    assert bool(fused[0])
    # and of a real state tree: what the engine digests is what a host
    # snapshot of it digests
    cfg = get_config("hla-1b", reduced=True)
    states = jax.tree.map(
        lambda x: jax.random.normal(jax.random.key(3), x.shape).astype(
            x.dtype) if jnp.issubdtype(x.dtype, jnp.inexact) else x,
        lm.lm_init_states(cfg, 1, 32))
    np.testing.assert_array_equal(
        np.asarray(jax.jit(tree_digest)(states)),
        np.asarray(tree_digest(jax.device_get(states))))


def test_state_bytes_budget_sizing():
    cfg = get_config("hla-1b", reduced=True)
    per_entry = state_bytes_for(cfg)
    assert per_entry > 0
    # the analytic size should be in the ballpark of a real host snapshot
    snap = jax.device_get(lm.lm_init_states(cfg, 1, 32))
    actual = tree_bytes(snap)
    assert 0.1 * actual <= per_entry <= 10 * actual


# --------------------------------------------------------------------------
# scheduler: priority, fairness, expiry, autoscaling
# --------------------------------------------------------------------------


def test_scheduler_fifo_within_class():
    clk = _Clock()
    s = Scheduler(SchedulerConfig(), clock=clk)
    for i in range(3):
        s.submit(_req(i))
    assert [s.pop().rid for _ in range(3)] == [0, 1, 2]
    assert s.pop() is None
    assert s.obs.registry.get("sched_promotions_total").total() == 0


def test_scheduler_priority_classes_and_promotion():
    clk = _Clock()
    s = Scheduler(SchedulerConfig(), clock=clk)
    s.submit(_req(0, priority=2))
    s.submit(_req(1, priority=0))
    s.submit(_req(2, priority=1))
    assert [s.pop().rid for _ in range(3)] == [1, 2, 0]
    # rids 1 and 2 both jumped rid 0 (the oldest live arrival)
    assert s.obs.registry.get("sched_promotions_total").total() == 2
    promos = s.obs.events("sched.promote")
    assert [e["rid"] for e in promos] == [1, 2]


def test_scheduler_deadline_slack_orders_within_class():
    clk = _Clock()
    s = Scheduler(SchedulerConfig(), clock=clk)
    s.submit(_req(0))  # no deadline: ranks last in its class
    s.submit(_req(1, deadline_s=5.0))
    s.submit(_req(2, deadline_s=1.0))
    assert [s.pop().rid for _ in range(3)] == [2, 1, 0]


def test_scheduler_tenant_fair_share():
    clk = _Clock()
    s = Scheduler(SchedulerConfig(), clock=clk)
    for i in range(3):
        s.submit(_req(i, tenant="chatty"))
    s.submit(_req(3, tenant="quiet"))
    first = s.pop()  # arrival order: chatty's first request
    assert first.rid == 0
    # chatty now holds a slot -> quiet's head outranks chatty's
    second = s.pop()
    assert second.rid == 3
    s.release(first)
    s.release(second)
    assert [s.pop().rid for _ in range(2)] == [1, 2]


def test_scheduler_expiry_and_cancel():
    clk = _Clock()
    s = Scheduler(SchedulerConfig(), clock=clk)
    s.submit(_req(0, deadline_s=1.0))
    s.submit(_req(1, deadline_s=10.0))
    s.submit(_req(2))
    assert s.expire() == []  # nothing passed yet
    clk.t = 2.0
    expired = s.expire()
    assert [r.rid for r in expired] == [0]
    assert len(s) == 2
    assert s.cancel(1).rid == 1
    assert s.cancel(1) is None  # idempotent
    assert s.pop().rid == 2
    assert len(s) == 0
    # cancelled/popped entries never resurface through expire
    clk.t = 20.0
    assert s.expire() == []
    assert s.obs.registry.get("sched_expired_total").total() == 1


def test_scheduler_autoscaler_hysteresis():
    clk = _Clock()
    cfg = SchedulerConfig(min_slots=1, max_slots=4, scale_down_ticks=3,
                          quarantine_cap=2)
    s = Scheduler(cfg, clock=clk)
    assert s.target_slots() == 1  # idle: stays at min
    for i in range(8):
        s.submit(_req(i))
    assert s.target_slots() == 4  # queue pressure: immediate scale-up
    for i in range(8):
        s.pop()
    # empty queue: needs scale_down_ticks consecutive idle ticks per step
    assert s.target_slots() == 4
    assert s.target_slots() == 4
    assert s.target_slots() == 3  # 3rd idle tick
    s.submit(_req(99))
    assert s.target_slots() == 4  # burst: back up immediately
    s.pop()
    # quarantine pressure clamps to min_slots regardless of history
    s.note_quarantine(2)
    assert s.target_slots() == 1


def test_scheduler_stall_fault_point():
    plan = FaultPlan(FaultSpec(point="sched.stall", at=1))
    s = Scheduler(SchedulerConfig(), faults=plan)
    assert not s.stalled()  # hit 0: not scheduled
    assert s.stalled()      # hit 1: fires
    assert not s.stalled()
    assert s.obs.registry.get("sched_stall_ticks_total").total() == 1


def test_scheduler_config_validation():
    with pytest.raises(ValueError, match="min_slots"):
        SchedulerConfig(min_slots=3, max_slots=2)
    with pytest.raises(ValueError, match="scale_down_ticks"):
        SchedulerConfig(scale_down_ticks=0)
    s = Scheduler(SchedulerConfig())
    s.submit(_req(7))
    with pytest.raises(ValueError, match="already queued"):
        s.submit(_req(7))


# --------------------------------------------------------------------------
# engine + cache: cached-prefix decode is EXACT
# --------------------------------------------------------------------------


@pytest.mark.parametrize("mixer", ["hla2", "gla", "rwkv6"])
def test_cached_prefix_decode_exact(mixer, rng):
    """Cache-hit decode == cold-start decode, token for token, across
    ragged prefix lengths and chunk-boundary/mid-chunk splits."""
    cfg = get_config("hla-1b", reduced=True, mixer=mixer)
    params = _params(cfg)
    prefix = rng.randint(2, cfg.vocab, 12)

    def prompts():
        out = []
        # suffix lengths 1/2/4 put the resume point at the cached
        # boundary (L=13: suffix of one token), mid-chunk (L=14), and
        # exactly on a granularity multiple (L=16)
        for i, sfx in enumerate([1, 2, 4]):
            out.append(np.concatenate(
                [prefix, rng.randint(2, cfg.vocab, sfx)]))
        # long prompt: hit at 12, then a carry to the NEXT boundary (20)
        # that inserts a new entry before the suffix prefill
        out.append(np.concatenate([prefix,
                                   rng.randint(2, cfg.vocab, 9)]))
        # short prompt (< granularity): stays on the pure cold path
        out.append(rng.randint(2, cfg.vocab, 3))
        return out

    ps = prompts()
    reqs = lambda: [GenRequest(rid=i, prompt=p, max_new=6)  # noqa: E731
                    for i, p in enumerate(ps)]

    cold = Engine(cfg, params, slots=1, max_len=64, block=4, seed=0)
    ref = cold.run(reqs())

    cache = PrefixCache(granularity=4, budget_bytes=1 << 26)
    warm = Engine(cfg, params, slots=1, max_len=64, block=4, seed=0,
                  cache=cache)
    got = warm.run(reqs())

    for r_ref, r_got in zip(ref, got):
        assert r_got.status == "ok"
        assert r_got.tokens == r_ref.tokens, (
            f"{mixer}: cached-prefix stream diverged for rid "
            f"{r_got.rid}: {r_got.tokens} != {r_ref.tokens}"
        )
    st = cache.stats()
    assert st["hits"] >= 3  # rids 1..3 all resume from rid 0's prefix
    admitted = warm.obs.events("request.admitted")
    hits = {e["rid"]: e["cached_prefix"] for e in admitted}
    assert hits[0] == 0 and hits[4] == 0  # cold + short prompt
    assert hits[1] == 12 and hits[2] == 12 and hits[3] == 12


def test_cache_corrupt_falls_back_to_cold_prefill(rng):
    """``cache.corrupt`` on a hit: the entry is dropped and admission
    degrades to cold prefill with an identical stream."""
    cfg = get_config("hla-1b", reduced=True)
    params = _params(cfg)
    prompt = rng.randint(2, cfg.vocab, 13)
    plan = FaultPlan(FaultSpec(point="cache.corrupt", at=0))
    cache = PrefixCache(granularity=4, budget_bytes=1 << 26)
    eng = Engine(cfg, params, slots=1, max_len=64, block=4, seed=0,
                 cache=cache, faults=plan)
    (r0,) = eng.run([GenRequest(rid=0, prompt=prompt, max_new=6)])
    # r1's lookup returns rid 0's entry -> corruption fires -> checksum
    # drops it -> cold prefill (which re-inserts the boundary state)
    (r1,) = eng.run([GenRequest(rid=1, prompt=prompt, max_new=6)])
    (r2,) = eng.run([GenRequest(rid=2, prompt=prompt, max_new=6)])
    assert r1.tokens == r0.tokens == r2.tokens
    assert plan.fired["cache.corrupt"] == 1
    reg = eng.obs.registry
    assert reg.get("cache_corrupt_dropped_total").total() == 1
    assert reg.get("cache_hits_total").total() == 1  # only r2 hits


def test_cache_insertion_gated_on_finite_state(rng):
    """A NaN-poisoned admission must never become a cache entry."""
    cfg = get_config("hla-1b", reduced=True)
    params = jax.tree.map(
        lambda x: jnp.full_like(x, jnp.nan)
        if jnp.issubdtype(x.dtype, jnp.inexact) else x,
        _params(cfg),
    )
    cache = PrefixCache(granularity=4, budget_bytes=1 << 26)
    eng = Engine(cfg, params, slots=1, max_len=64, block=4, cache=cache)
    (r,) = eng.run([GenRequest(rid=0, prompt=rng.randint(2, cfg.vocab, 13),
                               max_new=4)])
    assert r.status == "error"
    assert len(cache) == 0


def test_cache_corrupt_carry_hit_dropped_and_retried(rng):
    """A corrupt hit on the hit-then-carry path (``_carry_from``): the
    device's digest of the uploaded snapshot fails ``confirm``, the entry
    is dropped, the carry it produced is never inserted, and the redone
    admission streams what a cold engine does.  Nothing of the discarded
    attempt reaches the outside: no token, no stream hook call, no TTFT
    observation, no ``request.admitted`` event."""
    cfg = get_config("hla-1b", reduced=True)
    params = _params(cfg)
    prefix = rng.randint(2, cfg.vocab, 13)
    # 21 tokens: hit at 12, carry to the boundary at 20, suffix of one
    longer = np.concatenate([prefix[:12], rng.randint(2, cfg.vocab, 9)])
    reqs = [GenRequest(rid=0, prompt=prefix, max_new=6),
            GenRequest(rid=1, prompt=longer, max_new=6)]
    ref = Engine(cfg, params, slots=1, max_len=64, block=4).run(reqs)
    # the boundary state a clean cold admission of ``longer`` inserts
    clean = PrefixCache(granularity=4, budget_bytes=1 << 26)
    Engine(cfg, params, slots=1, max_len=64, block=4, cache=clean).run(
        [GenRequest(rid=1, prompt=longer, max_new=6)])
    (want,) = clean._entries.values()

    plan = FaultPlan(FaultSpec(point="cache.corrupt", at=0))
    cache = PrefixCache(granularity=4, budget_bytes=1 << 26)
    eng = Engine(cfg, params, slots=1, max_len=64, block=4, cache=cache,
                 faults=plan)
    inserted = []
    real_insert = cache.insert

    def spy(tokens, state, digest=None, **kw):
        inserted.append((len(tokens), np.asarray(digest)))
        return real_insert(tokens, state, digest, **kw)

    cache.insert = spy
    streamed = {0: [], 1: []}
    eng.on_stream = lambda rid, toks, res: streamed[rid].extend(toks)
    got = [eng.run([r])[0] for r in reqs]
    assert [r.tokens for r in got] == [r.tokens for r in ref]
    assert all(r.status == "ok" for r in got)
    assert plan.fired["cache.corrupt"] == 1
    reg = eng.obs.registry
    assert reg.get("cache_corrupt_dropped_total").total() == 1
    assert reg.get("cache_hits_total").total() == 0
    # rid 0 inserted the 12-boundary; rid 1's only insert is the redo's
    # cold carry to 20, bit for bit what a clean cold admission inserts
    assert [n for n, _ in inserted] == [12, 20]
    np.testing.assert_array_equal(inserted[1][1], want.digest)
    assert len(cache) == 1
    admitted = eng.obs.events("request.admitted")
    assert [(e["rid"], e["cached_prefix"]) for e in admitted] == [
        (0, 0), (1, 0)]
    assert streamed == {r.rid: r.tokens for r in got}
    assert reg.get("serving_ttft_seconds").count() == 2


def test_cache_corrupt_retry_keeps_sampling_key(rng):
    """Under a sampling law, a corrupt-and-retried admission reuses its
    subkey: it streams the same tokens as an uncorrupted engine with the
    same seed, whose admission resumed from the clean snapshot."""
    cfg = get_config("hla-1b", reduced=True)
    params = _params(cfg)
    prompt = rng.randint(2, cfg.vocab, 13)
    scfg = SamplingConfig("temperature", temperature=1.0)

    def serve(faults):
        eng = Engine(cfg, params, slots=1, max_len=64, block=4, seed=7,
                     sampling=scfg, faults=faults,
                     cache=PrefixCache(granularity=4, budget_bytes=1 << 26))
        out = [eng.run([GenRequest(rid=i, prompt=prompt, max_new=8)])[0]
               for i in range(2)]
        return eng.obs.registry, out

    plan = FaultPlan(FaultSpec(point="cache.corrupt", at=0))
    reg, got = serve(plan)
    clean_reg, want = serve(None)
    assert plan.fired["cache.corrupt"] == 1
    assert reg.get("cache_corrupt_dropped_total").total() == 1
    assert clean_reg.get("cache_hits_total").total() == 1
    assert all(r.status == "ok" for r in got)
    assert [r.tokens for r in got] == [r.tokens for r in want]


# --------------------------------------------------------------------------
# engine + scheduler: expiry, priority, cancellation
# --------------------------------------------------------------------------


def test_expired_queued_request_never_spends_a_prefill(rng):
    """Starvation regression: a queued request whose deadline passes is
    finalized as ``timeout`` on the next drive tick — while the only
    slot is still busy — and no slot is ever spent prefilling it."""
    cfg = get_config("hla-1b", reduced=True)
    plan = FaultPlan(  # every decode block sleeps 30ms
        FaultSpec(point="engine.slow_block", at=0, times=None, arg=0.03))
    eng = Engine(cfg, _params(cfg), slots=1, max_len=64, block=4,
                 faults=plan)
    admitted = []
    real_admit = eng.admit
    eng.admit = lambda s, r: (admitted.append(r.rid), real_admit(s, r))[1]
    terminal = []
    eng.on_stream = lambda rid, toks, res: (
        terminal.append(rid) if res is not None else None)
    # the long request outranks the doomed one by priority class —
    # otherwise deadline-slack ordering would (correctly) admit the
    # urgent request first and nothing would starve
    long = GenRequest(rid=0, prompt=rng.randint(2, cfg.vocab, 8),
                      max_new=24, priority=0)
    doomed = GenRequest(rid=1, prompt=rng.randint(2, cfg.vocab, 8),
                        max_new=4, deadline_s=0.05)
    r0, r1 = eng.run([long, doomed])
    assert r0.status == "ok" and len(r0.tokens) == 24
    assert r1.status == "timeout" and r1.tokens == []
    assert admitted == [0]  # the doomed request never touched a slot
    assert terminal[0] == 1  # ...and learned its fate before rid 0 ended
    assert eng.obs.registry.get("sched_expired_total").total() == 1


def test_priority_reorders_single_slot_admissions(rng):
    cfg = get_config("hla-1b", reduced=True)
    eng = Engine(cfg, _params(cfg), slots=1, max_len=64, block=4)
    terminal = []
    eng.on_stream = lambda rid, toks, res: (
        terminal.append(rid) if res is not None else None)
    low = GenRequest(rid=0, prompt=rng.randint(2, cfg.vocab, 6),
                     max_new=4, priority=2)
    high = GenRequest(rid=1, prompt=rng.randint(2, cfg.vocab, 6),
                      max_new=4, priority=0)
    r_low, r_high = eng.run([low, high])
    assert r_low.status == r_high.status == "ok"
    assert terminal == [1, 0]  # high drained first despite arrival order
    assert eng.obs.registry.get("sched_promotions_total").total() == 1


def test_cancel_queued_request_finalizes_immediately(rng):
    cfg = get_config("hla-1b", reduced=True)
    eng = Engine(cfg, _params(cfg), slots=1, max_len=64, block=4)
    eng.submit(GenRequest(rid=5, prompt=rng.randint(2, cfg.vocab, 6),
                          max_new=4))
    assert eng.cancel(5)
    assert eng.results[5].status == "cancelled"
    assert len(eng.scheduler) == 0
    assert not eng.cancel(5)  # already terminal


# --------------------------------------------------------------------------
# host snapshots
# --------------------------------------------------------------------------


def test_host_snapshot_roundtrip():
    cfg = get_config("hla-1b", reduced=True)
    pool = StatePool(lambda n: lm.lm_init_states(cfg, n, 32), slots=2)
    vals = jax.tree.map(
        lambda x: (jnp.arange(x.size, dtype=jnp.float32)
                   .reshape(x.shape).astype(x.dtype)
                   if jnp.issubdtype(x.dtype, jnp.inexact) else x),
        pool.empty_slot_state(),
    )
    pool.write_slot(1, vals)
    snap = pool.snapshot_slot(1, host=True)
    assert all(isinstance(leaf, np.ndarray)
               for leaf in jax.tree.leaves(snap))
    before = np.asarray(tree_digest(snap))
    pool.reset_slot(1)
    pool.restore_slot(1, snap)
    restored = jax.device_get(pool.read_slot(1))
    np.testing.assert_array_equal(np.asarray(tree_digest(restored)), before)
    for a, b in zip(jax.tree.leaves(snap), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------------------------------
# async streaming server
# --------------------------------------------------------------------------


def test_async_server_streams_match_results(rng):
    cfg = get_config("hla-1b", reduced=True)
    eng = Engine(cfg, _params(cfg), slots=2, max_len=64, block=4)
    reqs = [GenRequest(rid=i, prompt=rng.randint(2, cfg.vocab, 6),
                       max_new=5) for i in range(3)]

    async def main():
        async with AsyncServer(eng) as srv:
            outs = await asyncio.gather(*[collect(srv, r) for r in reqs])
        return outs

    outs = asyncio.run(main())
    for req, (toks, res) in zip(reqs, outs):
        assert res.status == "ok"
        assert toks == res.tokens == eng.results[req.rid].tokens
        assert len(toks) == 5
    reg = eng.obs.registry
    assert reg.get("server_streams_total").total() == 3
    assert reg.get("server_stream_tokens_total").total() == 15
    assert eng.on_stream is None  # drain uninstalled the hook


def test_async_server_drain_refuses_new_streams(rng):
    cfg = get_config("hla-1b", reduced=True)
    eng = Engine(cfg, _params(cfg), slots=1, max_len=64, block=4)

    async def main():
        srv = AsyncServer(eng)
        async with srv:
            toks, res = await collect(
                srv, GenRequest(rid=0, prompt=rng.randint(2, cfg.vocab, 6),
                                max_new=4))
            assert res.status == "ok" and len(toks) == 4
        with pytest.raises(RuntimeError, match="draining"):
            await srv.generate(
                GenRequest(rid=1, prompt=rng.randint(2, cfg.vocab, 6),
                           max_new=4)).__anext__()

    asyncio.run(main())


def test_async_server_backpressure_pauses_drive_loop(rng):
    """A slow consumer must throttle generation: with a tiny buffered-
    token watermark the drive loop pauses instead of growing queues."""
    cfg = get_config("hla-1b", reduced=True)
    eng = Engine(cfg, _params(cfg), slots=1, max_len=64, block=4)
    req = GenRequest(rid=0, prompt=rng.randint(2, cfg.vocab, 6),
                     max_new=12)

    async def main():
        async with AsyncServer(eng, max_buffered_tokens=2) as srv:
            toks = []
            async for t in srv.generate(req):
                toks.append(t)
                await asyncio.sleep(0.005)  # slow reader
            return toks

    toks = asyncio.run(main())
    assert len(toks) == 12
    assert eng.obs.registry.get(
        "server_backpressure_waits_total").total() >= 1

"""Hybrid stacks: a repeating layer pattern of HLA2 and softmax layers
(Olmo Hybrid's 3:1), HLA2 at key width != value width, per-slot KV caches
beside O(1) HLA2 state in one serving Engine.

The reference comparisons of this stack live with the benchmark
(``bench/tests/test_hybrid.py``); these tests hold the program's own
pieces: the pattern, the widths, the per-row cache, the Engine's
admission rules and its KV counters."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import attention, lm, seq_op
from repro.models.param import abstract_params, init_params
from repro.serving import (
    Engine, GenRequest, PrefixCache, SamplingConfig, SpecConfig)


@pytest.fixture(scope="module")
def olmo():
    cfg = get_config("olmo-hybrid-7b", reduced=True)
    return cfg, init_params(lm.lm_specs(cfg), jax.random.key(0))


def test_olmo_hybrid_pattern_and_widths():
    cfg = get_config("olmo-hybrid-7b")
    assert cfg.layer_pattern == ("mixer", "mixer", "mixer", "attn")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.head_dim) == (
        32, 3840, 30, 128)
    assert cfg.hla_widths() == (96, 192)
    assert [op.name for op in lm.stack_ops(cfg)] == ["hla2", "attn"]
    assert lm.holds_kv(cfg) and not lm.holds_kv(get_config("hla-1b"))
    specs = lm.lm_specs(cfg.replace(n_layers=8))
    mix = specs["groups"]["pos0"]["mixer"]
    assert mix["wq"]["kernel"].shape == (2, 3840, 30 * 96)
    assert mix["wv"]["kernel"].shape == (2, 3840, 30 * 192)
    assert mix["wo"]["kernel"].shape == (2, 30 * 192, 3840)
    assert specs["groups"]["pos3"]["attn"]["wk"]["kernel"].shape == (
        2, 3840, 30 * 128)


def test_hla_widths_default_to_the_head_width():
    """A configuration that names no HLA widths builds as before."""
    cfg = get_config("hla-1b")
    assert cfg.hla_widths() == (cfg.head_dim, cfg.head_dim)
    st = jax.eval_shape(lambda: lm.lm_init_states(cfg, 2, 8))
    assert {a.shape[-1] for a in jax.tree.leaves(st)} == {cfg.head_dim}


def test_layer_pattern_must_tile_the_stack():
    cfg = get_config("olmo-hybrid-7b", reduced=True).replace(n_layers=6)
    with pytest.raises(ValueError, match="whole periods"):
        lm.lm_specs(cfg)


def _tree_digest(cfg):
    tree = abstract_params(lm.lm_specs(cfg))
    rows = sorted(f"{jax.tree_util.keystr(p)} {a.shape} {a.dtype}"
                  for p, a in jax.tree_util.tree_leaves_with_path(tree))
    return tree, hashlib.sha256("\n".join(rows).encode()).hexdigest()[:16]


@pytest.mark.parametrize("reduced,digest", [
    (True, "0d1bc1e2ec7e3623"), (False, "712f32ba58580c14")])
def test_jamba_pattern_builds_the_same_parameter_tree(reduced, digest):
    """Jamba's 8-layer period (attention at index 4, Mamba elsewhere, MoE
    on every second layer) as a ``layer_pattern``: the parameter tree is
    the one the former ``group_size=8, attn_index=4`` built, leaf for
    leaf (the digest of its sorted paths, shapes and dtypes, taken from
    the former code)."""
    cfg = get_config("jamba-1.5-large-398b", reduced=reduced)
    tree, got = _tree_digest(cfg)
    assert got == digest
    groups = tree["groups"]
    assert "attn" in groups["pos4"] and "mamba" not in groups["pos4"]
    for i in (0, 1, 2, 3, 5, 6, 7):
        assert "mamba" in groups[f"pos{i}"]
        assert ("moe" in groups[f"pos{i}"]) is (i % 2 == 1)


def test_decode_attention_masks_each_row_to_its_length():
    """One query per row over a shared-shape cache, rows at lengths 3 and
    9: each row equals causal attention over its own prefix alone."""
    rng = np.random.default_rng(0)
    B, H, L, dh = 2, 2, 12, 8
    k, v = (jnp.asarray(rng.standard_normal((B, H, L, dh)), jnp.float32)
            for _ in range(2))
    q = jnp.asarray(rng.standard_normal((B, H, 1, dh)), jnp.float32)
    lens = jnp.asarray([3, 9], jnp.int32)
    got = attention.decode_attention(q, k, v, lens)
    for b, n in enumerate([3, 9]):
        s = jnp.einsum("hd,hkd->hk", q[b, :, 0], k[b, :, :n]) / np.sqrt(dh)
        want = jnp.einsum("hk,hkd->hd", jax.nn.softmax(s, -1), v[b, :, :n])
        np.testing.assert_allclose(np.asarray(got[b, :, 0]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)
        # the same row through the blocked prefill path, per-row offsets
        flash = attention.flash_attention(
            q, k, v, kv_len=lens, q_offset=lens - 1, kv_block=4)
        np.testing.assert_allclose(np.asarray(flash[b, :, 0]),
                                   np.asarray(want), rtol=1e-5, atol=1e-5)


def test_flash_attention_query_blocks_match_one_pass():
    rng = np.random.default_rng(1)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 2, 37, 8)), jnp.float32)
               for _ in range(3))
    one = attention.flash_attention(q, k, v, kv_block=8)
    blocked = attention.flash_attention(q, k, v, kv_block=8, q_block=16)
    np.testing.assert_allclose(np.asarray(blocked), np.asarray(one),
                               rtol=1e-5, atol=1e-5)


def test_kv_cache_keeps_one_length_per_row():
    cache = attention.init_kv_cache(3, 2, 16, 4, dtype=jnp.float32)
    assert cache.length.shape == (3,)
    assert seq_op.get_op("attn").step_at is not None


def test_decode_in_place_matches_the_full_forward(olmo):
    """Prefill 12 tokens into a 20-position cache, then decode one token
    at a time through the in-place decode scan: each step's logits equal
    the full forward's at that position."""
    cfg, params = olmo
    toks = jax.random.randint(jax.random.key(2), (2, 18), 0, cfg.vocab)
    full, _, _ = lm.lm_apply(params, toks, cfg)
    lg, st = lm.lm_prefill(params, toks[:, :12], cfg,
                           states=lm.lm_init_states(cfg, 2, 20))
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, 11]),
                               rtol=1e-4, atol=1e-4)
    for t in range(12, 18):
        lg, st, _ = lm.lm_apply(params, toks[:, t:t + 1], cfg, states=st,
                                positions=jnp.full((2, 1), t), mode="decode")
        np.testing.assert_allclose(np.asarray(lg[:, 0]),
                                   np.asarray(full[:, t]),
                                   rtol=1e-4, atol=1e-4)
    lengths = st["pos3"].length  # (periods, rows)
    assert lengths.shape == (2, 2) and bool(jnp.all(lengths == 18))


def test_engine_refuses_cache_and_spec_for_the_hybrid_stack(olmo):
    cfg, params = olmo
    Engine(cfg, params, slots=2, max_len=32)
    with pytest.raises(ValueError, match="prefix cache"):
        Engine(cfg, params, slots=2, max_len=32,
               cache=PrefixCache(granularity=8, budget_bytes=1 << 20))
    with pytest.raises(ValueError, match="speculative"):
        Engine(cfg, params, slots=2, max_len=32, spec=SpecConfig(k=2))


def test_kv_counter_and_span_labels_in_an_engine_run(olmo):
    """``serving_kv_tokens_total`` counts the KV positions the decode
    steps attend over active slots; ``engine.decode_block`` carries the
    block's share as ``kv_tokens`` and ``engine.prefill`` the cache length
    it fills as ``kv_len``."""
    cfg, params = olmo
    eng = Engine(cfg, params, slots=2, max_len=48, block=4,
                 sampling=SamplingConfig("greedy"))
    rng = np.random.default_rng(0)
    reqs = [GenRequest(rid=i, prompt=rng.integers(0, cfg.vocab, n,
                                                  dtype=np.int32),
                       max_new=6) for i, n in enumerate((10, 17))]
    res = eng.run(reqs)
    assert all(r.status == "ok" and len(r.tokens) == 6 for r in res)
    pre = eng.obs.events("engine.prefill", kind="span")
    assert sorted(e["kv_len"] for e in pre) == [10, 17]
    blocks = eng.obs.events("engine.decode_block", kind="span")
    # both admitted before the first block: 2 blocks of 4 steps, each
    # slot at position p attending p + 1 positions
    assert [e["slots_active"] for e in blocks] == [2, 2]
    want = sum(p + 1 for n in (10, 17) for p in range(n, n + 8))
    assert sum(e["kv_tokens"] for e in blocks) == want
    assert eng.obs.registry.get("serving_kv_tokens_total").total() == want


def test_streaming_stack_counts_no_kv():
    cfg = get_config("hla-1b", reduced=True)
    params = init_params(lm.lm_specs(cfg), jax.random.key(0))
    eng = Engine(cfg, params, slots=2, max_len=32, block=2,
                 sampling=SamplingConfig("greedy"))
    eng.run([GenRequest(rid=0, prompt=np.arange(5, dtype=np.int32),
                        max_new=3)])
    assert eng.obs.registry.get("serving_kv_tokens_total").total() == 0
    [pre] = eng.obs.events("engine.prefill", kind="span")
    assert pre["kv_len"] == 0

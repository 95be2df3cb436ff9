"""The plain reference against the masked second-order definition, and
against the program at a small size."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import hla2_lm, hla2_lm_ref as ref

HERE = os.path.dirname(os.path.abspath(__file__))


def _naive(q, k, v, g):
    """o_t = sum_{i<=j<=t} g^{(t-i)+(t-j)} (q_t.k_i)(k_i.q_j) v_j."""
    H, n, d = q.shape
    out = np.zeros((H, n, v.shape[-1]))
    for h in range(H):
        for t in range(n):
            for j in range(t + 1):
                w = 0.0
                for i in range(j + 1):
                    w += (g[h] ** ((t - i) + (t - j)) * (q[h, t] @ k[h, i])
                          * (k[h, i] @ q[h, j]))
                out[h, t] += w * v[h, j]
    return out


def test_chunked_hla2_matches_the_definition():
    rng = np.random.default_rng(0)
    H, n, d = 2, 11, 4  # n is not a multiple of the chunk
    q, k, v = (rng.standard_normal((H, n, d)).astype(np.float32) * 0.5
               for _ in range(3))
    g = np.array([0.9, 0.6], np.float32)
    got = ref.hla2(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                   jnp.asarray(g), chunk=4)
    want = _naive(q.astype(np.float64), k.astype(np.float64),
                  v.astype(np.float64), g.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_reference_logits_match_the_program_in_float32():
    from repro.models import lm

    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        c = json.load(f)
    W = ref.make_weights(c, ref.seed_key(2 ** 32 + 9))
    pcfg = hla2_lm.program_config(c)
    params = hla2_lm.to_program(W)
    hla2_lm.check_tree(pcfg, params)
    toks = np.random.default_rng(1).integers(0, c["vocab"], 40)
    pos = np.arange(40)
    with jax.default_matmul_precision("highest"):
        want = ref.logits_at(c, W, jnp.asarray(toks), jnp.asarray(pos))
        got, _, _ = lm.lm_apply(params, jnp.asarray(toks)[None], pcfg)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_weights_repeat_for_a_seed_and_round_trip_the_program_tree():
    with open(os.path.join(HERE, "data", "tiny.json")) as f:
        c = json.load(f)
    a = ref.make_weights(c, ref.seed_key(7))
    b = ref.make_weights(c, ref.seed_key(7))
    d = ref.make_weights(c, ref.seed_key(2 ** 32 + 7))
    assert all(np.array_equal(a[n], b[n]) for n in a)
    assert not np.array_equal(a["wq"], d["wq"])
    back = hla2_lm.from_program(hla2_lm.to_program(a))
    assert set(back) == set(a)
    assert all(back[n] is a[n] for n in a)


def test_fp8_control_rounds_and_passes_gradients_straight():
    x = jnp.linspace(-1.0, 1.0, 101)
    y = ref._round(x, "fp8", weight=True)
    assert 0 < float(jnp.max(jnp.abs(y - x))) < 0.05
    g = jax.grad(lambda z: jnp.sum(ref._round(z, "fp8", weight=False) ** 2))(x)
    np.testing.assert_allclose(np.asarray(g), 2 * np.asarray(y), rtol=1e-6)


def test_e4m3_rounding_matches_the_float8_type():
    x = jnp.concatenate([jnp.linspace(-448.0, 448.0, 4001),
                         jnp.linspace(-0.05, 0.05, 4001)])
    want = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    np.testing.assert_array_equal(np.asarray(ref.e4m3(x)), np.asarray(want))

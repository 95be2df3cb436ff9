"""Gradient correctness of the fused Pallas training path (interpret mode).

Deliverables pinned here:

* the fused VJP — forward checkpoints per-chunk incoming states, backward
  walks the chunk axis in reverse — matches ``jax.grad`` of the O(n^2)
  naive oracles to <= 1e-4 (fp32) across the full {gamma, normalize, lam}
  grid, for both HLA2 and AHLA;
* the fused backward matches the chunk-level jnp oracle in ``kernels.ref``
  (same shared per-chunk math, vmapped instead of gridded);
* arbitrary (non-chunk-multiple) sequence lengths work through the public
  API, values and gradients.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ahla import ahla_naive
from repro.core.hla2 import hla2_naive
from repro.kernels import ref as kref
from repro.kernels.ahla_chunk import ahla_chunk_bwd_pallas, ahla_chunk_pallas
from repro.kernels.hla2_chunk import hla2_chunk_bwd_pallas, hla2_chunk_pallas
from repro.kernels.ops import ahla_attention, hla2_attention

TOL = 1e-4


def _mk(rng, B, H, n, d, dv):
    q = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, n, dv) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.85, 0.99, (B, H)), jnp.float32)
    return q, k, v, g


def _assert_close(got, want, tol=TOL, msg=""):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    err = np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1.0)
    assert err <= tol, f"{msg}: rel err {err:.3e} > {tol:.0e}"


@pytest.mark.parametrize("use_gamma", [False, True])
@pytest.mark.parametrize("normalize", [False, True])
@pytest.mark.parametrize("lam", [0.0, 0.2])
def test_hla2_fused_vjp_matches_naive_grad(rng, use_gamma, normalize, lam):
    B, H, n, d = 1, 2, 32, 8
    q, k, v, g = _mk(rng, B, H, n, d, d)
    gamma = g if use_gamma else None
    do = jnp.asarray(rng.randn(B, H, n, d), jnp.float32)

    def loss_fused(q_, k_, v_, g_):
        o = hla2_attention(
            q_, k_, v_, g_, chunk=8, normalize=normalize, lam=lam,
            interpret=True, fused_bwd=True,
        )
        return jnp.sum(o * do)

    def loss_naive(q_, k_, v_, g_):
        o = hla2_naive(q_, k_, v_, g_, normalize=normalize, lam=lam)
        return jnp.sum(o * do)

    if gamma is None:
        got = jax.grad(
            lambda a, b, c: loss_fused(a, b, c, None), argnums=(0, 1, 2)
        )(q, k, v)
        want = jax.grad(
            lambda a, b, c: loss_naive(a, b, c, None), argnums=(0, 1, 2)
        )(q, k, v)
        names = ("dq", "dk", "dv")
    else:
        got = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(q, k, v, gamma)
        want = jax.grad(loss_naive, argnums=(0, 1, 2, 3))(q, k, v, gamma)
        names = ("dq", "dk", "dv", "dgamma")
    for a, b, nm in zip(got, want, names):
        _assert_close(a, b, msg=nm)


@pytest.mark.parametrize("use_gamma", [False, True])
@pytest.mark.parametrize("normalize", [False, True])
def test_ahla_fused_vjp_matches_naive_grad(rng, use_gamma, normalize):
    B, H, n, d = 1, 2, 32, 8
    q, k, v, g = _mk(rng, B, H, n, d, d)
    gamma = g if use_gamma else None
    do = jnp.asarray(rng.randn(B, H, n, d), jnp.float32)

    def loss_fused(q_, k_, v_, g_):
        o = ahla_attention(
            q_, k_, v_, g_, chunk=8, normalize=normalize,
            interpret=True, fused_bwd=True,
        )
        return jnp.sum(o * do)

    def loss_naive(q_, k_, v_, g_):
        o = ahla_naive(q_, k_, v_, g_, normalize=normalize)
        return jnp.sum(o * do)

    if gamma is None:
        got = jax.grad(
            lambda a, b, c: loss_fused(a, b, c, None), argnums=(0, 1, 2)
        )(q, k, v)
        want = jax.grad(
            lambda a, b, c: loss_naive(a, b, c, None), argnums=(0, 1, 2)
        )(q, k, v)
        names = ("dq", "dk", "dv")
    else:
        got = jax.grad(loss_fused, argnums=(0, 1, 2, 3))(q, k, v, gamma)
        want = jax.grad(loss_naive, argnums=(0, 1, 2, 3))(q, k, v, gamma)
        names = ("dq", "dk", "dv", "dgamma")
    for a, b, nm in zip(got, want, names):
        _assert_close(a, b, msg=nm)


@pytest.mark.parametrize("kernel", ["hla2", "ahla"])
def test_bwd_kernel_matches_chunk_oracle(rng, kernel):
    """Fused bwd kernel vs the chunk-level jnp oracle in kernels.ref."""
    BH, n, d, chunk = 3, 48, 8, 16
    q = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.85, 0.99, (BH,)), jnp.float32)
    do = jnp.asarray(rng.randn(BH, n, d), jnp.float32)
    if kernel == "hla2":
        _, _, cs = hla2_chunk_pallas(
            q, k, v, g, chunk=chunk, interpret=True, save_chunk_states=True
        )
        got = hla2_chunk_bwd_pallas(
            q, k, v, g, do, cs, chunk=chunk, interpret=True
        )
        want = kref.hla2_chunk_bwd_ref(q, k, v, g, do, chunk=chunk)
    else:
        _, _, cs = ahla_chunk_pallas(
            q, k, v, g, chunk=chunk, interpret=True, save_chunk_states=True
        )
        got = ahla_chunk_bwd_pallas(
            q, k, v, g, do, cs, chunk=chunk, interpret=True
        )
        want = kref.ahla_chunk_bwd_ref(q, k, v, g, do, chunk=chunk)
    for a, b, nm in zip(got, want, ("dq", "dk", "dv", "dgamma")):
        _assert_close(a, b, tol=1e-5, msg=nm)


@pytest.mark.parametrize("fn", [hla2_chunk_pallas, ahla_chunk_pallas])
def test_kernel_accepts_arbitrary_length(rng, fn):
    """n not a chunk multiple: wrappers pad + slice; state matches ref."""
    BH, n, d, chunk = 2, 40, 8, 16  # 40 = 2.5 chunks
    q = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(BH, n, d) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.85, 0.99, (BH,)), jnp.float32)
    ref_fn = (
        kref.hla2_chunk_ref if fn is hla2_chunk_pallas else kref.ahla_chunk_ref
    )
    for gamma in (None, g):
        o, st = fn(q, k, v, gamma, chunk=chunk, interpret=True)
        o_ref, st_ref = ref_fn(q, k, v, gamma, chunk=chunk)
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(o_ref), atol=1e-4, rtol=1e-4
        )
        for a, b in zip(st, st_ref):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-4, rtol=1e-4
            )


def test_public_api_arbitrary_length_grads(rng):
    """Values + gradients through hla2_attention at a ragged length."""
    B, H, n, d = 1, 2, 40, 8  # 40 = 2.5 chunks of 16
    q, k, v, g = _mk(rng, B, H, n, d, d)
    do = jnp.asarray(rng.randn(B, H, n, d), jnp.float32)

    def loss(q_, k_, v_, g_, fused):
        o = hla2_attention(
            q_, k_, v_, g_, chunk=16, use_pallas=fused, fused_bwd=fused,
            interpret=True,
        )
        return jnp.sum(o * do)

    o_fused = hla2_attention(q, k, v, g, chunk=16, interpret=True)
    o_ref = hla2_attention(q, k, v, g, chunk=16, use_pallas=False)
    _assert_close(o_fused, o_ref, msg="fwd")
    got = jax.grad(lambda *a: loss(*a, True), argnums=(0, 1, 2, 3))(q, k, v, g)
    want = jax.grad(lambda *a: loss(*a, False), argnums=(0, 1, 2, 3))(
        q, k, v, g
    )
    for a, b, nm in zip(got, want, ("dq", "dk", "dv", "dgamma")):
        _assert_close(a, b, msg=nm)


def test_fused_bwd_off_matches_fused_bwd_on(rng):
    """The legacy recompute-in-backward path stays available and agrees."""
    B, H, n, d = 1, 2, 32, 8
    q, k, v, g = _mk(rng, B, H, n, d, d)

    def loss(q_, k_, v_, g_, fused_bwd):
        o = hla2_attention(
            q_, k_, v_, g_, chunk=8, fused_bwd=fused_bwd, interpret=True
        )
        return jnp.sum(o**2)

    got = jax.grad(lambda *a: loss(*a, True), argnums=(0, 1, 2, 3))(q, k, v, g)
    want = jax.grad(lambda *a: loss(*a, False), argnums=(0, 1, 2, 3))(
        q, k, v, g
    )
    for a, b, nm in zip(got, want, ("dq", "dk", "dv", "dgamma")):
        _assert_close(a, b, msg=nm)

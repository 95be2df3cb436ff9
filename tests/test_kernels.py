"""Per-kernel allclose tests: Pallas (interpret mode) vs pure-jnp oracles.

Sweeps shapes / dtypes / decay / normalization per the deliverable (c).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ahla_chunk import ahla_chunk_pallas
from repro.kernels.hla2_chunk import hla2_chunk_pallas
from repro.kernels import ref as kref
from repro.kernels.ops import ahla_attention, hla2_attention


def _mk(rng, BH, n, d, dv, dtype):
    q = jnp.asarray(rng.randn(BH, n, d) * 0.5, dtype)
    k = jnp.asarray(rng.randn(BH, n, d) * 0.5, dtype)
    v = jnp.asarray(rng.randn(BH, n, dv) * 0.5, dtype)
    g = jnp.asarray(rng.uniform(0.85, 0.99, (BH,)), jnp.float32)
    return q, k, v, g


SHAPES = [
    # (BH, n, d, dv, chunk)
    (2, 32, 8, 8, 8),
    (3, 64, 16, 8, 16),
    (1, 128, 32, 32, 32),
    (2, 64, 8, 24, 64),  # single chunk == whole sequence
]


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_gamma", [False, True])
def test_hla2_kernel_matches_ref(rng, shape, dtype, use_gamma):
    BH, n, d, dv, chunk = shape
    q, k, v, g = _mk(rng, BH, n, d, dv, dtype)
    gamma = g if use_gamma else None
    o, st = hla2_chunk_pallas(q, k, v, gamma, chunk=chunk, interpret=True)
    o_ref, st_ref = kref.hla2_chunk_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        gamma, chunk=chunk,
    )
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=tol, rtol=tol,
    )
    for got, want in zip(st, st_ref):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=tol, rtol=tol
        )


@pytest.mark.parametrize("normalize", [False, True])
@pytest.mark.parametrize("lam", [0.0, 0.2])
def test_hla2_kernel_normalize_ridge(rng, normalize, lam):
    q, k, v, g = _mk(rng, 2, 32, 8, 8, jnp.float32)
    o, _ = hla2_chunk_pallas(
        q, k, v, g, chunk=8, normalize=normalize, lam=lam, interpret=True
    )
    o_ref, _ = kref.hla2_chunk_ref(
        q, k, v, g, chunk=8, normalize=normalize, lam=lam
    )
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("use_gamma", [False, True])
def test_ahla_kernel_matches_ref(rng, shape, dtype, use_gamma):
    BH, n, d, dv, chunk = shape
    q, k, v, g = _mk(rng, BH, n, d, dv, dtype)
    gamma = g if use_gamma else None
    o, st = ahla_chunk_pallas(q, k, v, gamma, chunk=chunk, interpret=True)
    o_ref, st_ref = kref.ahla_chunk_ref(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32),
        gamma, chunk=chunk,
    )
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(o_ref, np.float32),
        atol=tol, rtol=tol,
    )
    # P, E states (m, n come packed in the same buffers)
    for got, want in zip(st, st_ref):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=tol, rtol=tol
        )


def test_ops_wrapper_grads(rng):
    """custom_vjp wrappers: value == kernel forward, grad == jnp reference."""
    B, H, n, d = 2, 2, 32, 8
    q = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.9, 0.99, (B, H)), jnp.float32)

    for fn in (hla2_attention, ahla_attention):
        o_pallas = fn(q, k, v, g, chunk=8, interpret=True)
        o_ref = fn(q, k, v, g, chunk=8, use_pallas=False)
        np.testing.assert_allclose(
            np.asarray(o_pallas), np.asarray(o_ref), atol=1e-4, rtol=1e-4
        )

        def loss(args, f=fn, pallas=True):
            return jnp.sum(
                f(*args, g, chunk=8, use_pallas=pallas, interpret=True) ** 2
            )

        g_pallas = jax.grad(loss)((q, k, v))
        g_ref = jax.grad(lambda a: loss(a, pallas=False))((q, k, v))
        for x, y in zip(g_pallas, g_ref):
            np.testing.assert_allclose(
                np.asarray(x), np.asarray(y), atol=1e-3, rtol=1e-3
            )


def test_kernel_under_jit_and_vmap(rng):
    q, k, v, g = _mk(rng, 4, 32, 8, 8, jnp.float32)
    f = jax.jit(
        lambda a, b, c: hla2_chunk_pallas(a, b, c, None, chunk=8, interpret=True)[0]
    )
    o = f(q, k, v)
    o_ref, _ = kref.hla2_chunk_ref(q, k, v, None, chunk=8)
    np.testing.assert_allclose(np.asarray(o), np.asarray(o_ref), atol=1e-4, rtol=1e-4)

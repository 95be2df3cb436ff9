"""Stateful kernel API: chunk-parallel prefill == serial recurrence.

The serving engine's exactness contract (ISSUE acceptance / DESIGN.md §8):
a whole prompt prefilled through ONE chunk-parallel kernel call must land
on the same streaming state as token-by-token ``hla2_step`` / ``ahla_step``
decode (≤1e-4 in fp32), for ragged prompt lengths, with and without decay
and normalization — including resuming from a mid-stream carry.  Also
covers the fused batched decode-step kernels (interpret mode on CPU).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.ahla import ahla_init_state, ahla_step
from repro.core.hla2 import hla2_init_state, hla2_step
from repro.kernels import ops as kops

STATE_TOL = 1e-4


def _mk(rng, B, H, n, d, dv):
    q = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    k = jnp.asarray(rng.randn(B, H, n, d) * 0.5, jnp.float32)
    v = jnp.asarray(rng.randn(B, H, n, dv) * 0.5, jnp.float32)
    g = jnp.asarray(rng.uniform(0.85, 0.99, (B, H)), jnp.float32)
    return q, k, v, g


def _serial_hla2(q, k, v, gamma, n, **kw):
    st = hla2_init_state(q.shape[:2], q.shape[-1], v.shape[-1])
    for t in range(n):
        st, _ = hla2_step(st, q[:, :, t], k[:, :, t], v[:, :, t], gamma, **kw)
    return st


def _serial_ahla(q, k, v, gamma, n, **kw):
    st = ahla_init_state(q.shape[:2], q.shape[-1], v.shape[-1])
    for t in range(n):
        st, _ = ahla_step(st, q[:, :, t], k[:, :, t], v[:, :, t], gamma, **kw)
    return st


@pytest.mark.parametrize("n", [13, 37, 64])
@pytest.mark.parametrize("use_gamma", [False, True])
@pytest.mark.parametrize("normalize", [False, True])
def test_hla2_prefill_state_matches_serial(rng, n, use_gamma, normalize):
    q, k, v, g = _mk(rng, 2, 2, n, 8, 8)
    gamma = g if use_gamma else None
    st_serial = _serial_hla2(q, k, v, gamma, n, normalize=normalize)
    _, st_kernel = kops.hla2_prefill(
        q, k, v, gamma, chunk=16, normalize=normalize, interpret=True
    )
    for ref, got, name in zip(st_serial, st_kernel, "SCmGh"):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= STATE_TOL, f"{name}: {err}"


@pytest.mark.parametrize("n", [13, 37, 64])
@pytest.mark.parametrize("use_gamma", [False, True])
@pytest.mark.parametrize("normalize", [False, True])
def test_ahla_prefill_state_matches_serial(rng, n, use_gamma, normalize):
    q, k, v, g = _mk(rng, 2, 2, n, 8, 8)
    gamma = g if use_gamma else None
    st_serial = _serial_ahla(q, k, v, gamma, n, normalize=normalize)
    _, st_kernel = kops.ahla_prefill(
        q, k, v, gamma, chunk=16, normalize=normalize, interpret=True
    )
    for ref, got, name in zip(st_serial, st_kernel, ["R", "P", "m", "E", "n"]):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= 2 * STATE_TOL, f"{name}: {err}"


def test_hla2_prefill_512_token_acceptance(rng):
    """Acceptance: a 512-token prompt prefills via one chunk-parallel call
    (no per-token Python loop) and matches serial hla2_step decode ≤1e-4."""
    n = 512
    q, k, v, g = _mk(rng, 1, 2, n, 8, 8)
    _, st_kernel = kops.hla2_prefill(q, k, v, g, chunk=128, interpret=True)
    st_serial = _serial_hla2(q, k, v, g, n)
    for ref, got, name in zip(st_serial, st_kernel, "SCmGh"):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= STATE_TOL, f"{name}: {err}"


def test_hla2_prefill_resumes_from_carry(rng):
    """Split prompt: serial first half -> kernel second half == full serial."""
    q, k, v, g = _mk(rng, 2, 2, 37, 8, 8)
    cut = 20
    st_half = _serial_hla2(q[:, :, :cut], k[:, :, :cut], v[:, :, :cut], g, cut)
    _, st_resumed = kops.hla2_prefill(
        q[:, :, cut:], k[:, :, cut:], v[:, :, cut:], g, chunk=16,
        state=st_half, interpret=True,
    )
    st_full = _serial_hla2(q, k, v, g, 37)
    for ref, got, name in zip(st_full, st_resumed, "SCmGh"):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= STATE_TOL, f"{name}: {err}"


def test_ahla_prefill_resumes_from_carry(rng):
    q, k, v, g = _mk(rng, 2, 2, 37, 8, 8)
    cut = 20
    st_half = _serial_ahla(q[:, :, :cut], k[:, :, :cut], v[:, :, :cut], g, cut)
    _, st_resumed = kops.ahla_prefill(
        q[:, :, cut:], k[:, :, cut:], v[:, :, cut:], g, chunk=16,
        state=st_half, interpret=True,
    )
    st_full = _serial_ahla(q, k, v, g, 37)
    for ref, got, name in zip(st_full, st_resumed, ["R", "P", "m", "E", "n"]):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= 2 * STATE_TOL, f"{name}: {err}"


# --------------------------------------------------------------------------
# fused batched decode steps
# --------------------------------------------------------------------------


@pytest.mark.parametrize("use_gamma", [False, True])
def test_hla2_fused_decode_step_matches_jnp(rng, use_gamma):
    q, k, v, g = _mk(rng, 2, 3, 6, 8, 8)
    gamma = g.reshape(2, 3) if use_gamma else None
    st_ref = hla2_init_state((2, 3), 8, 8)
    st_ker = st_ref
    for t in range(6):
        args = (q[:, :, t], k[:, :, t], v[:, :, t], gamma)
        st_ref, o_ref = hla2_step(st_ref, *args, lam=0.1)
        st_ker, o_ker = kops.hla2_decode_step(
            st_ker, *args, lam=0.1, interpret=True
        )
        assert float(jnp.max(jnp.abs(o_ref - o_ker))) <= STATE_TOL
    for ref, got in zip(st_ref, st_ker):
        assert float(jnp.max(jnp.abs(ref - got))) <= STATE_TOL


@pytest.mark.parametrize("use_gamma", [False, True])
def test_ahla_fused_decode_step_matches_jnp(rng, use_gamma):
    q, k, v, g = _mk(rng, 2, 3, 6, 8, 8)
    gamma = g.reshape(2, 3) if use_gamma else None
    st_ref = ahla_init_state((2, 3), 8, 8)
    st_ker = st_ref
    for t in range(6):
        args = (q[:, :, t], k[:, :, t], v[:, :, t], gamma)
        st_ref, o_ref = ahla_step(st_ref, *args)
        st_ker, o_ker = kops.ahla_decode_step(st_ker, *args, interpret=True)
        assert float(jnp.max(jnp.abs(o_ref - o_ker))) <= STATE_TOL
    for ref, got in zip(st_ref, st_ker):
        assert float(jnp.max(jnp.abs(ref - got))) <= STATE_TOL


def test_decode_step_continues_prefill_state(rng):
    """prefill(prompt) then fused steps == serial steps over prompt+decode."""
    q, k, v, g = _mk(rng, 1, 2, 20, 8, 8)
    _, st = kops.hla2_prefill(
        q[:, :, :16], k[:, :, :16], v[:, :, :16], g, chunk=8, interpret=True
    )
    for t in range(16, 20):
        st, _ = kops.hla2_decode_step(
            st, q[:, :, t], k[:, :, t], v[:, :, t], g, interpret=True
        )
    st_serial = _serial_hla2(q, k, v, g, 20)
    for ref, got, name in zip(st_serial, st, "SCmGh"):
        err = float(jnp.max(jnp.abs(ref.astype(jnp.float32) - got)))
        assert err <= STATE_TOL, f"{name}: {err}"

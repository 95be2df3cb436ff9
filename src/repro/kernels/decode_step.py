"""Fused batched decode steps — Pallas TPU kernels for the serving hot path.

One token of the streaming recurrence (paper Fig. 1(A) / Theorem 3.1 for
HLA2, Algorithm 2 for AHLA) applied to **every slot in one launch**:

* Grid ``(BH,)`` with ``dimension_semantics=("parallel",)`` — each program
  owns one (batch*head) row; there is no sequential axis, so all slots'
  state updates and outputs happen in a single kernel dispatch instead of
  the einsum chain in ``core/hla2.py`` (each einsum a separate HBM
  round-trip of the state under XLA).
* ``input_output_aliases`` alias every state operand to its output — the
  O(1) decode state is updated in place in HBM, never copied.
* All math in fp32 (matches the jnp steps bit-for-bit up to reassociation);
  the jnp fallback (``core.hla2.hla2_step`` / ``core.ahla.ahla_step``)
  stays the CPU path and the exactness oracle.

The container is CPU-only: tests run these kernels with ``interpret=True``;
on TPU the same ``pl.pallas_call`` lowers natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .hla2_chunk import _state_shapes, gamma_rows


def _step_compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel",))


def _step_gamma(gamma, batch_shape, BH: int):
    """Decay broadcast over the batch dims, as ``(BH, 1, 1)`` rows."""
    if gamma is not None:
        gamma = jnp.broadcast_to(jnp.asarray(gamma, jnp.float32), batch_shape)
    return gamma_rows(gamma, BH)


def _dot(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


def _dot_t(a, b):  # a @ b.T
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )


def _outer(a, b):  # a.T @ b  with a (1, d), b (1, e) -> (d, e)
    return jax.lax.dot_general(
        a, b, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


# --------------------------------------------------------------------------
# HLA2
# --------------------------------------------------------------------------


def _hla2_step_kernel(
    gamma_ref,  # (1, 1, 1) f32
    q_ref,  # (1, 1, d)
    k_ref,  # (1, 1, d)
    v_ref,  # (1, 1, dv)
    S_ref,  # (1, d, d)   aliased in/out
    C_ref,  # (1, d, dv)
    m_ref,  # (1, 1, d)
    G_ref,  # (1, d, dv)
    h_ref,  # (1, 1, d)
    o_ref,  # (1, 1, dv)
    S_out,
    C_out,
    m_out,
    G_out,
    h_out,
    *,
    normalize: bool,
    eps: float,
    lam: float,
    has_decay: bool,
):
    f32 = jnp.float32
    q = q_ref[0].astype(f32)  # (1, d)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    g = gamma_ref[0] if has_decay else jnp.ones((), f32)  # (1, 1)

    S0, C0, m0, G0, h0 = (
        S_ref[0], C_ref[0], m_ref[0], G_ref[0], h_ref[0]
    )

    # cross summaries first: strict causality consumes the *previous* C, m
    kC = _dot(k, C0)  # (1, dv)
    km = _dot_t(k, m0)  # (1, 1)
    G1 = g * g * G0 + g * _outer(k, kC)
    h1 = g * g * h0 + g * km * k
    S1 = g * S0 + _outer(k, k)
    C1 = g * C0 + _outer(q, v)
    m1 = g * m0 + q

    u = _dot(q, S1)  # (1, d)
    num = _dot(u, C1) - _dot(q, G1)
    if lam:
        num = num + lam * _dot(q, C1)
    if normalize:
        den = _dot_t(u, m1) - _dot_t(q, h1)
        if lam:
            den = den + lam * _dot_t(q, m1)
        o = num / (den + eps)
    else:
        o = num

    o_ref[0] = o.astype(o_ref.dtype)
    S_out[0] = S1
    C_out[0] = C1
    m_out[0] = m1
    G_out[0] = G1
    h_out[0] = h1


def hla2_step_pallas(
    state,  # (S, C, m, G, h) with leading (..., d, ...) batch dims
    q_t: jax.Array,  # (..., d)
    k_t: jax.Array,
    v_t: jax.Array,  # (..., dv)
    gamma=None,  # broadcastable to the batch dims, or None
    *,
    normalize: bool = False,
    eps: float = 1e-6,
    lam: float = 0.0,
    interpret: bool = False,
):
    """One fused decode step for all rows.  Returns ``(new_state, o_t)``
    (same order as ``core.hla2.hla2_step``)."""
    S, C, m, G, h = state
    batch_shape = q_t.shape[:-1]
    d = q_t.shape[-1]
    dv = v_t.shape[-1]
    BH = 1
    for s in batch_shape:
        BH *= s
    has_decay = gamma is not None
    f32 = jnp.float32
    gamma_in = _step_gamma(gamma, batch_shape, BH)
    qf = q_t.reshape(BH, 1, d)
    kf = k_t.reshape(BH, 1, d)
    vf = v_t.reshape(BH, 1, dv)
    Sf = S.reshape(BH, d, d).astype(f32)
    Cf = C.reshape(BH, d, dv).astype(f32)
    mf = m.reshape(BH, 1, d).astype(f32)
    Gf = G.reshape(BH, d, dv).astype(f32)
    hf = h.reshape(BH, 1, d).astype(f32)

    kernel = functools.partial(
        _hla2_step_kernel,
        normalize=normalize,
        eps=eps,
        lam=lam,
        has_decay=has_decay,
    )
    st_shapes = _state_shapes(d, dv)
    row = lambda a, b: pl.BlockSpec((1, a, b), lambda i: (i, 0, 0))  # noqa: E731
    in_specs = [
        row(1, 1),  # gamma
        row(1, d), row(1, d), row(1, dv),
    ] + [row(a, b) for a, b in st_shapes]
    out_specs = [row(1, dv)] + [row(a, b) for a, b in st_shapes]
    out_shape = [jax.ShapeDtypeStruct((BH, 1, dv), v_t.dtype)] + [
        jax.ShapeDtypeStruct((BH,) + s, f32) for s in st_shapes
    ]
    o, S1, C1, m1, G1, h1 = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        # state operands update in place in HBM (operand 4..8 -> output 1..5)
        input_output_aliases={4: 1, 5: 2, 6: 3, 7: 4, 8: 5},
        interpret=interpret,
        name="hla2_decode_step",
        compiler_params=_step_compiler_params(interpret),
    )(gamma_in, qf, kf, vf, Sf, Cf, mf, Gf, hf)
    new_state = (
        S1.reshape(S.shape).astype(S.dtype),
        C1.reshape(C.shape).astype(C.dtype),
        m1.reshape(m.shape).astype(m.dtype),
        G1.reshape(G.shape).astype(G.dtype),
        h1.reshape(h.shape).astype(h.dtype),
    )
    return new_state, o.reshape(batch_shape + (dv,)).astype(v_t.dtype)


# --------------------------------------------------------------------------
# AHLA
# --------------------------------------------------------------------------


def _ahla_step_kernel(
    gamma_ref,  # (1, 1, 1)
    q_ref,  # (1, 1, d)
    k_ref,  # (1, 1, d)
    vb_ref,  # (1, 1, dv+1)  ones-augmented value
    R_ref,  # (1, d, d)      aliased in/out (undecayed cross moment)
    P_ref,  # (1, d, dv+1)   [P | m]
    E_ref,  # (1, d, dv+1)   [E | n]
    o_ref,  # (1, 1, dv+1)   augmented output [num | den]
    R_out,
    P_out,
    E_out,
    *,
    normalize: bool,
    eps: float,
    has_decay: bool,
):
    f32 = jnp.float32
    q = q_ref[0].astype(f32)
    k = k_ref[0].astype(f32)
    vb = vb_ref[0].astype(f32)
    g = gamma_ref[0] if has_decay else jnp.ones((), f32)  # (1, 1)

    P1 = g * P_ref[0] + _outer(k, vb)  # [P | m] update (Algorithm 2)
    rbar = _dot(q, P1)  # [r_t | s_t], inclusive P (Thm 6.1)
    E1 = g * E_ref[0] + _outer(k, rbar)  # [E | n] update
    obar = _dot(q, E1)  # (1, dv+1)
    if normalize:
        dv = obar.shape[-1] - 1
        o = obar[:, :dv] / (obar[:, dv:] + eps)
        obar = jnp.concatenate([o, obar[:, dv:]], axis=-1)
    R1 = R_ref[0] + _outer(k, q)

    o_ref[0] = obar.astype(o_ref.dtype)
    R_out[0] = R1
    P_out[0] = P1
    E_out[0] = E1


def ahla_step_pallas(
    state,  # (R, P, m, E, n) with leading batch dims
    q_t: jax.Array,  # (..., d)
    k_t: jax.Array,
    v_t: jax.Array,  # (..., dv)
    gamma=None,
    *,
    normalize: bool = False,
    eps: float = 1e-6,
    interpret: bool = False,
):
    """One fused AHLA decode step for all rows.  Returns ``(new_state, o_t)``."""
    R, P, m, E, n = state
    batch_shape = q_t.shape[:-1]
    d = q_t.shape[-1]
    dv = v_t.shape[-1]
    BH = 1
    for s in batch_shape:
        BH *= s
    has_decay = gamma is not None
    f32 = jnp.float32
    gamma_in = _step_gamma(gamma, batch_shape, BH)
    qf = q_t.reshape(BH, 1, d)
    kf = k_t.reshape(BH, 1, d)
    vb = jnp.concatenate(
        [v_t.reshape(BH, 1, dv), jnp.ones((BH, 1, 1), v_t.dtype)], axis=-1
    )
    Rf = R.reshape(BH, d, d).astype(f32)
    Pbar = jnp.concatenate(
        [P.reshape(BH, d, dv).astype(f32),
         m.reshape(BH, d, 1).astype(f32)], axis=-1
    )
    Ebar = jnp.concatenate(
        [E.reshape(BH, d, dv).astype(f32),
         n.reshape(BH, d, 1).astype(f32)], axis=-1
    )

    kernel = functools.partial(
        _ahla_step_kernel, normalize=normalize, eps=eps, has_decay=has_decay
    )
    row = lambda a, b: pl.BlockSpec((1, a, b), lambda i: (i, 0, 0))  # noqa: E731
    in_specs = [
        row(1, 1),  # gamma
        row(1, d), row(1, d), row(1, dv + 1),
        row(d, d), row(d, dv + 1), row(d, dv + 1),
    ]
    out_specs = [row(1, dv + 1), row(d, d), row(d, dv + 1), row(d, dv + 1)]
    out_shape = [
        jax.ShapeDtypeStruct((BH, 1, dv + 1), v_t.dtype),
        jax.ShapeDtypeStruct((BH, d, d), f32),
        jax.ShapeDtypeStruct((BH, d, dv + 1), f32),
        jax.ShapeDtypeStruct((BH, d, dv + 1), f32),
    ]
    obar, R1, P1, E1 = pl.pallas_call(
        kernel,
        grid=(BH,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={4: 1, 5: 2, 6: 3},
        interpret=interpret,
        name="ahla_decode_step",
        compiler_params=_step_compiler_params(interpret),
    )(gamma_in, qf, kf, vb, Rf, Pbar, Ebar)
    new_state = (
        R1.reshape(R.shape).astype(R.dtype),
        P1[..., :dv].reshape(P.shape).astype(P.dtype),
        P1[..., dv].reshape(m.shape).astype(m.dtype),
        E1[..., :dv].reshape(E.shape).astype(E.dtype),
        E1[..., dv].reshape(n.shape).astype(n.dtype),
    )
    o = obar[..., 0, :dv].reshape(batch_shape + (dv,)).astype(v_t.dtype)
    return new_state, o

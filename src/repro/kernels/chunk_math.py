"""Per-chunk HLA2 / AHLA math shared by the Pallas kernels and references.

One chunk of the chunkwise scheme (DESIGN.md §2) as a *pure function* of
``(Q, K, V, state_in, gamma) -> (o, state_out)`` on single-head 2D tiles:

* the **forward** kernels call it once per grid step, carrying ``state`` in
  VMEM scratch;
* the **backward** kernels (DESIGN.md §3) call ``jax.vjp`` on it — the
  linearization recomputes the intra-chunk tiles from ``q/k/v`` plus the
  checkpointed incoming state and emits only transposed MXU-shaped
  contractions, so the reverse pass is exactly the adjoint of the forward
  math with no hand-derivation drift;
* the pure-jnp backward oracle in ``ref.py`` is the same function ``vmap``-ed
  over the batch×head axis — kernel and oracle are bit-identical by
  construction.

Everything here must stay Pallas-traceable: 2D tiles, ``broadcasted_iota``
masks, ``dot_general`` with fp32 accumulation, no data-dependent shapes.
The decay masks clamp the exponent *before* ``exp`` so the VJP is free of
``0 * inf`` NaNs at masked positions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def decay_mats(w: int, g, dtype):
    """In-kernel L_gamma, g^(t+1), g^(w-1-t) from g (g=1 => plain L).

    ``g`` is a scalar or a ``(1, 1)`` tile (the kernels keep per-row decay
    as a 2D block; Mosaic has no scalar loads from VMEM).  Returns
    ``(Lg, pow_t, pow_rev, mask)`` with ``pow_t``/``pow_rev`` as ``(w, 1)``
    columns: every operand stays 2D and every iota is integer (Mosaic
    refuses float iotas).  The masked exponent is clamped to 0 before
    ``exp`` so reverse-mode AD never sees an overflowed branch.
    """
    t = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    mask = t >= j
    diff = jnp.where(mask, t - j, 0).astype(dtype)
    logg = jnp.log(g)
    Lg = jnp.where(mask, jnp.exp(diff * logg), jnp.zeros((), dtype))
    tv = jax.lax.broadcasted_iota(jnp.int32, (w, 1), 0).astype(dtype)
    pow_t = jnp.exp((tv + 1.0) * logg)  # g^t for t=1..w
    pow_rev = jnp.exp((w - 1.0 - tv) * logg)  # g^(w-t) for t=1..w
    return Lg, pow_t, pow_rev, mask


def hla2_chunk_math(
    Q,  # (w, d) f32
    K,  # (w, d) f32
    V,  # (w, dv) f32
    state,  # (S0 (d,d), C0 (d,dv), m0 (1,d), G0 (d,dv), h0 (1,d)) f32
    g,  # scalar f32 decay (1.0 = no decay)
    *,
    normalize: bool,
    eps: float,
    lam: float,
):
    """One HLA2 chunk: outputs + monoid carry update (DESIGN.md §2).

    For local tokens 1..w with carry (S0, C0, m0, G0, h0), D0 = S0 C0 - G0:

        num_t = g^{2t} q_t D0                              (T1: Q @ D0)
              + g^t   row_t[(Q S0 Q^T . Lg) V]             (T2)
              + row_t[((A B) . Lg) V]                      (T3, intra)
        A = (Q K^T) . Lg,  B = (K Q^T) . U  (U = upper incl diag)
    """
    f32 = jnp.float32
    w = Q.shape[0]
    S0, C0, m0, G0, h0 = state

    Lg, pow_t, pow_rev, mask = decay_mats(w, g, f32)
    t = jax.lax.broadcasted_iota(jnp.int32, (w, w), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (w, w), 1)
    U = (t <= j).astype(f32)  # i <= j (upper incl)
    Ls = (t > j).astype(f32)  # strict lower

    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    mm = lambda a, b: dot(a, b, (((1,), (0,)), ((), ())))  # noqa: E731
    mmT = lambda a, b: dot(a, b, (((1,), (1,)), ((), ())))  # noqa: E731  a @ b.T

    A = mmT(Q, K) * Lg  # (w, w)   (QK^T) . Lg
    Bm = mmT(K, Q) * U  # B[i, j] = (k_i . q_j) masked i<=j
    M3 = mm(A, Bm) * Lg
    QS0 = mm(Q, S0)  # (w, d)
    QS0Q = mmT(QS0, Q) * Lg

    D0 = mm(S0, C0) - G0  # (d, dv)
    T1 = pow_t**2 * mm(Q, D0)
    T2 = pow_t * mm(QS0Q, V)
    T3 = mm(M3, V)
    num = T1 + T2 + T3
    if lam:
        Wqq = mmT(Q, Q) * Lg
        num = num + lam * (pow_t * mm(Q, C0) + mm(Wqq, V))
    if normalize:
        d0v = mm(S0, m0.T) - h0.T  # (d, 1)
        den = (
            pow_t**2 * mm(Q, d0v)
            + pow_t * jnp.sum(QS0Q, -1, keepdims=True)
            + jnp.sum(M3, -1, keepdims=True)
        )
        if lam:
            den = den + lam * (
                pow_t * mm(Q, m0.T) + jnp.sum(Wqq, -1, keepdims=True)
            )
        o = num / (den + eps)
    else:
        o = num

    # ---- carry update (monoid, B = whole chunk) ----
    rho = jnp.exp(jnp.log(g) * w)
    Kg = pow_rev * K
    Qg = pow_rev * Q
    Sw = dot(Kg, K, (((0,), (0,)), ((), ())))  # (d, d)
    Cw = dot(Qg, V, (((0,), (0,)), ((), ())))  # (d, dv)
    mw = jnp.sum(Qg, 0, keepdims=True)  # (1, d)
    N = mmT(K, Q) * Ls
    Vg = pow_rev * V
    NVg = mm(N, Vg)
    Gw = dot(Kg, NVg, (((0,), (0,)), ((), ())))
    # sum_j N[t, j] g^(w-j): the row of exponents comes from its own
    # integer iota (no (w, 1) -> (1, w) relayout in the kernel)
    jv = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1).astype(f32)
    pow_rev_row = jnp.exp((w - 1.0 - jv) * jnp.log(g))
    Nmg = jnp.sum(N * pow_rev_row, -1, keepdims=True)  # (w, 1)
    hw = dot(Nmg, Kg, (((0,), (0,)), ((), ())))  # (1, d)

    S1 = rho * S0 + Sw
    C1 = rho * C0 + Cw
    m1 = rho * m0 + mw
    G1 = rho**2 * G0 + Gw + rho * mm(Sw, C0)
    h1 = rho**2 * h0 + hw + rho * mm(m0, Sw.T)
    return o, (S1, C1, m1, G1, h1)


def ahla_chunk_math(
    Q,  # (w, d) f32
    K,  # (w, d) f32
    V,  # (w, dv) f32
    state,  # (P0 (d, dv+1), E0 (d, dv+1)) f32 — den columns augmented
    g,  # scalar f32
    *,
    normalize: bool,
    eps: float,
):
    """One AHLA chunk: fused inner+outer linear-attention passes.

    The intermediate first-order outputs ``r`` never materialize outside the
    chunk; the carry is ``(P | m, E | n)`` with the ones-augmented V trick.
    """
    f32 = jnp.float32
    w = Q.shape[0]
    P0, E0 = state
    Vb = jnp.concatenate([V, jnp.ones((w, 1), f32)], axis=-1)

    Lg, pow_t, pow_rev, mask = decay_mats(w, g, f32)

    dot = functools.partial(jax.lax.dot_general, preferred_element_type=f32)
    mm = lambda a, b: dot(a, b, (((1,), (0,)), ((), ())))  # noqa: E731
    mmT = lambda a, b: dot(a, b, (((1,), (1,)), ((), ())))  # noqa: E731

    A = mmT(Q, K) * Lg
    AV = mm(A, Vb)  # local first-order outputs
    r = pow_t * mm(Q, P0) + AV  # carry-inclusive r_t | s_t
    o_aug = pow_t * mm(Q, E0) + mm(A, r)
    if normalize:
        o = o_aug[:, :-1] / (o_aug[:, -1:] + eps)
    else:
        o = o_aug[:, :-1]

    rho = jnp.exp(jnp.log(g) * w)
    Kg = pow_rev * K
    KgT_ = lambda X: dot(Kg, X, (((0,), (0,)), ((), ())))  # noqa: E731
    R = dot(K, Q, (((0,), (0,)), ((), ())))  # (d, d) sum_t k_t q_t^T (undecayed)
    P1 = rho * P0 + KgT_(Vb)
    E1 = rho * E0 + KgT_(AV) + rho * mm(R, P0)
    return o, (P1, E1)

"""Fused chunkwise AHLA — Pallas TPU kernels (fwd + bwd).

AHLA = LinAttn o LinAttn (DESIGN.md §2): both passes are fused in one
kernel so the intermediate first-order outputs ``r`` never leave VMEM.
The carry ``(P | m, E | n)`` (den columns augmented) persists in VMEM
scratch across the sequential chunk axis.  Grid/BlockSpec layout mirrors
``hla2_chunk.py``, as does the training path (DESIGN.md §3): the forward
can checkpoint each chunk's incoming ``(P, E)`` to HBM and
``ahla_chunk_bwd_pallas`` walks the chunks in reverse, recomputing the
intra-chunk tiles via ``jax.vjp`` of the shared per-chunk math while the
state cotangents live in VMEM scratch.  Arbitrary sequence lengths are
handled by zero-padding to a chunk multiple in the wrappers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunk_math import ahla_chunk_math
from .hla2_chunk import _compiler_params, _pad_chunk_multiple, gamma_rows


def _unscale_padded_state(Pa, Ea, gamma, pad: int):
    """Phantom zero-tokens only decay the AHLA carry (all at rate gamma):
    divide the spurious gamma^pad back out."""
    if gamma is None or pad == 0:
        return Pa, Ea
    inv = jnp.power(gamma.astype(jnp.float32), -float(pad))[:, None, None]
    return Pa * inv, Ea * inv


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _ahla_chunk_kernel(
    gamma_ref,  # (1, 1, 1)
    q_ref,  # (1, w, d)
    k_ref,  # (1, w, d)
    v_ref,  # (1, w, dv)
    *rest,  # [P0_in, E0_in iff has_init], o, P_out, E_out,
    #         [Pc_out, Ec_out iff save_states], scratch P, E
    w: int,
    normalize: bool,
    eps: float,
    has_decay: bool,
    has_init: bool,
    n_chunks: int,
    save_states: bool,
):
    if has_init:
        P0_in, E0_in = rest[:2]
        rest = rest[2:]
    o_ref, P_out, E_out = rest[:3]
    rest = rest[3:]
    if save_states:
        Pc_out, Ec_out, P, E = rest
    else:
        P, E = rest
    c = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _init():
        if has_init:
            P[...] = P0_in[0].astype(f32)
            E[...] = E0_in[0].astype(f32)
        else:
            P[...] = jnp.zeros_like(P)
            E[...] = jnp.zeros_like(E)

    Q = q_ref[0].astype(f32)
    K = k_ref[0].astype(f32)
    V = v_ref[0].astype(f32)
    g = gamma_ref[0] if has_decay else jnp.ones((), f32)  # (1, 1)

    state0 = (P[...], E[...])
    if save_states:
        Pc_out[0, 0] = state0[0]
        Ec_out[0, 0] = state0[1]

    o, state1 = ahla_chunk_math(
        Q, K, V, state0, g, normalize=normalize, eps=eps
    )
    o_ref[0, :, :] = o.astype(o_ref.dtype)
    P[...], E[...] = state1

    @pl.when(c == n_chunks - 1)
    def _write_state():
        P_out[0] = P[...].astype(P_out.dtype)
        E_out[0] = E[...].astype(E_out.dtype)


def ahla_chunk_pallas(
    q: jax.Array,  # (BH, n, d)
    k: jax.Array,
    v: jax.Array,
    gamma: jax.Array | None = None,
    *,
    chunk: int = 128,
    normalize: bool = False,
    eps: float = 1e-6,
    interpret: bool = False,
    save_chunk_states: bool = False,
    initial_state=None,
):
    """Fused AHLA forward.  Returns ``(o, (P, m, E, n))``, plus the
    per-chunk incoming ``([P|m], [E|n])`` checkpoints (``(BH, nc, d, dv+1)``)
    when ``save_chunk_states=True``.  Arbitrary ``n`` is zero-padded to a
    chunk multiple and sliced back.

    ``initial_state`` is an optional ``(P, m, E, n)`` carry per row
    (``(BH, d, dv) / (BH, d) / (BH, d, dv) / (BH, d)``) the chunk walk
    resumes from — one chunk-parallel call prefills a whole prompt exactly
    (serving engine prefill path)."""
    BH, n, d = q.shape
    dv = v.shape[-1]
    w = min(chunk, n)
    pad = (-n) % w
    if pad:
        q, k, v = _pad_chunk_multiple(n, w, q, k, v)
    np_ = n + pad
    nc = np_ // w
    has_decay = gamma is not None
    has_init = initial_state is not None
    gamma_in = gamma_rows(gamma, BH)
    kernel = functools.partial(
        _ahla_chunk_kernel,
        w=w,
        normalize=normalize,
        eps=eps,
        has_decay=has_decay,
        has_init=has_init,
        n_chunks=nc,
        save_states=save_chunk_states,
    )
    out_shape = [
        jax.ShapeDtypeStruct((BH, np_, dv), v.dtype),
        jax.ShapeDtypeStruct((BH, d, dv + 1), jnp.float32),
        jax.ShapeDtypeStruct((BH, d, dv + 1), jnp.float32),
    ]
    grid = (BH, nc)
    in_specs = [
            pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((1, w, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, w, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, w, dv), lambda i, c: (i, c, 0)),
    ]
    inputs = [gamma_in, q, k, v]
    if has_init:
        P0, m0, E0, n0 = initial_state
        f32 = jnp.float32
        Pbar = jnp.concatenate(
            [P0.astype(f32), m0.astype(f32)[..., None]], axis=-1
        )
        Ebar = jnp.concatenate(
            [E0.astype(f32), n0.astype(f32)[..., None]], axis=-1
        )
        inputs += [Pbar, Ebar]
        in_specs += [
            pl.BlockSpec((1, d, dv + 1), lambda i, c: (i, 0, 0))
            for _ in range(2)
        ]
    out_specs = [
            pl.BlockSpec((1, w, dv), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, d, dv + 1), lambda i, c: (i, 0, 0)),
            pl.BlockSpec((1, d, dv + 1), lambda i, c: (i, 0, 0)),
    ]
    if save_chunk_states:
        out_shape += [
            jax.ShapeDtypeStruct((BH, nc, d, dv + 1), jnp.float32)
            for _ in range(2)
        ]
        out_specs += [
            pl.BlockSpec((1, 1, d, dv + 1), lambda i, c: (i, c, 0, 0))
            for _ in range(2)
        ]
    scratch_shapes = [
        pltpu.VMEM((d, dv + 1), jnp.float32),
        pltpu.VMEM((d, dv + 1), jnp.float32),
    ]
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="ahla_chunk_fwd",
        compiler_params=_compiler_params(interpret),
    )(*inputs)
    o, Pa, Ea = outs[:3]
    o = o[:, :n]
    Pa, Ea = _unscale_padded_state(Pa, Ea, gamma, pad)
    state = (Pa[..., :dv], Pa[..., dv], Ea[..., :dv], Ea[..., dv])
    if save_chunk_states:
        return o, state, tuple(outs[3:])
    return o, state


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _ahla_chunk_bwd_kernel(
    gamma_ref,  # (1, 1, 1)
    q_ref,  # (1, w, d)  — chunk nc-1-c (reversed walk)
    k_ref,
    v_ref,
    Pc_ref,  # (1, 1, d, dv+1) checkpointed incoming [P|m]
    Ec_ref,  # (1, 1, d, dv+1) checkpointed incoming [E|n]
    do_ref,  # (1, w, dv)
    dq_ref,
    dk_ref,
    dv_ref,
    dg_ref,  # (1, 1, 1)
    dP,  # scratch (d, dv+1) f32 — state cotangents
    dE,  # scratch (d, dv+1) f32
    dg_acc,  # scratch (1, 1) f32
    *,
    w: int,
    normalize: bool,
    eps: float,
    has_decay: bool,
    n_chunks: int,
):
    c = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _init():
        dP[...] = jnp.zeros_like(dP)
        dE[...] = jnp.zeros_like(dE)
        dg_acc[...] = jnp.zeros_like(dg_acc)

    Q = q_ref[0].astype(f32)
    K = k_ref[0].astype(f32)
    V = v_ref[0].astype(f32)
    dO = do_ref[0].astype(f32)
    state0 = (Pc_ref[0, 0], Ec_ref[0, 0])
    dstate1 = (dP[...], dE[...])

    if has_decay:
        g = gamma_ref[0]  # (1, 1)
        _, vjp = jax.vjp(
            functools.partial(ahla_chunk_math, normalize=normalize, eps=eps),
            Q, K, V, state0, g,
        )
        dQ, dK, dV, dstate0, dgc = vjp((dO, dstate1))
        dg_acc[...] += dgc
    else:
        one = jnp.ones((), f32)
        _, vjp = jax.vjp(
            lambda q_, k_, v_, st_: ahla_chunk_math(
                q_, k_, v_, st_, one, normalize=normalize, eps=eps
            ),
            Q, K, V, state0,
        )
        dQ, dK, dV, dstate0 = vjp((dO, dstate1))

    dq_ref[0] = dQ.astype(dq_ref.dtype)
    dk_ref[0] = dK.astype(dk_ref.dtype)
    dv_ref[0] = dV.astype(dv_ref.dtype)
    dP[...], dE[...] = dstate0

    @pl.when(c == n_chunks - 1)
    def _write_dg():
        dg_ref[0] = dg_acc[...]


def ahla_chunk_bwd_pallas(
    q: jax.Array,  # (BH, n, d)
    k: jax.Array,
    v: jax.Array,
    gamma: jax.Array | None,
    do: jax.Array,  # (BH, n, dv)
    chunk_states,  # ([P|m], [E|n]) checkpoints from the forward
    *,
    chunk: int = 128,
    normalize: bool = False,
    eps: float = 1e-6,
    interpret: bool = False,
):
    """Fused AHLA backward (reverse chunk walk).  Returns (dq, dk, dv, dgamma)."""
    BH, n, d = q.shape
    dv_ = v.shape[-1]
    w = min(chunk, n)
    pad = (-n) % w
    if pad:
        q, k, v, do = _pad_chunk_multiple(n, w, q, k, v, do)
    np_ = n + pad
    nc = np_ // w
    assert chunk_states[0].shape[1] == nc, (
        "chunk_states do not match the (padded) chunk grid; pass the tuple "
        "returned by ahla_chunk_pallas(save_chunk_states=True) unchanged"
    )
    has_decay = gamma is not None
    gamma_in = gamma_rows(gamma, BH)
    kernel = functools.partial(
        _ahla_chunk_bwd_kernel,
        w=w,
        normalize=normalize,
        eps=eps,
        has_decay=has_decay,
        n_chunks=nc,
    )
    grid = (BH, nc)
    rev_blk = lambda i, c: (i, nc - 1 - c, 0)  # noqa: E731
    rev_st = lambda i, c: (i, nc - 1 - c, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, dv_), rev_blk),
        pl.BlockSpec((1, 1, d, dv_ + 1), rev_st),
        pl.BlockSpec((1, 1, d, dv_ + 1), rev_st),
        pl.BlockSpec((1, w, dv_), rev_blk),
    ]
    out_specs = [
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, dv_), rev_blk),
        pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, np_, d), q.dtype),
        jax.ShapeDtypeStruct((BH, np_, d), k.dtype),
        jax.ShapeDtypeStruct((BH, np_, dv_), v.dtype),
        jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
    ]
    scratch_shapes = [
        pltpu.VMEM((d, dv_ + 1), jnp.float32),
        pltpu.VMEM((d, dv_ + 1), jnp.float32),
        pltpu.VMEM((1, 1), jnp.float32),
    ]
    dq, dk, dv, dg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="ahla_chunk_bwd",
        compiler_params=_compiler_params(interpret),
    )(gamma_in, q, k, v, *chunk_states, do)
    dq, dk, dv = dq[:, :n], dk[:, :n], dv[:, :n]
    dgamma = dg[:, 0, 0] if has_decay else None
    return dq, dk, dv, dgamma

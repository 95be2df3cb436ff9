"""Fused chunkwise masked second-order HLA — Pallas TPU kernels (fwd + bwd).

Design (DESIGN.md §2 / §3, hardware adaptation):

* Grid ``(BH, n_chunks)`` with ``dimension_semantics=("parallel",
  "arbitrary")``: the batch×head axis parallelizes across cores, the chunk
  axis is sequential and carries the running state tuple
  ``(S, C, m, G, h)`` in **VMEM scratch** — the state never round-trips to
  HBM between chunks (the main win over the XLA-scheduled jnp version).
* Every intra-chunk contraction is an MXU-shaped matmul on ``(w, d)`` /
  ``(w, w)`` tiles: choose ``w`` and ``d`` multiples of 128 on real TPUs.
* bf16/fp32 inputs; all accumulation in fp32 via ``preferred_element_type``.
* Per-(batch,head) scalar decay ``gamma``; masks are built in-kernel with
  ``broadcasted_iota`` (no host-side (w, w) constants shipped per head).
* **Training** (``save_chunk_states=True``): the forward additionally spills
  each chunk's *incoming* state to HBM — ``nc ×`` constant-size state, the
  classic checkpointing trade.  ``hla2_chunk_bwd_pallas`` then walks the
  chunk axis in reverse over the same grid, recomputes the intra-chunk
  tiles from ``q/k/v`` + the checkpointed state via ``jax.vjp`` of the
  shared per-chunk math (``chunk_math.py``), and carries the reverse-mode
  state cotangents in VMEM scratch — one fused backward, no second
  XLA-scheduled forward.
* Arbitrary sequence lengths: inputs are zero-padded to a chunk multiple
  in the wrappers and outputs sliced back (final-state decay attenuation
  from the phantom tokens is divided back out).

VMEM budget at d = dv = 128, w = 256, fp32:
  state 3*(128*128) + 2*128 floats ~ 197 KB; blocks q/k/v/o 4*(256*128)
  ~ 512 KB; intra tiles (w,w) 3*(256*256) ~ 768 KB  => well under 16 MB.
The backward adds the 5 cotangent state buffers (~197 KB) and the VJP's
transposed intra tiles — still comfortably inside VMEM.

The container is CPU-only: tests run these kernels with ``interpret=True``
(the kernel body executes in Python) against ``ref.py``; on TPU hardware
the same ``pl.pallas_call`` lowers natively.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .chunk_math import hla2_chunk_math


def _state_shapes(d: int, dv: int):
    return ((d, d), (d, dv), (1, d), (d, dv), (1, d))


def _compiler_params(interpret: bool):
    if interpret:
        return None
    return pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary"))


def gamma_rows(gamma, BH: int):
    """Per-row decay as a ``(BH, 1, 1)`` f32 array (ones when None).

    Each grid row reads its own ``(1, 1, 1)`` block: the block's last two
    dims equal the array's, which is what the TPU's (8, 128) tiling rule
    asks of blocks narrower than a tile.  Kernels use the ``(1, 1)`` tile
    as a broadcasting operand — never as a scalar, which Mosaic cannot load
    from or store to VMEM.
    """
    if gamma is None:
        return jnp.ones((BH, 1, 1), jnp.float32)
    return gamma.reshape(BH, 1, 1).astype(jnp.float32)


def _pad_chunk_multiple(n: int, w: int, *arrays):
    """Zero-pad time axis (axis 1) of each (BH, n, ·) array to a multiple of w."""
    pad = (-n) % w
    if pad == 0:
        return arrays
    return tuple(
        jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays
    )


def _unscale_padded_state(state, gamma, pad: int):
    """Undo the spurious gamma^pad decay phantom zero-tokens apply to the
    final carry (gamma^2pad on the cross summaries G, h)."""
    if gamma is None or pad == 0:
        return state
    inv = jnp.power(gamma.astype(jnp.float32), -float(pad))
    S, C, m, G, h = state
    return (
        S * inv[:, None, None],
        C * inv[:, None, None],
        m * inv[:, None],
        G * (inv**2)[:, None, None],
        h * (inv**2)[:, None],
    )


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------


def _hla2_chunk_kernel(
    # inputs: gamma, q/k/v, then the initial carry (5) iff has_init
    gamma_ref,  # (1, 1, 1) f32
    q_ref,  # (1, w, d)
    k_ref,  # (1, w, d)
    v_ref,  # (1, w, dv)
    # outputs: o, final state (5), then per-chunk states (5) iff save_states
    *rest,
    w: int,
    normalize: bool,
    eps: float,
    lam: float,
    has_decay: bool,
    has_init: bool,
    n_chunks: int,
    save_states: bool,
):
    if has_init:
        (S0_in, C0_in, m0_in, G0_in, h0_in) = rest[:5]
        rest = rest[5:]
    o_ref = rest[0]
    rest = rest[1:]
    if save_states:
        (S_out, C_out, m_out, G_out, h_out,
         Sc_out, Cc_out, mc_out, Gc_out, hc_out,
         S, C, m, G, h) = rest
    else:
        (S_out, C_out, m_out, G_out, h_out, S, C, m, G, h) = rest
    c = pl.program_id(1)
    f32 = jnp.float32

    @pl.when(c == 0)
    def _init():
        if has_init:
            S[...] = S0_in[0].astype(f32)
            C[...] = C0_in[0].astype(f32)
            m[...] = m0_in[0].astype(f32)
            G[...] = G0_in[0].astype(f32)
            h[...] = h0_in[0].astype(f32)
        else:
            S[...] = jnp.zeros_like(S)
            C[...] = jnp.zeros_like(C)
            m[...] = jnp.zeros_like(m)
            G[...] = jnp.zeros_like(G)
            h[...] = jnp.zeros_like(h)

    Q = q_ref[0].astype(f32)  # (w, d)
    K = k_ref[0].astype(f32)
    V = v_ref[0].astype(f32)
    g = gamma_ref[0] if has_decay else jnp.ones((), f32)  # (1, 1)

    state0 = (S[...], C[...], m[...], G[...], h[...])
    if save_states:
        # checkpoint the *incoming* state — exactly what the reverse walk
        # needs to recompute this chunk.
        Sc_out[0, 0] = state0[0]
        Cc_out[0, 0] = state0[1]
        mc_out[0, 0] = state0[2]
        Gc_out[0, 0] = state0[3]
        hc_out[0, 0] = state0[4]

    o, state1 = hla2_chunk_math(
        Q, K, V, state0, g, normalize=normalize, eps=eps, lam=lam
    )
    o_ref[0, :, :] = o.astype(o_ref.dtype)
    S[...], C[...], m[...], G[...], h[...] = state1

    @pl.when(c == n_chunks - 1)
    def _write_state():
        S_out[0] = S[...].astype(S_out.dtype)
        C_out[0] = C[...].astype(C_out.dtype)
        m_out[0] = m[...].astype(m_out.dtype)
        G_out[0] = G[...].astype(G_out.dtype)
        h_out[0] = h[...].astype(h_out.dtype)


def hla2_chunk_pallas(
    q: jax.Array,  # (BH, n, d)
    k: jax.Array,  # (BH, n, d)
    v: jax.Array,  # (BH, n, dv)
    gamma: jax.Array | None = None,  # (BH,) or None
    *,
    chunk: int = 128,
    normalize: bool = False,
    eps: float = 1e-6,
    lam: float = 0.0,
    interpret: bool = False,
    save_chunk_states: bool = False,
    initial_state=None,
):
    """Fused forward.  Returns ``(o, (S, C, m, G, h))`` final state per row,
    plus the per-chunk incoming-state checkpoint tuple (shapes
    ``(BH, nc, ...)``) when ``save_chunk_states=True``.

    ``initial_state`` is an optional ``(S, C, m, G, h)`` carry per row
    (shapes ``(BH, d, d) / (BH, d, dv) / (BH, d) / (BH, d, dv) / (BH, d)``)
    the chunk walk resumes from — this is how a whole prompt prefills in a
    single chunk-parallel call that exactly reproduces the serial
    recurrence (the Section-4 identity; used by the serving engine).

    Arbitrary ``n``: inputs are zero-padded up to a chunk multiple and the
    output sliced back to ``n`` (the checkpoint tuple keeps the padded
    chunk count — feed it unchanged to ``hla2_chunk_bwd_pallas``).

    ``interpret=True`` runs the kernel body in Python (CPU tests); the
    default compiles it for the TPU and fails elsewhere.
    """
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
        _hla2_chunk_kernel,
        w=w,
        normalize=normalize,
        eps=eps,
        lam=lam,
        has_decay=has_decay,
        has_init=has_init,
        n_chunks=nc,
        save_states=save_chunk_states,
    )
    state_shapes = _state_shapes(d, dv)
    out_shape = [
        jax.ShapeDtypeStruct((BH, np_, dv), v.dtype),
    ] + [
        jax.ShapeDtypeStruct((BH,) + s, jnp.float32) for s in state_shapes
    ]
    state_spec = lambda a, b: pl.BlockSpec(  # noqa: E731
        (1, a, b), lambda i, c: (i, 0, 0)
    )
    grid = (BH, nc)
    in_specs = [
            pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),  # gamma
            pl.BlockSpec((1, w, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, w, d), lambda i, c: (i, c, 0)),
            pl.BlockSpec((1, w, dv), lambda i, c: (i, c, 0)),
    ]
    inputs = [gamma_in, q, k, v]
    if has_init:
        S0, C0, m0, G0, h0 = initial_state
        inputs += [
            S0.astype(jnp.float32),
            C0.astype(jnp.float32),
            m0.reshape(BH, 1, d).astype(jnp.float32),
            G0.astype(jnp.float32),
            h0.reshape(BH, 1, d).astype(jnp.float32),
        ]
        in_specs += [state_spec(a, b) for a, b in state_shapes]
    out_specs = [
            pl.BlockSpec((1, w, dv), lambda i, c: (i, c, 0)),
    ] + [state_spec(a, b) for a, b in state_shapes]
    if save_chunk_states:
        out_shape += [
            jax.ShapeDtypeStruct((BH, nc) + s, jnp.float32)
            for s in state_shapes
        ]
        out_specs += [
            pl.BlockSpec((1, 1) + s, lambda i, c: (i, c, 0, 0))
            for s in state_shapes
        ]
    scratch_shapes = [pltpu.VMEM(s, jnp.float32) for s in state_shapes]
    outs = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="hla2_chunk_fwd",
        compiler_params=_compiler_params(interpret),
    )(*inputs)
    o, S, C, m, G, h = outs[:6]
    o = o[:, :n]
    state = _unscale_padded_state((S, C, m[:, 0], G, h[:, 0]), gamma, pad)
    if save_chunk_states:
        return o, state, tuple(outs[6:])
    return o, state


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------


def _hla2_chunk_bwd_kernel(
    # inputs
    gamma_ref,  # (1, 1, 1) f32
    q_ref,  # (1, w, d)   — chunk nc-1-c (reversed walk)
    k_ref,
    v_ref,
    Sc_ref,  # (1, 1, d, d)   checkpointed incoming state of this chunk
    Cc_ref,  # (1, 1, d, dv)
    mc_ref,  # (1, 1, 1, d)
    Gc_ref,  # (1, 1, d, dv)
    hc_ref,  # (1, 1, 1, d)
    do_ref,  # (1, w, dv)
    # outputs
    dq_ref,  # (1, w, d)
    dk_ref,
    dv_ref,
    dg_ref,  # (1, 1, 1) f32
    # scratch: reverse-mode state cotangents + dgamma accumulator
    dS,  # (d, d) f32
    dC,  # (d, dv)
    dm,  # (1, d)
    dG,  # (d, dv)
    dh,  # (1, d)
    dg_acc,  # (1, 1)
    *,
    w: int,
    normalize: bool,
    eps: float,
    lam: float,
    has_decay: bool,
    n_chunks: int,
):
    c = pl.program_id(1)  # grid step; actual chunk index is nc-1-c
    f32 = jnp.float32

    @pl.when(c == 0)
    def _init():
        # the forward discards the final carry, so its cotangent is zero
        dS[...] = jnp.zeros_like(dS)
        dC[...] = jnp.zeros_like(dC)
        dm[...] = jnp.zeros_like(dm)
        dG[...] = jnp.zeros_like(dG)
        dh[...] = jnp.zeros_like(dh)
        dg_acc[...] = jnp.zeros_like(dg_acc)

    Q = q_ref[0].astype(f32)
    K = k_ref[0].astype(f32)
    V = v_ref[0].astype(f32)
    dO = do_ref[0].astype(f32)
    state0 = (Sc_ref[0, 0], Cc_ref[0, 0], mc_ref[0, 0], Gc_ref[0, 0],
              hc_ref[0, 0])
    dstate1 = (dS[...], dC[...], dm[...], dG[...], dh[...])

    if has_decay:
        g = gamma_ref[0]  # (1, 1)
        _, vjp = jax.vjp(
            functools.partial(
                hla2_chunk_math, normalize=normalize, eps=eps, lam=lam
            ),
            Q, K, V, state0, g,
        )
        dQ, dK, dV, dstate0, dgc = vjp((dO, dstate1))
        dg_acc[...] += dgc
    else:
        one = jnp.ones((), f32)
        _, vjp = jax.vjp(
            lambda q_, k_, v_, st_: hla2_chunk_math(
                q_, k_, v_, st_, one, normalize=normalize, eps=eps, lam=lam
            ),
            Q, K, V, state0,
        )
        dQ, dK, dV, dstate0 = vjp((dO, dstate1))

    dq_ref[0] = dQ.astype(dq_ref.dtype)
    dk_ref[0] = dK.astype(dk_ref.dtype)
    dv_ref[0] = dV.astype(dv_ref.dtype)
    dS[...], dC[...], dm[...], dG[...], dh[...] = dstate0

    @pl.when(c == n_chunks - 1)
    def _write_dg():
        dg_ref[0] = dg_acc[...]


def hla2_chunk_bwd_pallas(
    q: jax.Array,  # (BH, n, d)
    k: jax.Array,
    v: jax.Array,  # (BH, n, dv)
    gamma: jax.Array | None,
    do: jax.Array,  # (BH, n, dv) output cotangent
    chunk_states,  # per-chunk incoming states from the forward (padded nc)
    *,
    chunk: int = 128,
    normalize: bool = False,
    eps: float = 1e-6,
    lam: float = 0.0,
    interpret: bool = False,
):
    """Fused backward: reverse chunk walk with checkpointed states.

    Returns ``(dq, dk, dv, dgamma)`` (``dgamma`` is None iff gamma is None).
    """
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
        "returned by hla2_chunk_pallas(save_chunk_states=True) unchanged"
    )
    has_decay = gamma is not None
    gamma_in = gamma_rows(gamma, BH)

    kernel = functools.partial(
        _hla2_chunk_bwd_kernel,
        w=w,
        normalize=normalize,
        eps=eps,
        lam=lam,
        has_decay=has_decay,
        n_chunks=nc,
    )
    state_shapes = _state_shapes(d, dv_)
    grid = (BH, nc)
    rev_blk = lambda i, c: (i, nc - 1 - c, 0)  # noqa: E731
    rev_st = lambda i, c: (i, nc - 1 - c, 0, 0)  # noqa: E731
    in_specs = [
        pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),  # gamma
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, dv_), rev_blk),
    ] + [
        pl.BlockSpec((1, 1) + s, rev_st) for s in state_shapes
    ] + [
        pl.BlockSpec((1, w, dv_), rev_blk),  # do
    ]
    out_specs = [
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, d), rev_blk),
        pl.BlockSpec((1, w, dv_), rev_blk),
        pl.BlockSpec((1, 1, 1), lambda i, c: (i, 0, 0)),  # dgamma
    ]
    out_shape = [
        jax.ShapeDtypeStruct((BH, np_, d), q.dtype),
        jax.ShapeDtypeStruct((BH, np_, d), k.dtype),
        jax.ShapeDtypeStruct((BH, np_, dv_), v.dtype),
        jax.ShapeDtypeStruct((BH, 1, 1), jnp.float32),
    ]
    scratch_shapes = [pltpu.VMEM(s, jnp.float32) for s in state_shapes]
    scratch_shapes.append(pltpu.VMEM((1, 1), jnp.float32))
    dq, dk, dv, dg = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch_shapes,
        interpret=interpret,
        name="hla2_chunk_bwd",
        compiler_params=_compiler_params(interpret),
    )(gamma_in, q, k, v, *chunk_states, do)
    dq, dk, dv = dq[:, :n], dk[:, :n], dv[:, :n]
    dgamma = dg[:, 0, 0] if has_decay else None
    return dq, dk, dv, dgamma

"""Jit'd public wrappers around the Pallas kernels.

``hla2_attention`` / ``ahla_attention`` take model-layout tensors
``(B, H, n, d)`` and dispatch to the fused Pallas kernels for **both**
passes of training:

* **Forward**: the chunkwise kernel carries the inter-chunk state in VMEM
  scratch; under differentiation it additionally spills each chunk's
  *incoming* state tuple to HBM (``nc ×`` constant-size state — the
  chunk-level checkpointing trade: O(n/w · d·dv) extra memory buys back a
  full unfused recompute forward).
* **Backward** (``fused_bwd=True``, the default): a second Pallas kernel
  walks the chunk axis in reverse, recomputes the intra-chunk tiles from
  ``q/k/v`` plus the checkpointed state, and accumulates ``dq/dk/dv/dgamma``
  with the reverse-mode state cotangents living in VMEM scratch.  Gradients
  are exact: the backward differentiates the *same* per-chunk math
  (``chunk_math.py``) the forward kernel executes.
* ``fused_bwd=False`` restores the legacy recompute-in-backward design
  (``jax.vjp`` over the pure-jnp chunkwise reference — a second,
  XLA-scheduled forward whose carried state round-trips through HBM).
* ``use_pallas=False`` falls back to the reference end to end (used on CPU
  training runs).  ``interpret=True`` runs the kernels in Pallas interpret
  mode (how the CPU tests exercise them); by default they compile for the
  TPU, and fail on any other backend rather than fall back in silence.

Arbitrary sequence lengths are supported: the kernel wrappers zero-pad to
a chunk multiple and slice the results back.
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp

from . import ref as _ref
from ..core.ahla import AHLAState, ahla_chunkwise
from ..core.hla2 import HLA2State, hla2_chunkwise
from .ahla_chunk import ahla_chunk_bwd_pallas, ahla_chunk_pallas
from .decode_step import ahla_step_pallas, hla2_step_pallas
from .hla2_chunk import hla2_chunk_bwd_pallas, hla2_chunk_pallas


# Trace-time dispatch counters: incremented whenever a Pallas path is
# *traced* (wrapper Python runs under jit/shard_map tracing).  The
# distributed tests use these to assert the sharded train step really
# lowered the fused kernels rather than the jnp fallback.
TRACE_COUNTS = collections.Counter()


def _merge_bh(x):
    B, H = x.shape[:2]
    return x.reshape((B * H,) + x.shape[2:]), B, H


# --------------------------------------------------------------------------
# HLA2
# --------------------------------------------------------------------------


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10)
)
def _hla2_fwd_core(
    q, k, v, gamma, chunk, normalize, eps, lam, use_pallas, fused_bwd,
    interpret,
):
    if use_pallas:
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gf = None if gamma is None else gamma.reshape(B * H)
        o, _ = hla2_chunk_pallas(
            qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps, lam=lam,
            interpret=interpret,
        )
        return o.reshape(q.shape[:2] + o.shape[1:])
    o, _ = _ref.hla2_chunk_ref(
        q, k, v, gamma, chunk=chunk, normalize=normalize, eps=eps, lam=lam
    )
    return o


def _hla2_vjp_fwd(
    q, k, v, gamma, chunk, normalize, eps, lam, use_pallas, fused_bwd,
    interpret,
):
    if use_pallas and fused_bwd:
        # fused training path: forward checkpoints per-chunk incoming states
        TRACE_COUNTS["hla2_fwd_fused"] += 1
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gf = None if gamma is None else gamma.reshape(B * H)
        o, _, chunk_states = hla2_chunk_pallas(
            qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps,
            lam=lam, save_chunk_states=True, interpret=interpret,
        )
        out = o.reshape(q.shape[:2] + o.shape[1:])
        return out, (q, k, v, gamma, chunk_states)
    out = _hla2_fwd_core(
        q, k, v, gamma, chunk, normalize, eps, lam, use_pallas, fused_bwd,
        interpret,
    )
    return out, (q, k, v, gamma, None)


def _hla2_vjp_bwd(chunk, normalize, eps, lam, use_pallas, fused_bwd,
                  interpret, res, g):
    q, k, v, gamma, chunk_states = res

    if use_pallas and fused_bwd:
        TRACE_COUNTS["hla2_bwd_fused"] += 1
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gof, _, _ = _merge_bh(g)
        gf = None if gamma is None else gamma.reshape(B * H)
        dq, dk, dv, dgamma = hla2_chunk_bwd_pallas(
            qf, kf, vf, gf, gof, chunk_states, chunk=chunk,
            normalize=normalize, eps=eps, lam=lam, interpret=interpret,
        )
        unmerge = lambda x, p: x.reshape(p.shape).astype(p.dtype)  # noqa: E731
        dgamma = (
            None if gamma is None
            else dgamma.reshape(gamma.shape).astype(gamma.dtype)
        )
        return unmerge(dq, q), unmerge(dk, k), unmerge(dv, v), dgamma

    # legacy recompute-in-backward: differentiate the jnp chunkwise reference
    def f(q_, k_, v_, gamma_):
        o, _ = _ref.hla2_chunk_ref(
            q_, k_, v_, gamma_, chunk=chunk, normalize=normalize, eps=eps,
            lam=lam,
        )
        return o

    if gamma is None:
        _, vjp = jax.vjp(lambda a, b, c: f(a, b, c, None), q, k, v)
        return (*vjp(g), None)
    _, vjp = jax.vjp(f, q, k, v, gamma)
    return vjp(g)


_hla2_fwd_core.defvjp(_hla2_vjp_fwd, _hla2_vjp_bwd)


def hla2_attention(
    q, k, v, gamma=None, *, chunk: int = 128, normalize: bool = False,
    eps: float = 1e-6, lam: float = 0.0, use_pallas: bool = True,
    fused_bwd: bool = True, interpret: bool = False,
):
    """Masked second-order HLA over (B, H, n, d) tensors (fused fwd + bwd)."""
    return _hla2_fwd_core(
        q, k, v, gamma, chunk, normalize, eps, lam, use_pallas, fused_bwd,
        interpret,
    )


# --------------------------------------------------------------------------
# AHLA
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ahla_fwd_core(q, k, v, gamma, chunk, normalize, eps, use_pallas,
                   fused_bwd, interpret):
    if use_pallas:
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gf = None if gamma is None else gamma.reshape(B * H)
        o, _ = ahla_chunk_pallas(
            qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps,
            interpret=interpret,
        )
        return o.reshape(q.shape[:2] + o.shape[1:])
    o, _ = _ref.ahla_chunk_ref(
        q, k, v, gamma, chunk=chunk, normalize=normalize, eps=eps
    )
    return o


def _ahla_vjp_fwd(q, k, v, gamma, chunk, normalize, eps, use_pallas,
                  fused_bwd, interpret):
    if use_pallas and fused_bwd:
        TRACE_COUNTS["ahla_fwd_fused"] += 1
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gf = None if gamma is None else gamma.reshape(B * H)
        o, _, chunk_states = ahla_chunk_pallas(
            qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps,
            save_chunk_states=True, interpret=interpret,
        )
        out = o.reshape(q.shape[:2] + o.shape[1:])
        return out, (q, k, v, gamma, chunk_states)
    out = _ahla_fwd_core(
        q, k, v, gamma, chunk, normalize, eps, use_pallas, fused_bwd,
        interpret,
    )
    return out, (q, k, v, gamma, None)


def _ahla_vjp_bwd(chunk, normalize, eps, use_pallas, fused_bwd, interpret,
                  res, g):
    q, k, v, gamma, chunk_states = res

    if use_pallas and fused_bwd:
        TRACE_COUNTS["ahla_bwd_fused"] += 1
        qf, B, H = _merge_bh(q)
        kf, _, _ = _merge_bh(k)
        vf, _, _ = _merge_bh(v)
        gof, _, _ = _merge_bh(g)
        gf = None if gamma is None else gamma.reshape(B * H)
        dq, dk, dv, dgamma = ahla_chunk_bwd_pallas(
            qf, kf, vf, gf, gof, chunk_states, chunk=chunk,
            normalize=normalize, eps=eps, interpret=interpret,
        )
        unmerge = lambda x, p: x.reshape(p.shape).astype(p.dtype)  # noqa: E731
        dgamma = (
            None if gamma is None
            else dgamma.reshape(gamma.shape).astype(gamma.dtype)
        )
        return unmerge(dq, q), unmerge(dk, k), unmerge(dv, v), dgamma

    def f(q_, k_, v_, gamma_):
        o, _ = _ref.ahla_chunk_ref(
            q_, k_, v_, gamma_, chunk=chunk, normalize=normalize, eps=eps
        )
        return o

    if gamma is None:
        _, vjp = jax.vjp(lambda a, b, c: f(a, b, c, None), q, k, v)
        return (*vjp(g), None)
    _, vjp = jax.vjp(f, q, k, v, gamma)
    return vjp(g)


_ahla_fwd_core.defvjp(_ahla_vjp_fwd, _ahla_vjp_bwd)


def ahla_attention(
    q, k, v, gamma=None, *, chunk: int = 128, normalize: bool = False,
    eps: float = 1e-6, use_pallas: bool = True, fused_bwd: bool = True,
    interpret: bool = False,
):
    """AHLA over (B, H, n, d) tensors (fused fwd + bwd)."""
    return _ahla_fwd_core(
        q, k, v, gamma, chunk, normalize, eps, use_pallas, fused_bwd,
        interpret,
    )


# --------------------------------------------------------------------------
# Inference: chunk-parallel prefill + fused batched decode steps
# --------------------------------------------------------------------------
#
# ``*_prefill`` runs a whole prompt through ONE chunk-parallel kernel call,
# optionally resuming from a carry, and returns the final streaming state —
# exactly the serial recurrence by the paper's Section-4 identity (no
# per-token loop, no approximation).  ``*_decode_step`` applies one token of
# the streaming recurrence to every (batch, head) row in a single fused
# launch with in-place state update.  Both are inference-only (no VJP) and
# keep a pure-jnp fallback (CPU / correctness oracle).


def hla2_prefill(
    q, k, v, gamma=None, *, state: HLA2State | None = None,
    chunk: int = 128, normalize: bool = False, eps: float = 1e-6,
    lam: float = 0.0, use_pallas: bool = True, interpret: bool = False,
):
    """Chunk-parallel HLA2 prefill over (B, H, n, d).  Returns
    ``(o, HLA2State)`` — the state decodes onward via ``hla2_decode_step``."""
    if not use_pallas:
        return hla2_chunkwise(
            q, k, v, gamma, chunk=chunk, normalize=normalize, eps=eps,
            lam=lam, state=state,
        )
    TRACE_COUNTS["hla2_prefill"] += 1
    qf, B, H = _merge_bh(q)
    kf, _, _ = _merge_bh(k)
    vf, _, _ = _merge_bh(v)
    gf = None if gamma is None else (
        jnp.broadcast_to(jnp.asarray(gamma), (B, H)).reshape(B * H)
    )
    init = None
    if state is not None:
        init = tuple(
            x.astype(jnp.float32).reshape((B * H,) + x.shape[2:])
            for x in state
        )
    o, (S, C, m, G, h) = hla2_chunk_pallas(
        qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps, lam=lam,
        initial_state=init, interpret=interpret,
    )
    o = o.reshape(q.shape[:2] + o.shape[1:])
    unm = lambda x: x.reshape((B, H) + x.shape[1:])  # noqa: E731
    return o, HLA2State(unm(S), unm(C), unm(m), unm(G), unm(h))


def ahla_prefill(
    q, k, v, gamma=None, *, state: AHLAState | None = None,
    chunk: int = 128, normalize: bool = False, eps: float = 1e-6,
    use_pallas: bool = True, interpret: bool = False,
):
    """Chunk-parallel AHLA prefill over (B, H, n, d).  Returns
    ``(o, AHLAState)``.  The undecayed cross moment ``R`` (scan-only
    bookkeeping, unused by decode outputs) accumulates outside the kernel."""
    if not use_pallas:
        return ahla_chunkwise(
            q, k, v, gamma, chunk=chunk, normalize=normalize, eps=eps,
            state=state,
        )
    TRACE_COUNTS["ahla_prefill"] += 1
    qf, B, H = _merge_bh(q)
    kf, _, _ = _merge_bh(k)
    vf, _, _ = _merge_bh(v)
    gf = None if gamma is None else (
        jnp.broadcast_to(jnp.asarray(gamma), (B, H)).reshape(B * H)
    )
    init = None
    R0 = None
    if state is not None:
        R0 = state.R
        init = tuple(
            x.astype(jnp.float32).reshape((B * H,) + x.shape[2:])
            for x in (state.P, state.m, state.E, state.n)
        )
    o, (P, m, E, n) = ahla_chunk_pallas(
        qf, kf, vf, gf, chunk=chunk, normalize=normalize, eps=eps,
        initial_state=init, interpret=interpret,
    )
    o = o.reshape(q.shape[:2] + o.shape[1:])
    unm = lambda x: x.reshape((B, H) + x.shape[1:])  # noqa: E731
    f32 = jnp.float32
    R = jnp.einsum(
        "bhtd,bhte->bhde", k.astype(f32), q.astype(f32)
    )
    if R0 is not None:
        R = R + R0.astype(f32)
    return o, AHLAState(R, unm(P), unm(m), unm(E), unm(n))


def hla2_decode_step(
    state: HLA2State, q_t, k_t, v_t, gamma=None, *,
    normalize: bool = False, eps: float = 1e-6, lam: float = 0.0,
    use_pallas: bool = True, interpret: bool = False,
):
    """One fused decode token over (..., d) rows.  Returns ``(state, o_t)``."""
    if not use_pallas:
        from ..core.hla2 import hla2_step

        return hla2_step(
            state, q_t, k_t, v_t, gamma, normalize=normalize, eps=eps,
            lam=lam,
        )
    TRACE_COUNTS["hla2_decode_step"] += 1
    new_state, o = hla2_step_pallas(
        tuple(state), q_t, k_t, v_t, gamma, normalize=normalize, eps=eps,
        lam=lam, interpret=interpret,
    )
    return HLA2State(*new_state), o


def ahla_decode_step(
    state: AHLAState, q_t, k_t, v_t, gamma=None, *,
    normalize: bool = False, eps: float = 1e-6, use_pallas: bool = True,
    interpret: bool = False,
):
    """One fused AHLA decode token.  Returns ``(state, o_t)``."""
    if not use_pallas:
        from ..core.ahla import ahla_step

        return ahla_step(
            state, q_t, k_t, v_t, gamma, normalize=normalize, eps=eps
        )
    TRACE_COUNTS["ahla_decode_step"] += 1
    new_state, o = ahla_step_pallas(
        tuple(state), q_t, k_t, v_t, gamma, normalize=normalize, eps=eps,
        interpret=interpret,
    )
    return AHLAState(*new_state), o

"""The HLA mixer sublayer — the paper's drop-in attention replacement (§5.2).

Multi-head projections around the core operators (hla2 / ahla / hla3 /
hla3_paper / linattn), with:

* per-head learnable decay gamma = sigmoid(a)  (cfg.hla.decay = "learned"),
  or a fixed scalar ("fixed"), or none ("none");
* GQA/MQA: K, V projected at n_kv_heads and broadcast to q heads — with
  ``share_kv_state`` the decode state stores S^K once per KV group (§5.2);
* optional ratio normalization (Eq. 3.4) and ridge lam (Alg. 1);
* per-head RMS output norm (standard practice for unnormalized linear
  attention outputs; paper is silent on output scaling — documented in
  DESIGN.md §7);
* training path: fused Pallas kernels for forward AND backward (TPU; the
  backward walks checkpointed chunk states in reverse — cfg.hla.fused_bwd,
  DESIGN.md §3) or jnp chunkwise (CPU);
* decode path: O(1)-state streaming steps (view A).

Each variant is registered as ONE ``seq_op.SequenceOp`` record
(DESIGN.md §11); the old five ``variant ==`` ladders are gone.  The
Pallas-vs-jnp selection and the ``shard_ops.call_sharded`` mesh dispatch
live inside each record's forward/step — lm / serving / distributed
callers never see them.  ``mixer_apply``/``mixer_step``/
``mixer_init_state``/``mixer_state_axes`` remain as thin registry-backed
wrappers for direct (test / example) callers.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# NOTE: ``repro.core.__init__`` re-exports functions named like the
# submodules (``hla2``...), so module-level imports would grab the
# function.  Bind the submodules through sys.modules instead.
import importlib

core_ahla = importlib.import_module("repro.core.ahla")
core_hla2 = importlib.import_module("repro.core.hla2")
core_hla3 = importlib.import_module("repro.core.hla3")
core_lin = importlib.import_module("repro.core.linear_attn")
from ..kernels import ops as kops
from ..distributed import shard_ops
from ..distributed.sharding import constrain
from . import seq_op
from .blocks import dense_apply, dense_specs
from .param import Axes, Spec


def mixer_specs(cfg):
    d, H, Hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    s = {
        "wq": dense_specs(d, H * dh, axes=("embed", "q_heads_flat"), bias=cfg.qkv_bias),
        "wk": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat"), bias=cfg.qkv_bias),
        "wv": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat"), bias=cfg.qkv_bias),
        "wo": dense_specs(H * dh, d, axes=("q_heads_flat", "embed")),
        "out_scale": Spec((H, dh), ("q_heads", "head_dim"), init="ones"),
    }
    if cfg.hla.decay == "learned":
        s["decay_a"] = Spec((H,), ("q_heads",), init="constant", const=3.0)
    return s


def _gamma(p, cfg, B):
    if cfg.hla.decay == "none":
        return None
    if cfg.hla.decay == "fixed":
        g = jnp.full((cfg.n_heads,), cfg.hla.fixed_gamma, jnp.float32)
    else:
        g = jax.nn.sigmoid(p["decay_a"].astype(jnp.float32))
    return jnp.broadcast_to(g[None], (B, cfg.n_heads))


def _project(p, x, cfg):
    B, n, _ = x.shape
    H, Hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense_apply(p["wq"], x).reshape(B, n, H, dh).swapaxes(1, 2)
    k = dense_apply(p["wk"], x).reshape(B, n, Hk, dh).swapaxes(1, 2)
    v = dense_apply(p["wv"], x).reshape(B, n, Hk, dh).swapaxes(1, 2)
    q = q * (dh**-0.5)
    if Hk != H:  # GQA: broadcast KV heads to query heads
        rep = H // Hk
        k = jnp.repeat(k, rep, axis=1)
        v = jnp.repeat(v, rep, axis=1)
    spec = ("batch", "q_heads", None, None)
    return (constrain(q, spec), constrain(k, spec), constrain(v, spec))


def _out_norm(p, o, cfg, eps=1e-6):
    """Per-head RMS norm + learned scale (stabilizes unnormalized HLA)."""
    o32 = o.astype(jnp.float32)
    var = jnp.mean(o32 * o32, axis=-1, keepdims=True)
    o32 = o32 * jax.lax.rsqrt(var + eps)
    return (o32 * p["out_scale"][None, :, None, :]).astype(o.dtype)


def _pallas_enabled(hc) -> bool:
    """Fused Pallas kernels: native on TPU; elsewhere only when
    ``force_pallas`` asks for them in interpret mode (CPU tests)."""
    return hc.use_pallas and (
        jax.default_backend() == "tpu" or hc.force_pallas
    )


def _kernel_kw(hc, kw):
    """Kernel keywords: ``kw`` plus the interpret mode the config asks for."""
    return dict(kw, interpret=hc.force_pallas)


# output ranks for shard_ops.call_sharded (avoids an eval_shape re-trace
# of the kernel per compile): state leaves are (B, H, d, d)/(B, H, d, dv)
# rank 4 and (B, H, d) rank 3; o is (B, H, n, dv); o_t is (B, H, dv).
_HLA2_STATE_NDIMS = core_hla2.HLA2State(4, 4, 3, 4, 3)
_AHLA_STATE_NDIMS = core_ahla.AHLAState(4, 4, 3, 4, 3)


# --------------------------------------------------------------------------
# per-variant cores: full-sequence forward + one-token step over projected
# (B, H, n, dh) rows.  Pallas/jnp/mesh selection is sealed in here.
# --------------------------------------------------------------------------


def _hla2_fwd(q, k, v, gamma, hc, *, state, want_state, kw):
    if hc.impl == "scan":  # paper-faithful token-level Blelloch
        return core_hla2.hla2_scan(q, k, v, gamma, lam=hc.lam, state=state, **kw)
    if _pallas_enabled(hc) and (want_state or state is not None):
        # one chunk-parallel kernel call prefills the whole prompt and
        # hands back the exact streaming state (Section-4 identity)
        fn = functools.partial(
            kops.hla2_prefill, chunk=hc.chunk, lam=hc.lam, **_kernel_kw(hc, kw)
        )
        return shard_ops.call_sharded(
            lambda q_, k_, v_, g_, s_: fn(q_, k_, v_, g_, state=s_),
            q, k, v, gamma, state,
            out_ndims=(4, _HLA2_STATE_NDIMS),
        )
    if _pallas_enabled(hc):
        o = shard_ops.call_sharded(
            functools.partial(
                kops.hla2_attention, chunk=hc.chunk, lam=hc.lam,
                fused_bwd=hc.fused_bwd, **_kernel_kw(hc, kw)
            ),
            q, k, v, gamma, out_ndims=4,
        )
        return o, None
    return core_hla2.hla2_chunkwise(
        q, k, v, gamma, chunk=hc.chunk, lam=hc.lam, state=state, **kw
    )


def _hla2_step(state, q1, k1, v1, gamma, hc, kw):
    if _pallas_enabled(hc):
        return shard_ops.call_sharded(
            functools.partial(
                kops.hla2_decode_step, lam=hc.lam, **_kernel_kw(hc, kw)
            ),
            state, q1, k1, v1, gamma,
            out_ndims=(_HLA2_STATE_NDIMS, 3),
        )
    return core_hla2.hla2_step(state, q1, k1, v1, gamma, lam=hc.lam, **kw)


def _ahla_fwd(q, k, v, gamma, hc, *, state, want_state, kw):
    if hc.impl == "scan":
        return core_ahla.ahla_scan(q, k, v, gamma, state=state, **kw)
    if _pallas_enabled(hc) and (want_state or state is not None):
        fn = functools.partial(
            kops.ahla_prefill, chunk=hc.chunk, **_kernel_kw(hc, kw)
        )
        return shard_ops.call_sharded(
            lambda q_, k_, v_, g_, s_: fn(q_, k_, v_, g_, state=s_),
            q, k, v, gamma, state,
            out_ndims=(4, _AHLA_STATE_NDIMS),
        )
    if _pallas_enabled(hc):
        o = shard_ops.call_sharded(
            functools.partial(
                kops.ahla_attention, chunk=hc.chunk,
                fused_bwd=hc.fused_bwd, **_kernel_kw(hc, kw)
            ),
            q, k, v, gamma, out_ndims=4,
        )
        return o, None
    return core_ahla.ahla_chunkwise(
        q, k, v, gamma, chunk=hc.chunk, state=state, **kw
    )


def _ahla_step(state, q1, k1, v1, gamma, hc, kw):
    if _pallas_enabled(hc):
        return shard_ops.call_sharded(
            functools.partial(kops.ahla_decode_step, **_kernel_kw(hc, kw)),
            state, q1, k1, v1, gamma,
            out_ndims=(_AHLA_STATE_NDIMS, 3),
        )
    return core_ahla.ahla_step(state, q1, k1, v1, gamma, **kw)


def _hla3_fwd(q, k, v, gamma, hc, *, state, want_state, kw):
    return core_hla3.hla3_exact_chunkwise(
        q, k, v, gamma, chunk=hc.chunk, state=state, **kw
    )


def _hla3_step(state, q1, k1, v1, gamma, hc, kw):
    return core_hla3.hla3_exact_step(state, q1, k1, v1, gamma, **kw)


def _hla3_paper_fwd(q, k, v, gamma, hc, *, state, want_state, kw):
    return core_hla3.hla3_paper_chunkwise(
        q, k, v, chunk=hc.chunk, state=state, **kw
    )


def _hla3_paper_step(state, q1, k1, v1, gamma, hc, kw):
    # n=1 chunkwise call: same state layout AND same gamma=1 semantics
    # as the prefill path (the Alg.-3 step applied learned decay that
    # the chunk path never saw — prefill-then-decode diverged)
    return core_hla3.hla3_paper_chunk_step(state, q1, k1, v1, **kw)


def _linattn_fwd(q, k, v, gamma, hc, *, state, want_state, kw):
    return core_lin.linattn_chunkwise(
        q, k, v, gamma, chunk=hc.chunk, state=state, **kw
    )


def _linattn_step(state, q1, k1, v1, gamma, hc, kw):
    return core_lin.linattn_step(state, q1, k1, v1, gamma, **kw)


# --------------------------------------------------------------------------
# record assembly: shared projection/out-norm wrapper around each core
# --------------------------------------------------------------------------


def _sublayer_forward(core_fwd):
    def forward(p, x, cfg, *, state=None, want_state=False, positions=None):
        """Training/prefill path over a full sequence.  Returns
        (out, final_state); ``state`` is an optional streaming carry to
        resume from (incremental prefill)."""
        B, n, _ = x.shape
        hc = cfg.hla
        q, k, v = _project(p, x, cfg)
        gamma = _gamma(p, cfg, B)
        kw = dict(normalize=hc.normalize, eps=1e-6)
        o, st = core_fwd(q, k, v, gamma, hc, state=state,
                         want_state=want_state, kw=kw)
        o = _out_norm(p, o.astype(x.dtype), cfg)
        o = o.swapaxes(1, 2).reshape(B, n, cfg.n_heads * cfg.head_dim)
        o = constrain(o, ("batch", None, "q_heads_flat"))
        return dense_apply(p["wo"], o), st

    return forward


def _sublayer_step(core_step):
    def step(p, x_t, state, cfg, *, positions=None):
        """One-token decode.  x_t: (B, 1, d).  Returns (out, new_state).

        On TPU the hla2/ahla state update runs as ONE fused Pallas launch
        over all (batch, head) rows with in-place state I/O
        (kernels/decode_step.py); jnp steps remain the CPU path.
        """
        B = x_t.shape[0]
        hc = cfg.hla
        q, k, v = _project(p, x_t, cfg)  # (B, H, 1, dh)
        q1, k1, v1 = q[..., 0, :], k[..., 0, :], v[..., 0, :]
        gamma = _gamma(p, cfg, B)
        kw = dict(normalize=hc.normalize, eps=1e-6)
        state, o = core_step(state, q1, k1, v1, gamma, hc, kw)
        o = o[..., None, :]  # (B, H, 1, dh)
        o = _out_norm(p, o.astype(x_t.dtype), cfg)
        o = o.swapaxes(1, 2).reshape(B, 1, cfg.n_heads * cfg.head_dim)
        return dense_apply(p["wo"], o), state

    return step


def _mixer_init(core_init):
    def init_state(cfg, B, *, max_len=0, dtype=None):
        dh = cfg.head_dim
        return core_init(
            (B, cfg.n_heads), dh, dh,
            jnp.float32 if dtype is None else dtype,
        )

    return init_state


# Per-variant state axes: every HLA-family decode-state leaf is a
# ``(batch, heads, ...feature)`` row tensor, declared field-by-field so
# each variant's layout is EXPLICIT (hla3/hla3_paper included — rank-based
# inference silently depended on every future state leaf happening to
# follow the row layout).  Heads shard on "model" exactly like the kernel
# row grid; consumed via ``SequenceOp.state_axes`` by
# ``distributed.steps.state_specs`` and the serving ``StatePool``.
_ROW_MAT = Axes(("batch", "q_heads", None, None))
_ROW_VEC = Axes(("batch", "q_heads", None))

_HLA2_AXES = core_hla2.HLA2State(
    S=_ROW_MAT, C=_ROW_MAT, m=_ROW_VEC, G=_ROW_MAT, h=_ROW_VEC
)
_LINATTN_AXES = core_lin.LinAttnState(P=_ROW_MAT, m=_ROW_VEC)


def _register(name, core_fwd, core_step, core_init, axes, ndims=None,
              fused=False):
    seq_op.register_op(seq_op.SequenceOp(
        name=name,
        specs=mixer_specs,
        forward=_sublayer_forward(core_fwd),
        step=_sublayer_step(core_step),
        init_state=_mixer_init(core_init),
        state_axes=lambda cfg, _axes=axes: _axes,
        state_ndims=(None if ndims is None else (lambda cfg, _n=ndims: _n)),
        streaming=True,
        has_fused_kernels=fused,
        spec_decodable=True,
        param_key="mixer",
    ))


_register("hla2", _hla2_fwd, _hla2_step, core_hla2.hla2_init_state,
          _HLA2_AXES, ndims=_HLA2_STATE_NDIMS, fused=True)
_register("ahla", _ahla_fwd, _ahla_step, core_ahla.ahla_init_state,
          core_ahla.AHLAState(R=_ROW_MAT, P=_ROW_MAT, m=_ROW_VEC,
                              E=_ROW_MAT, n=_ROW_VEC),
          ndims=_AHLA_STATE_NDIMS, fused=True)
_register("hla3", _hla3_fwd, _hla3_step, core_hla3.hla3_exact_init_state,
          core_hla3.HLA3ExactState(inner=_LINATTN_AXES, outer=_HLA2_AXES))
# chunk-state layout: prefill (hla3_paper_chunkwise) and decode
# (hla3_paper_chunk_step) share it; the Algorithm-3 10-field state only
# serves the serial/scan fidelity paths.  Using it here made serving
# impossible: prefill handed back a 6-field carry that could never be
# scattered into a 10-field pool.
_register("hla3_paper", _hla3_paper_fwd, _hla3_paper_step,
          core_hla3.hla3_chunk_init_state,
          core_hla3.HLA3ChunkState(SK=_ROW_MAT, SQ=_ROW_MAT, P=_ROW_MAT,
                                   m=_ROW_VEC, F=_ROW_MAT, eta=_ROW_VEC))
_register("linattn", _linattn_fwd, _linattn_step,
          core_lin.linattn_init_state, _LINATTN_AXES)


# --------------------------------------------------------------------------
# registry-backed wrappers (direct callers: tests, examples, whisper compat)
# --------------------------------------------------------------------------


def mixer_apply(p, x, cfg, want_state: bool = False, state=None):
    """Full-sequence apply through the registered record for ``cfg``."""
    return seq_op.op_for(cfg).forward(
        p, x, cfg, state=state, want_state=want_state
    )


def mixer_step(p, x_t, state, cfg):
    """One-token decode through the registered record for ``cfg``."""
    return seq_op.op_for(cfg).step(p, x_t, state, cfg)


def mixer_init_state(cfg, B, dtype=jnp.float32):
    return seq_op.op_for(cfg).init_state(cfg, B, dtype=dtype)


def mixer_state_axes(cfg):
    """Logical axes pytree matching ``mixer_init_state`` leaf-for-leaf."""
    return seq_op.op_for(cfg).state_axes(cfg)

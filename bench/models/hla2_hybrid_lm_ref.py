"""Plain float32 reference of the hybrid HLA2 + softmax language model
(Olmo Hybrid's layer pattern with masked HLA2 in its linear-attention
layers), and its weights.

This file imports nothing of the program under test; from the benchmark
it takes the HLA2 reference's seed, precision and dense helpers
(``hla2_lm_ref``).  It holds:

* ``weight_shapes`` / ``make_weights``: the model's named weights, made on
  the device from the seed in one jitted call and stored at the
  configuration's ``param_dtype`` (the numbers the model is).  The harness
  hands the same arrays (re-arranged by ``hla2_hybrid_lm.to_program``) to
  the program; the reference makes its own copy from the seed.
* ``forward_hidden`` / ``logits_at``: the forward pass in straightforward
  ``jax.numpy``, every operation in float32 at "highest" precision:
  embedding; per layer RMSNorm -> mixer -> residual -> RMSNorm -> SwiGLU
  -> residual; final RMSNorm; untied output head.  The layer's mixer is
  named by ``layer_types``: ``linear_attention`` is masked second-order
  HLA at key width ``linear_key_head_dim`` and value width
  ``linear_value_head_dim``, ``full_attention`` is causal softmax
  attention without a rotary embedding.

The HLA2 mixer follows the masked second-order form (arXiv 2510.27258,
Sec. 3, with per-head decay g):

    o_t = sum_{i <= j <= t} g^{(t-i)+(t-j)} (q_t . k_i)(k_i . q_j) v_j

computed chunk by chunk: with ``S = sum_i g^{t0-i} k_i k_i^T`` (d x d) and
``M = sum_{i<=j} g^{2 t0-i-j} k_i k_i^T q_j v_j^T`` (d x dv) at a chunk's
start t0, a position t in the chunk reads ``g^{2(t-t0)} q_t M`` (both
indices before the chunk), ``g^{t-t0} sum_{j<=t} g^{t-j} (q_t S q_j) v_j``
(i before, j inside) and the masked intra-chunk product (both inside).
Each head's output is RMS-normalised and scaled (``out_scale``).

Softmax attention runs over blocks of queries against every key, and the
SwiGLU over blocks of rows, so that 25,600 positions fit beside the
weights.  ``lowp="fp8"`` rounds the dense operands and the mixers' q/k/v
to float8 e4m3 (the control one step below bfloat16), as in
``hla2_lm_ref``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.models.hla2_lm_ref import (  # noqa: F401  (seed_key: interface)
    F32, HI, _dense, _rms, _round, seed_key)

LINEAR, FULL = "linear_attention", "full_attention"
Q_BLOCK = 256  # softmax queries per block
ROW_BLOCK = 2048  # SwiGLU rows per block


def dims(c: dict) -> dict:
    d, H = c["hidden_size"], c["num_attention_heads"]
    types = list(c["layer_types"][: c["num_hidden_layers"]])
    return {"L": len(types), "d": d, "ff": c["intermediate_size"],
            "V": c["vocab_size"], "H": H, "Hk": c["num_key_value_heads"],
            "dh": d // H, "Hl": c["linear_num_key_heads"],
            "dk": c["linear_key_head_dim"], "dv": c["linear_value_head_dim"],
            "types": types, "period": period(types)}


def period(types) -> list:
    """The shortest repeating period of ``layer_types`` that tiles it."""
    n = len(types)
    for p in range(1, n + 1):
        if n % p == 0 and types == types[:p] * (n // p):
            return types[:p]
    return types


def weight_shapes(c: dict) -> dict:
    """Named shapes.  Layer weights are stacked over the periods of the
    layer pattern, one name per position ``i`` of the period (``p<i>_``),
    so that layer ``g * period + i`` holds entry ``g`` of each."""
    m = dims(c)
    d, ff, V = m["d"], m["ff"], m["V"]
    G = m["L"] // len(m["period"])
    out = {"embed": (V, d), "final_norm": (d,), "unembed": (d, V)}
    for i, kind in enumerate(m["period"]):
        p = f"p{i}_"
        out.update({p + "ln1": (G, d), p + "ln2": (G, d),
                    p + "w_gate": (G, d, ff), p + "w_up": (G, d, ff),
                    p + "w_down": (G, ff, d)})
        if kind == LINEAR:
            H, dk, dv = m["Hl"], m["dk"], m["dv"]
            out.update({p + "wq": (G, d, H * dk), p + "wk": (G, d, H * dk),
                        p + "wv": (G, d, H * dv), p + "wo": (G, H * dv, d),
                        p + "out_scale": (G, H, dv), p + "decay_a": (G, H)})
        else:
            H, Hk, dh = m["H"], m["Hk"], m["dh"]
            out.update({p + "wq": (G, d, H * dh), p + "wk": (G, d, Hk * dh),
                        p + "wv": (G, d, Hk * dh), p + "wo": (G, H * dh, d)})
    return out


def _init(name: str, shape, key):
    if name.endswith(("ln1", "ln2", "final_norm", "out_scale")):
        return jnp.ones(shape, F32)
    if name.endswith("decay_a"):
        # per-head decay gamma = sigmoid(a) in about [0.88, 0.9975]
        return jax.random.uniform(key, shape, F32, 2.0, 6.0)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, F32)
    return jax.random.normal(key, shape, F32) / math.sqrt(shape[-2])


def make_weights(c: dict, key):
    """Named weights of configuration ``c`` from ``key`` at its
    ``param_dtype``: one leaf per name, each from its own fold of the key.
    Jit-able."""
    dt = jnp.dtype(c["param_dtype"])
    return {
        name: _init(name, shape, jax.random.fold_in(key, i)).astype(dt)
        for i, (name, shape) in enumerate(sorted(weight_shapes(c).items()))
    }


# --------------------------------------------------------------------------
# masked second-order HLA at key width d and value width dv
# --------------------------------------------------------------------------


def hla2(q, k, v, g, chunk: int):
    """(H, n, d) q and k, (H, n, dv) v, (H,) decay -> (H, n, dv) float32
    outputs.  ``n`` is padded with zero rows to a multiple of ``chunk``;
    zero rows add nothing to the summaries and their outputs are
    dropped."""
    H, n, d = q.shape
    dv = v.shape[-1]
    w = chunk
    pad = (-n) % w
    if pad:
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        q, k, v = zp(q), zp(k), zp(v)
    nc = q.shape[1] // w
    split = lambda a: a.reshape(H, nc, w, a.shape[-1]).swapaxes(0, 1)  # noqa: E731,E501
    t = jnp.arange(w)
    diff = t[:, None] - t[None, :]
    lower = diff >= 0
    logg = jnp.log(g)[:, None, None]  # (H, 1, 1)
    Lg = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0) * logg), 0.0)
    upper = (t[:, None] <= t[None, :]).astype(F32)
    pow_in = jnp.exp((t + 1.0) * logg[:, :, 0])[:, :, None]  # g^(t-t0)
    pow_end = jnp.exp((w - 1.0 - t) * logg[:, :, 0])[:, :, None]  # g^(t1-i)
    rho = jnp.exp(w * logg)  # (H, 1, 1)
    mm = functools.partial(jnp.einsum, precision=HI)

    def body(carry, xs):
        S, M = carry  # (H, d, d), (H, d, dv)
        Q, K, V = xs  # (H, w, d), (H, w, d), (H, w, dv)
        A = mm("htd,hid->hti", Q, K) * Lg
        B = mm("hid,hjd->hij", K, Q) * upper
        intra = mm("htj,hjf->htf", mm("hti,hij->htj", A, B) * Lg, V)
        QSQ = mm("htd,hde,hje->htj", Q, S, Q) * Lg
        cross = pow_in * mm("htj,hjf->htf", QSQ, V)
        past = pow_in**2 * mm("htd,hdf->htf", Q, M)
        o = past + cross + intra
        Kd, Qd, Vd = pow_end * K, pow_end * Q, pow_end * V
        S1 = rho * S + mm("hid,hie->hde", Kd, K)
        M1 = (rho**2 * M
              + rho * mm("hde,hje,hjf->hdf", S, Qd, V)
              + mm("hid,hij,hjf->hdf", Kd, B, Vd))
        return (S1, M1), o

    init = (jnp.zeros((H, d, d), F32), jnp.zeros((H, d, dv), F32))
    _, o = jax.lax.scan(body, init, (split(q), split(k), split(v)))
    return o.swapaxes(0, 1).reshape(H, nc * w, dv)[:, :n]


# --------------------------------------------------------------------------
# causal softmax attention over blocks of queries
# --------------------------------------------------------------------------


def softmax_attention(q, k, v, *, block: int = Q_BLOCK):
    """(H, n, dh) q, (Hk, n, dh) k and v -> (H, n, dh) causal softmax
    attention, queries taken ``block`` at a time against every key."""
    H, n, dh = q.shape
    Hk = k.shape[0]
    k = jnp.repeat(k, H // Hk, axis=0)
    v = jnp.repeat(v, H // Hk, axis=0)
    pad = (-n) % block
    qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0)))
    qb = qb.reshape(H, -1, block, dh).swapaxes(0, 1)  # (nb, H, block, dh)
    keys = jnp.arange(n)

    def one(args):
        Q, start = args
        s = jnp.einsum("hqd,hkd->hqk", Q, k, precision=HI) / math.sqrt(dh)
        mask = keys[None, :] <= (start + jnp.arange(block))[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v, precision=HI)

    starts = jnp.arange(qb.shape[0]) * block
    o = jax.lax.map(one, (qb, starts))  # (nb, H, block, dh)
    return o.swapaxes(0, 1).reshape(H, -1, dh)[:, :n]


# --------------------------------------------------------------------------
# the language model
# --------------------------------------------------------------------------


def _swiglu(h, wg, wu, wd, lowp):
    """SwiGLU over blocks of ``ROW_BLOCK`` rows."""
    n, d = h.shape
    blk = min(ROW_BLOCK, n)
    pad = (-n) % blk
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, blk, d)

    def one(x):
        u = jax.nn.silu(_dense(x, wg, lowp)) * _dense(x, wu, lowp)
        return _dense(u, wd, lowp)

    return jax.lax.map(one, hb).reshape(-1, d)[:n]


def _heads(a, H):
    n = a.shape[0]
    return a.reshape(n, H, -1).swapaxes(0, 1)


def _linear_mixer(c, m, h, p, lowp):
    q = _heads(_dense(h, p["wq"], lowp), m["Hl"]) * m["dk"] ** -0.5
    k = _heads(_dense(h, p["wk"], lowp), m["Hl"])
    v = _heads(_dense(h, p["wv"], lowp), m["Hl"])
    q, k, v = (_round(a, lowp, weight=False) for a in (q, k, v))
    g = jax.nn.sigmoid(p["decay_a"])
    o = hla2(q, k, v, g, c["chunk"])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
    o = (o * p["out_scale"][:, None, :]).swapaxes(0, 1)
    return _dense(o.reshape(h.shape[0], -1), p["wo"], lowp)


def _full_mixer(c, m, h, p, lowp):
    q = _heads(_dense(h, p["wq"], lowp), m["H"])
    k = _heads(_dense(h, p["wk"], lowp), m["Hk"])
    v = _heads(_dense(h, p["wv"], lowp), m["Hk"])
    q, k, v = (_round(a, lowp, weight=False) for a in (q, k, v))
    o = softmax_attention(q, k, v).swapaxes(0, 1)
    return _dense(o.reshape(h.shape[0], -1), p["wo"], lowp)


def forward_hidden(c, W, tokens, *, lowp=None):
    """(n,) int tokens -> (n, d) final-normed hidden rows, float32."""
    m = dims(c)
    eps = c["rms_norm_eps"]
    P = len(m["period"])
    f32 = lambda a: a.astype(F32)  # noqa: E731  (one layer's leaves at a time)
    x = f32(jnp.take(W["embed"], tokens, axis=0))
    for layer, kind in enumerate(m["types"]):
        pre, g = f"p{layer % P}_", layer // P
        p = {name[len(pre):]: f32(W[name][g]) for name in W
             if name.startswith(pre)}
        h = _rms(x, p["ln1"], eps)
        mixer = _linear_mixer if kind == LINEAR else _full_mixer
        x = x + mixer(c, m, h, p, lowp)
        h = _rms(x, p["ln2"], eps)
        x = x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], lowp)
    return _rms(x, f32(W["final_norm"]), eps)


def logits_at(c, W, tokens, positions, *, lowp=None):
    """Logits (len(positions), vocab) of the next token after each of
    ``positions`` in the sequence ``tokens``."""
    h = forward_hidden(c, W, tokens, lowp=lowp)
    return _dense(jnp.take(h, positions, axis=0),
                  W["unembed"].astype(F32), lowp)

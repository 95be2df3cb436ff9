"""Plain float32 reference of the HLA2 language model, and its weights.

This file imports nothing of the program under test.  It holds:

* ``weight_shapes`` / ``make_weights``: the model's named weights, made on
  the device from the seed in one jitted call.  The harness hands the same
  arrays (re-arranged by ``hla2_lm.to_program``) to the program; the
  reference makes its own copy from the seed and never reads the
  program's.
* ``forward_hidden`` / ``logits_at`` / ``loss``: the forward pass in
  straightforward ``jax.numpy`` at float32 "highest" precision: embedding,
  per layer RMSNorm -> masked second-order HLA mixer -> residual ->
  RMSNorm -> SwiGLU -> residual, final RMSNorm, untied output head.
* ``adamw_step``: the optimizer the training cells run (AdamW, global-norm
  clipping, linear warm-up into cosine decay).

The HLA2 mixer follows the paper's masked second-order form (arXiv
2510.27258, Sec. 3, with per-head decay gamma):

    o_t = sum_{i <= j <= t} g^{(t-i)+(t-j)} (q_t . k_i)(k_i . q_j) v_j

computed chunk by chunk.  With ``S = sum_i g^{t0-i} k_i k_i^T`` and
``M = sum_{i<=j} g^{2 t0-i-j} k_i k_i^T q_j v_j^T`` at a chunk's start t0,
a position t in the chunk reads ``g^{2(t-t0)} q_t M`` (both indices
before the chunk), ``g^{t-t0} sum_{j<=t} g^{t-j} (q_t S q_j) v_j`` (i
before, j inside) and the masked intra-chunk product (both inside).  Each
head's output is RMS-normalised and scaled (``out_scale``), as the
configuration's mixer states.

``lowp`` selects the precision of the dense matmuls' operands and of the
mixer's q/k/v: ``None`` is float32 (the reference), ``"fp8"`` rounds them
to float8 e4m3 with a per-tensor scale for weights and a per-row scale for
activations (the control: one step below the bfloat16 the configuration
computes in).  In training the rounding is straight-through, so the
backward pass stays float32.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0


def dims(c: dict):
    d = c["d_model"]
    H = c["n_heads"]
    dh = c.get("head_dim") or d // H
    return c["n_layers"], d, H, dh, c["d_ff"], c["vocab"]


def weight_shapes(c: dict) -> dict:
    L, d, H, dh, ff, V = dims(c)
    return {
        "embed": (V, d),
        "ln1": (L, d),
        "wq": (L, d, H * dh),
        "wk": (L, d, H * dh),
        "wv": (L, d, H * dh),
        "wo": (L, H * dh, d),
        "out_scale": (L, H, dh),
        "decay_a": (L, H),
        "ln2": (L, d),
        "w_gate": (L, d, ff),
        "w_up": (L, d, ff),
        "w_down": (L, ff, d),
        "final_norm": (d,),
        "unembed": (d, V),
    }


def seed_key(seed: int):
    """A PRNG key from any non-negative seed (more than 32 bits too)."""
    seed = int(seed)
    key = jax.random.key(seed % (1 << 32))
    return jax.random.fold_in(key, (seed >> 32) % (1 << 31))


def _init(name: str, shape, key):
    if name in ("ln1", "ln2", "final_norm", "out_scale"):
        return jnp.ones(shape, F32)
    if name == "decay_a":
        # per-head decay gamma = sigmoid(a) in about [0.88, 0.9975]: the
        # slowest heads remember some hundreds of tokens
        return jax.random.uniform(key, shape, F32, 2.0, 6.0)
    if name == "embed":
        return 0.02 * jax.random.normal(key, shape, F32)
    fan_in = shape[-2]
    return jax.random.normal(key, shape, F32) / math.sqrt(fan_in)


def make_weights(c: dict, key):
    """Named float32 weights of configuration ``c`` from ``key``: one leaf
    per name, each from its own fold of the key.  Jit-able."""
    shapes = weight_shapes(c)
    return {
        name: _init(name, shape, jax.random.fold_in(key, i))
        for i, (name, shape) in enumerate(sorted(shapes.items()))
    }


# --------------------------------------------------------------------------
# precision of the dense operands
# --------------------------------------------------------------------------


def e4m3(y):
    """Round float32 ``y`` to the nearest float8 e4m3 value (3 mantissa
    bits, exponents -6 to 8, subnormals below 2^-6, largest 448), in
    float32 arithmetic."""
    a = jnp.abs(y)
    e = jnp.clip(jnp.floor(jnp.log2(jnp.maximum(a, 1e-30))), -6.0, 8.0)
    step = jnp.exp2(e - 3.0)
    return jnp.clip(jnp.round(y / step) * step, -FP8_MAX, FP8_MAX)


def _fp8(x, axis):
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / FP8_MAX
    q = e4m3(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight-through


def _round(x, lowp, *, weight: bool):
    if lowp is None:
        return x
    if lowp == "fp8":
        axis = None if weight else -1
        return _fp8(x, axis)
    raise ValueError(lowp)


def _dense(x, w, lowp):
    x = _round(x, lowp, weight=False)
    w = _round(w, lowp, weight=True)
    return jnp.einsum("...d,df->...f", x, w, precision=HI)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


# --------------------------------------------------------------------------
# masked second-order HLA, chunk by chunk
# --------------------------------------------------------------------------


def hla2(q, k, v, g, chunk: int):
    """(H, n, dh) float32 q, k, v and (H,) decay -> (H, n, dh) outputs.
    ``n`` is padded with zero rows to a multiple of ``chunk``; zero rows
    add nothing to the summaries and their outputs are dropped."""
    H, n, dh = q.shape
    w = chunk
    pad = (-n) % w
    if pad:
        zp = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))  # noqa: E731
        q, k, v = zp(q), zp(k), zp(v)
    nc = q.shape[1] // w
    split = lambda a: a.reshape(H, nc, w, dh).swapaxes(0, 1)  # noqa: E731
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
        S, M = carry  # (H, dh, dh), (H, dh, dh)
        Q, K, V = xs  # (H, w, dh)
        A = mm("htd,hid->hti", Q, K) * Lg
        B = mm("hid,hjd->hij", K, Q) * upper
        intra = mm("htj,hjd->htd", mm("hti,hij->htj", A, B) * Lg, V)
        QSQ = mm("htd,hde,hje->htj", Q, S, Q) * Lg
        cross = pow_in * mm("htj,hjd->htd", QSQ, V)
        past = pow_in**2 * mm("htd,hde->hte", Q, M)
        o = past + cross + intra
        Kd, Qd, Vd = pow_end * K, pow_end * Q, pow_end * V
        S1 = rho * S + mm("hid,hie->hde", Kd, K)
        M1 = (rho**2 * M
              + rho * mm("hde,hje,hjf->hdf", S, Qd, V)
              + mm("hid,hij,hjf->hdf", Kd, B, Vd))
        return (S1, M1), o

    zero = jnp.zeros((H, dh, dh), F32)
    _, o = jax.lax.scan(body, (zero, zero), (split(q), split(k), split(v)))
    return o.swapaxes(0, 1).reshape(H, nc * w, dh)[:, :n]


# --------------------------------------------------------------------------
# the language model
# --------------------------------------------------------------------------


def _layer(c, x, p, lowp):
    """One residual layer on (n, d) float32 rows."""
    L, d, H, dh, ff, V = dims(c)
    eps = c["norm_eps"]
    n = x.shape[0]
    h = _rms(x, p["ln1"], eps)
    heads = lambda a: a.reshape(n, H, dh).swapaxes(0, 1)  # noqa: E731
    q = heads(_dense(h, p["wq"], lowp)) * dh**-0.5
    k = heads(_dense(h, p["wk"], lowp))
    v = heads(_dense(h, p["wv"], lowp))
    q, k, v = (_round(a, lowp, weight=False) for a in (q, k, v))
    g = jax.nn.sigmoid(p["decay_a"])
    o = hla2(q, k, v, g, c["chunk"])
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-6)
    o = (o * p["out_scale"][:, None, :]).swapaxes(0, 1).reshape(n, H * dh)
    x = x + _dense(o, p["wo"], lowp)
    h = _rms(x, p["ln2"], eps)
    u = jax.nn.silu(_dense(h, p["w_gate"], lowp)) * _dense(h, p["w_up"], lowp)
    return x + _dense(u, p["w_down"], lowp)


_LAYER_KEYS = ("ln1", "wq", "wk", "wv", "wo", "out_scale", "decay_a", "ln2",
               "w_gate", "w_up", "w_down")


def forward_hidden(c, W, tokens, *, lowp=None, remat=False):
    """(n,) int tokens -> (n, d) final-normed hidden rows, float32."""
    x = jnp.take(W["embed"], tokens, axis=0)
    layer = functools.partial(_layer, c, lowp=lowp)
    if remat:
        layer = jax.checkpoint(layer)

    def body(x, p):
        return layer(x, p), None

    x, _ = jax.lax.scan(body, x, {k: W[k] for k in _LAYER_KEYS})
    return _rms(x, W["final_norm"], c["norm_eps"])


def logits_at(c, W, tokens, positions, *, lowp=None):
    """Logits (len(positions), vocab) of the next token after each of
    ``positions`` in the sequence ``tokens``."""
    h = forward_hidden(c, W, tokens, lowp=lowp)
    return _dense(jnp.take(h, positions, axis=0), W["unembed"], lowp)


def loss(c, W, tokens, labels, *, lowp=None, block: int = 1024):
    """Mean next-token cross entropy over a (B, n) batch; the output head
    and the softmax run over blocks of ``block`` rows."""
    h = jax.vmap(lambda t: forward_hidden(c, W, t, lowp=lowp, remat=True))(
        tokens)
    d = h.shape[-1]
    block = math.gcd(block, labels.size)
    h = h.reshape(-1, block, d)
    y = labels.reshape(-1, block)

    @jax.checkpoint
    def ce(hb, yb):
        lg = _dense(hb, W["unembed"], lowp)
        lse = jax.scipy.special.logsumexp(lg, -1)
        return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], -1)[:, 0])

    total = jnp.sum(jax.lax.map(lambda a: ce(*a), (h, y)))
    return total / labels.size


# --------------------------------------------------------------------------
# AdamW as the training cells run it
# --------------------------------------------------------------------------


def lr_at(step, o: dict):
    """Linear warm-up to ``lr`` over ``warmup_steps``, then cosine decay to
    ``min_lr_ratio * lr`` at ``total_steps``."""
    warm = jnp.minimum(step / max(o["warmup_steps"], 1), 1.0)
    span = max(o["total_steps"] - o["warmup_steps"], 1)
    prog = jnp.clip((step - o["warmup_steps"]) / span, 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    r = o["min_lr_ratio"]
    return o["lr"] * warm * (r + (1.0 - r) * cos)


def clip_grads(grads, max_norm):
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-9))
    return jax.tree.map(lambda g: g * scale, grads)


def adamw_step(W, m, v, grads, step, o: dict):
    """One AdamW update on clipped gradients.  Weight decay applies to
    every stored leaf of rank two or more (the layer-stacked norm scales
    and decay logits included), as the configured optimizer does."""
    b1, b2 = o["betas"]
    g = clip_grads(grads, o["grad_clip"])
    m = jax.tree.map(lambda a, b: b1 * a + (1 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1 - b2) * b * b, v, g)
    bc1 = 1 - b1**step
    bc2 = 1 - b2**step
    lr = lr_at(step, o)

    def upd(p, mi, vi):
        delta = (mi / bc1) / (jnp.sqrt(vi / bc2) + o["eps"])
        if p.ndim >= 2:
            delta = delta + o["weight_decay"] * p
        return p - lr * delta

    return jax.tree.map(upd, W, m, v), m, v, g

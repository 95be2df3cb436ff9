"""Softmax attention: blockwise (flash-style) GQA + KV-cache decode.

``flash_attention`` never materializes the (n, n) score matrix: it scans
over KV blocks carrying (acc, row_max, row_sum) — O(n * block) memory, so
prefill_32k fits HBM without a fused kernel (the paper's contribution is
the HLA mixer; softmax stays pure JAX).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..distributed.sharding import constrain
from .blocks import dense_apply, dense_specs, rope
from .param import Spec

NEG_INF = -1e30
PREFILL_Q_BLOCK = 2048  # queries per pass of a cache-filling prefill


def attention_specs(cfg):
    d, H, Hk, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    return {
        "wq": dense_specs(d, H * dh, axes=("embed", "q_heads_flat"), bias=cfg.qkv_bias),
        "wk": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat"), bias=cfg.qkv_bias),
        "wv": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat"), bias=cfg.qkv_bias),
        "wo": dense_specs(H * dh, d, axes=("q_heads_flat", "embed")),
    }


def flash_attention(
    q: jax.Array,  # (B, H, nq, dh)
    k: jax.Array,  # (B, Hk, nk, dh)
    v: jax.Array,  # (B, Hk, nk, dh)
    *,
    causal: bool = True,
    kv_block: int = 512,
    q_offset=0,  # absolute position of q[0] (causal masking): a scalar,
    #   or (B,) per row
    kv_len: Optional[jax.Array] = None,  # valid kv length (decode
    #   masking): a scalar, or (B,) per row
    score_dtype=None,  # stored score/prob dtype; defaults to the input
    # dtype (bf16 models store bf16 scores — §Perf lever D: fp32 score
    # round-trips dominated the attention memory roofline term);
    # accumulation is always fp32.
    q_block: Optional[int] = None,  # queries per pass over the keys; None
    #   = all at once (a long prefill keeps its scores at q_block x
    #   kv_block per head this way)
):
    """Blockwise softmax attention with online renormalization."""
    if score_dtype is None:
        score_dtype = (
            jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
        )
    B, H, nq, dh = q.shape
    if q_block is not None and nq > q_block:
        pad = (-nq) % q_block
        qs = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        qs = jnp.moveaxis(qs.reshape(B, H, -1, q_block, dh), 2, 0)
        offs = jnp.arange(qs.shape[0]) * q_block
        out = jax.lax.map(
            lambda a: flash_attention(
                a[0], k, v, causal=causal, kv_block=kv_block,
                q_offset=jnp.asarray(q_offset) + a[1], kv_len=kv_len,
                score_dtype=score_dtype,
            ),
            (qs, offs),
        )
        return jnp.moveaxis(out, 0, 2).reshape(B, H, -1, dh)[:, :, :nq]
    Hk, nk = k.shape[1], k.shape[2]
    G = H // Hk
    qg = q.reshape(B, Hk, G, nq, dh).astype(score_dtype)
    scale = jnp.asarray(1.0 / np.sqrt(dh), jnp.float32)

    blk = min(kv_block, nk)
    if nk % blk != 0:  # pad keys (masked out below)
        pad = blk - nk % blk
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        pad_len = nk + pad
    else:
        pad_len = nk
    nblk = pad_len // blk
    kb = jnp.moveaxis(k.reshape(B, Hk, nblk, blk, dh), 2, 0).astype(score_dtype)
    vb = jnp.moveaxis(v.reshape(B, Hk, nblk, blk, dh), 2, 0).astype(score_dtype)

    # (nq,) or (B, nq) query positions; () or (B, 1, 1) key limits
    q_pos = jnp.asarray(q_offset)[..., None] + jnp.arange(nq)
    limit = jnp.asarray(kv_len if kv_len is not None else nk)[..., None,
                                                               None]

    def body(carry, inp):
        acc, mx, sm = carry
        kblk, vblk, bidx = inp
        kv_pos = bidx * blk + jnp.arange(blk)
        s = jnp.einsum(
            "bhgqd,bhkd->bhgqk", qg, kblk,
            preferred_element_type=jnp.float32,
        ) * scale
        mask = kv_pos < limit
        if causal:
            mask = mask & (kv_pos <= q_pos[..., :, None])
        mask = jnp.broadcast_to(mask, (B, nq, blk))
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        new_mx = jnp.maximum(mx, jnp.max(s, axis=-1))
        # probs stored in score_dtype (HBM); sums/acc accumulate fp32
        p = jnp.exp((s - new_mx[..., None])).astype(score_dtype)
        corr = jnp.exp(mx - new_mx)
        sm = sm * corr + jnp.sum(p.astype(jnp.float32), axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bhkd->bhgqd", p, vblk,
            preferred_element_type=jnp.float32,
        )
        return (acc, new_mx, sm), None

    acc0 = jnp.zeros((B, Hk, G, nq, dh), jnp.float32)
    mx0 = jnp.full((B, Hk, G, nq), NEG_INF, jnp.float32)
    sm0 = jnp.zeros((B, Hk, G, nq), jnp.float32)
    (acc, mx, sm), _ = jax.lax.scan(
        body, (acc0, mx0, sm0), (kb, vb, jnp.arange(nblk))
    )
    out = acc / jnp.maximum(sm[..., None], 1e-30)
    return out.reshape(B, H, nq, dh).astype(q.dtype)


def decode_attention(q, k, v, kv_len, score_dtype=None):
    """Attention of a few new queries over a whole cache, each row masked
    to its own length: ``q`` (B, H, nq, dh) at positions ``kv_len - nq``
    .. ``kv_len - 1`` of their row, ``k``/``v`` (B, Hk, max_len, dh),
    ``kv_len`` (B,).  The scores are (B, H, nq, max_len): one pass over
    the cache, which is what bounds a decode step (no block scan, no
    blocked copy of the cache)."""
    if score_dtype is None:
        score_dtype = (
            jnp.bfloat16 if q.dtype == jnp.bfloat16 else jnp.float32
        )
    B, H, nq, dh = q.shape
    Hk, L = k.shape[1], k.shape[2]
    qg = q.reshape(B, Hk, H // Hk, nq, dh).astype(score_dtype)
    s = jnp.einsum(
        "bhgqd,bhkd->bhgqk", qg, k.astype(score_dtype),
        preferred_element_type=jnp.float32,
    ) * jnp.asarray(1.0 / np.sqrt(dh), jnp.float32)
    q_pos = kv_len[:, None] - nq + jnp.arange(nq)  # (B, nq)
    mask = jnp.arange(L) <= q_pos[..., None]  # (B, nq, L)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    mx = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - mx)
    sm = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "bhgqk,bhkd->bhgqd", p.astype(score_dtype), v.astype(score_dtype),
        preferred_element_type=jnp.float32,
    ) / sm
    return out.reshape(B, H, nq, dh).astype(q.dtype)


class KVCache(NamedTuple):
    k: jax.Array  # (B, Hk, max_len, dh)
    v: jax.Array  # (B, Hk, max_len, dh)
    length: jax.Array  # (B,) int32 — tokens currently valid in each row


def init_kv_cache(B, Hk, max_len, dh, dtype=jnp.bfloat16) -> KVCache:
    return KVCache(
        k=jnp.zeros((B, Hk, max_len, dh), dtype),
        v=jnp.zeros((B, Hk, max_len, dh), dtype),
        length=jnp.zeros((B,), jnp.int32),
    )


def kv_cache_axes() -> KVCache:
    """Logical axes per cache leaf (state-sharding source of truth):
    batch over data, KV heads over model, time/feature replicated."""
    from .param import Axes

    return KVCache(
        k=Axes(("batch", "kv_heads", None, None)),
        v=Axes(("batch", "kv_heads", None, None)),
        length=Axes(("batch",)),
    )


def _cache_put(cache, new, at):
    """Write ``new`` (B, Hk, n, dh) into ``cache`` at each row's own
    offset ``at`` (B,)."""
    put = lambda c, x, a: jax.lax.dynamic_update_slice(  # noqa: E731
        c, x.astype(c.dtype), (jnp.zeros_like(a), a, jnp.zeros_like(a)))
    return jax.vmap(put)(cache, new, at)


def _append_rows(cache, i, new, at):
    """Write one token per row, ``new`` (B, Hk, dh), into layer ``i`` of
    the stacked ``cache`` (layers, B, Hk, max_len, dh) at each row's own
    position ``at`` (B,), in place.  Each row rewrites the aligned block
    of 8 positions that holds its token: the TPU compiler then keeps the
    cache in its own layout, where a scatter of single positions makes it
    lay the whole cache out anew (two copies of it per step)."""
    B, Hk, L, dh = cache.shape[1:]
    T = min(8, L)
    zero = jnp.zeros((), jnp.int32)
    i, at = jnp.asarray(i, jnp.int32), at.astype(jnp.int32)
    for b in range(B):
        base = jnp.minimum(at[b] // T * T, L - T)
        start = (i, jnp.int32(b), zero, base, zero)
        block = jax.lax.dynamic_slice(cache, start, (1, 1, Hk, T, dh))
        row = (jnp.arange(T) == at[b] - base)[:, None]
        block = jnp.where(row, new[b][None, None, :, None, :].astype(
            cache.dtype), block)
        cache = jax.lax.dynamic_update_slice(cache, block, start)
    return cache


def _self_qkv(p, x, cfg, positions, use_rope):
    """Projected (B, heads, n, dh) queries, keys and values, with the rotary
    embedding where the config has one."""
    B, n, _ = x.shape
    H, Hk, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = dense_apply(p["wq"], x).reshape(B, n, H, dh)
    k = dense_apply(p["wk"], x).reshape(B, n, Hk, dh)
    v = dense_apply(p["wv"], x).reshape(B, n, Hk, dh)
    if use_rope and cfg.rope_theta is not None:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(jnp.swapaxes(q, 1, 2), ("batch", "q_heads", None, None))
    k = constrain(jnp.swapaxes(k, 1, 2), ("batch", "kv_heads", None, None))
    v = constrain(jnp.swapaxes(v, 1, 2), ("batch", "kv_heads", None, None))
    return q, k, v


def attention_apply(
    p,
    x: jax.Array,  # (B, n, d)
    cfg,
    *,
    positions: Optional[jax.Array] = None,
    cache: Optional[KVCache] = None,
    cross_kv: Optional[tuple] = None,  # (k, v) for cross-attention
    causal: bool = True,
    use_rope: bool = True,
):
    """Self- or cross-attention sublayer.  Returns (out, new_cache)."""
    B, n, _ = x.shape
    H, dh = cfg.n_heads, cfg.head_dim
    if positions is None:
        positions = jnp.arange(n)[None, :]

    if cross_kv is None:
        q, k, v = _self_qkv(p, x, cfg, positions, use_rope)
        new_cache = None
        if cache is not None:
            # each row appends at its own length (per-slot serving)
            new_cache = KVCache(_cache_put(cache.k, k, cache.length),
                                _cache_put(cache.v, v, cache.length),
                                cache.length + n)
            if n == 1:
                out = decode_attention(q, new_cache.k, new_cache.v,
                                       new_cache.length)
            else:
                out = flash_attention(
                    q, new_cache.k, new_cache.v, causal=causal,
                    q_offset=cache.length, kv_len=new_cache.length,
                    q_block=PREFILL_Q_BLOCK,
                )
        else:
            out = flash_attention(q, k, v, causal=causal)
    else:
        q = dense_apply(p["wq"], x).reshape(B, n, H, dh)
        kc, vc = cross_kv  # precomputed encoder K/V: (B, Hk, ne, dh)
        q = jnp.swapaxes(q, 1, 2)
        out = flash_attention(q, kc, vc, causal=False)
        new_cache = None

    out = jnp.swapaxes(out, 1, 2).reshape(B, n, H * dh)
    out = constrain(out, ("batch", None, "q_heads_flat"))
    return dense_apply(p["wo"], out), new_cache


# --------------------------------------------------------------------------
# SequenceOp registration: softmax attention as "attn"
# --------------------------------------------------------------------------


def _attn_forward(p, x, cfg, *, state=None, want_state=False, positions=None,
                  use_rope=True):
    """Train (state=None) or prefill/decode (state=KVCache, filled in
    place at ``state.length``).  ``want_state`` is implied by ``state``."""
    return attention_apply(
        p, x, cfg, positions=positions, cache=state, use_rope=use_rope
    )


def _attn_step(p, x_t, state, cfg, *, positions=None):
    return attention_apply(p, x_t, cfg, positions=positions, cache=state)


def _attn_step_at(p, x_t, stack, i, cfg, *, positions=None):
    """One decode token against the layer-stacked cache ``stack`` (leaves
    (layers, B, Hk, max_len, dh), lengths (layers, B)) at layer ``i``: the
    token's keys and values are written into the stacked leaves at each
    row's own length, and the layer's cache is read where it lies — the
    cache is updated in place, never copied out and back."""
    B = x_t.shape[0]
    q, k, v = _self_qkv(p, x_t, cfg, positions, True)
    length = jax.lax.dynamic_index_in_dim(stack.length, i, keepdims=False)
    K = _append_rows(stack.k, i, k[:, :, 0], length)
    V = _append_rows(stack.v, i, v[:, :, 0], length)
    at = lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False)  # noqa: E731,E501
    out = decode_attention(q, at(K), at(V), length + 1)
    out = jnp.swapaxes(out, 1, 2).reshape(B, 1, cfg.n_heads * cfg.head_dim)
    out = constrain(out, ("batch", None, "q_heads_flat"))
    new = KVCache(K, V, stack.length.at[i].add(1))
    return dense_apply(p["wo"], out), new


def _attn_init_state(cfg, B, *, max_len=0, dtype=None):
    # the cache holds keys and values at the precision they are computed
    # in (a float32 model's cache is float32)
    return init_kv_cache(B, cfg.n_kv_heads, max_len, cfg.head_dim,
                         dtype=jnp.dtype(cfg.dtype if dtype is None
                                         else dtype))


from . import seq_op as _seq_op  # noqa: E402  (import cycle: none — seq_op
#   imports this module lazily, after its own module body has run)

_seq_op.register_op(_seq_op.SequenceOp(
    name="attn",
    specs=attention_specs,
    forward=_attn_forward,
    step=_attn_step,
    init_state=_attn_init_state,
    state_axes=lambda cfg: kv_cache_axes(),
    streaming=False,  # the KV cache grows with context; each row keeps
    #   its own ``length``, so the serving engine admits and decodes it
    #   per slot (engine.py), without prefix caching or speculation
    step_at=_attn_step_at,
    spec_decodable=False,
    needs_positions=True,
    prealloc_state=True,  # prefill fills a preallocated cache
))


def cross_kv_specs(cfg):
    d, Hk, dh = cfg.d_model, cfg.n_kv_heads, cfg.head_dim
    return {
        "wk": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat")),
        "wv": dense_specs(d, Hk * dh, axes=("embed", "kv_heads_flat")),
    }


def cross_kv_apply(p, enc_out, cfg):
    B, ne, _ = enc_out.shape
    Hk, dh = cfg.n_kv_heads, cfg.head_dim
    k = dense_apply(p["wk"], enc_out).reshape(B, ne, Hk, dh)
    v = dense_apply(p["wv"], enc_out).reshape(B, ne, Hk, dh)
    return jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)

"""Decoder-only LM assembly: uniform / hybrid stacks, train / prefill / decode.

Layers are stacked (leading "layers" axis on every param leaf) and applied
with ``lax.scan`` — one layer body in the HLO regardless of depth (fast
compiles, pipeline-friendly).  Hybrid stacks scan over *periods* of
``cfg.layer_pattern`` (jamba: 1 attention/HLA + 7 mamba, MoE on alternate
positions; Olmo Hybrid: 3 HLA2 + 1 softmax), unrolled inside the scan body.

Every sequence-mixing sublayer is a registered ``seq_op.SequenceOp``
(DESIGN.md §11): this module resolves ``cfg`` to op records ONCE and then
programs purely against the record interface — specs / forward / step /
init_state / state_axes plus capability flags.  There is no per-kind
dispatch here; registering a new operator (see ``models/gla.py``) makes it
train, prefill and decode through this file with zero edits.

Decode states are stacked pytrees matching the scan structure — each
leaf's layout is whatever the op's ``init_state`` returns (KVCache for
attn, core state tuples for the HLA family, MambaState, RWKVState, ...).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from . import moe as moe_mod
from . import seq_op
from .blocks import (
    embed_apply,
    embed_specs,
    mlp_apply,
    mlp_specs,
    rmsnorm_apply,
    rmsnorm_specs,
    unembed_apply,
)
from .param import Spec, is_spec
from ..distributed.sharding import constrain


# --------------------------------------------------------------------------
# per-layer specs / apply (SequenceOp-generic)
# --------------------------------------------------------------------------


def layer_specs(cfg, op: seq_op.SequenceOp, use_moe: bool):
    if op.self_contained:  # e.g. rwkv6: owns norms + channel mix
        return op.specs(cfg)
    s = {"ln1": rmsnorm_specs(cfg.d_model), "ln2": rmsnorm_specs(cfg.d_model)}
    s[op.param_key] = op.specs(cfg)
    if use_moe:
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp)
    return s


def layer_apply(
    p, x, cfg, op: seq_op.SequenceOp, use_moe: bool, *,
    positions=None, state=None, mode: str = "train", index=None,
):
    """Returns (x, new_state, aux_loss).  With ``index`` (decode only),
    ``state`` is the layer-stacked state and the op's ``step_at`` updates
    layer ``index``'s part of it in place."""
    aux = jnp.zeros((), jnp.float32)
    if op.self_contained:
        x, new_state = op.forward(p, x, cfg, state=state)
        return x, (None if mode == "train" else new_state), aux

    h = rmsnorm_apply(p["ln1"], x, cfg.norm_eps)
    sub = p[op.param_key]
    if index is not None:
        y, new_state = op.step_at(sub, h, state, index, cfg,
                                  positions=positions)
    elif mode == "decode" and op.streaming:
        y, new_state = op.step(sub, h, state, cfg, positions=positions)
    else:
        # train: state is None, want_state False -> pure training path;
        # prefill: one chunkwise/cache-filling call returning the decode
        # state; decode for non-streaming ops (attn) is a cache-append
        # forward over the single new token.
        y, new_state = op.forward(
            sub, h, cfg, state=state,
            want_state=(mode != "train"), positions=positions,
        )
    if mode == "train":
        new_state = None
    x = x + y

    h = rmsnorm_apply(p["ln2"], x, cfg.norm_eps)
    if use_moe:
        y, aux = moe_mod.moe_apply(p["moe"], h, cfg)
    else:
        y = mlp_apply(p["mlp"], h, cfg.mlp)
    x = x + y
    return x, new_state, aux


def layer_decode_at(p, x, cfg, op: seq_op.SequenceOp, use_moe: bool, *,
                    positions, stack, index):
    """One decode layer against the layer-stacked ``stack`` at ``index``:
    in place through the op's ``step_at``, else the layer's state sliced
    out, stepped and written back."""
    if op.step_at is not None and not op.self_contained:
        return layer_apply(p, x, cfg, op, use_moe, positions=positions,
                           state=stack, mode="decode", index=index)
    st = jax.tree.map(
        lambda s: jax.lax.dynamic_index_in_dim(s, index, keepdims=False),
        stack)
    x, st, aux = layer_apply(p, x, cfg, op, use_moe, positions=positions,
                             state=st, mode="decode")
    stack = jax.tree.map(
        lambda s, n: jax.lax.dynamic_update_index_in_dim(
            s, n.astype(s.dtype), index, 0),
        stack, st)
    return x, stack, aux


def layer_init_state(cfg, op: seq_op.SequenceOp, B: int, max_len: int):
    return op.init_state(cfg, B, max_len=max_len)


def layer_state_axes(cfg, op: seq_op.SequenceOp):
    """Logical axes matching ``layer_init_state``'s tree (per-op source of
    truth; ``lm_state_axes`` adds the "layers" stacking dim)."""
    return op.state_axes(cfg)


# --------------------------------------------------------------------------
# stacks
# --------------------------------------------------------------------------


def _stack_specs(specs, L: int):
    return jax.tree.map(
        lambda s: dataclasses.replace(
            s, shape=(L,) + s.shape, axes=("layers",) + s.axes
        ),
        specs,
        is_leaf=is_spec,
    )


def _group_layout(cfg):
    """Hybrid layout: list of (op, use_moe) per position of the period
    ``cfg.layer_pattern`` ("mixer" is the configured mixer op)."""
    period = len(cfg.layer_pattern)
    if cfg.n_layers % period:
        raise ValueError(
            f"{cfg.name}: {cfg.n_layers} layers are not whole periods of "
            f"the {period}-layer pattern {cfg.layer_pattern}"
        )
    out = []
    for i, name in enumerate(cfg.layer_pattern):
        op = seq_op.op_for(cfg) if name == "mixer" else seq_op.get_op(name)
        use_moe = cfg.moe is not None and (i % cfg.moe.every == cfg.moe.every - 1)
        out.append((op, use_moe))
    return out


def stack_ops(cfg):
    """Every distinct sequence op of the stack."""
    if cfg.layer_pattern:
        return list({op.name: op for op, _ in _group_layout(cfg)}.values())
    return [seq_op.op_for(cfg)]


def needs_prealloc_states(cfg) -> bool:
    """True when prefill must write into preallocated states (KV caches /
    hybrid stacks) rather than building streaming state from scratch —
    derived from the ops' ``prealloc_state`` capability flag."""
    return any(op.prealloc_state for op in stack_ops(cfg))


def holds_kv(cfg) -> bool:
    """True when some layer's decode state grows with the context (a KV
    cache): an op that is not ``streaming``."""
    return any(not op.streaming for op in stack_ops(cfg))


def lm_specs(cfg):
    specs = {"embed": embed_specs(cfg.vocab, cfg.d_model)}
    if cfg.layer_pattern:
        n_groups = cfg.n_layers // len(cfg.layer_pattern)
        group = {
            f"pos{i}": layer_specs(cfg, op, use_moe)
            for i, (op, use_moe) in enumerate(_group_layout(cfg))
        }
        specs["groups"] = _stack_specs(group, n_groups)
    else:
        op = seq_op.op_for(cfg)
        use_moe = cfg.moe is not None
        specs["layers"] = _stack_specs(
            layer_specs(cfg, op, use_moe), cfg.n_layers
        )
    specs["final_norm"] = rmsnorm_specs(cfg.d_model)
    if not cfg.tie_embeddings:
        specs["unembed"] = {
            "kernel": Spec((cfg.d_model, cfg.vocab), ("embed", "vocab"))
        }
    return specs


def _cast_stack(params, cfg):
    """Optionally cast the stacked layer params before the scan: the FSDP
    all-gather then moves bf16 instead of fp32 (half the collective bytes;
    §Perf lever A).  Norm scales stay fp32 (they are recast to fp32 inside
    the norm anyway; keeping them bf16 is also fine numerically)."""
    gd = jnp.dtype(getattr(cfg, "gather_dtype", "float32"))
    if gd == jnp.float32:
        return params
    return jax.tree.map(
        lambda x: x.astype(gd) if x.dtype == jnp.float32 else x, params
    )


def _maybe_remat(f, cfg):
    if cfg.remat == "full":
        return jax.checkpoint(f)
    if cfg.remat == "dots":
        return jax.checkpoint(
            f, policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        )
    return f


def lm_init_states(cfg, B: int, max_len: int):
    """Stacked decode states matching the scan layout."""
    if cfg.layer_pattern:
        n_groups = cfg.n_layers // len(cfg.layer_pattern)
        one = {
            f"pos{i}": layer_init_state(cfg, op, B, max_len)
            for i, (op, _) in enumerate(_group_layout(cfg))
        }
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_groups,) + x.shape), one
        )
    one = layer_init_state(cfg, seq_op.op_for(cfg), B, max_len)
    return jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (cfg.n_layers,) + x.shape), one
    )


def lm_state_axes(cfg):
    """Pytree of ``Axes`` matching ``lm_init_states`` leaf-for-leaf — the
    single sharding source of truth for decode/serving states
    (``distributed.steps.state_specs`` resolves these against a mesh).
    """
    from .param import Axes

    if cfg.layer_pattern:
        one = {
            f"pos{i}": layer_state_axes(cfg, op)
            for i, (op, _) in enumerate(_group_layout(cfg))
        }
    else:
        one = layer_state_axes(cfg, seq_op.op_for(cfg))
    return jax.tree.map(
        lambda ax: Axes(("layers",) + tuple(ax)), one,
        is_leaf=lambda x: isinstance(x, Axes),
    )


def lm_apply(
    params,
    tokens: jax.Array,  # (B, n) int32
    cfg,
    *,
    states=None,
    positions: Optional[jax.Array] = None,
    mode: str = "train",  # train | prefill | decode
    vis_embed: Optional[jax.Array] = None,  # (B, nv, d) VLM stub frontend
    return_hidden: bool = False,
):
    """Returns (logits, new_states, aux_loss)."""
    B, n = tokens.shape
    act_dtype = jnp.dtype(cfg.dtype)
    x = embed_apply(params["embed"], tokens).astype(act_dtype)
    if vis_embed is not None:
        x = jnp.concatenate([vis_embed.astype(act_dtype), x], axis=1)
        n = x.shape[1]
    if positions is None:
        positions = jnp.arange(n)[None, :]

    collect_state = mode in ("prefill", "decode")
    if mode == "prefill" and states is None and needs_prealloc_states(cfg):
        # KV-cache/hybrid archs need states allocated to be filled
        # (+ margin for subsequent decode); streaming ops build state
        # from scratch.
        states = lm_init_states(cfg, B, n + 64)

    if mode == "decode" and any(op.step_at is not None
                                for op in stack_ops(cfg)):
        x, new_states, aux = _decode_in_place(params, x, states, cfg,
                                              positions)
    elif cfg.layer_pattern:
        layout = _group_layout(cfg)

        def group_body(carry, inp):
            x, aux = carry
            x = constrain(x, ("batch", "seq", "embed"))
            gp = inp["params"]
            gst = inp.get("state")
            new_states = {}
            for i, (op, use_moe) in enumerate(layout):
                st_i = gst[f"pos{i}"] if gst is not None else None
                x, new_st, a = layer_apply(
                    gp[f"pos{i}"], x, cfg, op, use_moe,
                    positions=positions, state=st_i, mode=mode,
                )
                new_states[f"pos{i}"] = new_st
                aux = aux + a
            ys = new_states if collect_state else 0.0
            return (x, aux), ys

        body = _maybe_remat(group_body, cfg)
        xs = {"params": _cast_stack(params["groups"], cfg)}
        if states is not None:
            xs["state"] = states
        (x, aux), new_states = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs
        )
    else:
        op = seq_op.op_for(cfg)
        use_moe = cfg.moe is not None

        def layer_body(carry, inp):
            x, aux = carry
            x = constrain(x, ("batch", "seq", "embed"))
            st = inp.get("state")
            x, new_st, a = layer_apply(
                inp["params"], x, cfg, op, use_moe,
                positions=positions, state=st, mode=mode,
            )
            ys = new_st if collect_state else 0.0
            return (x, aux + a), ys

        body = _maybe_remat(layer_body, cfg)
        xs = {"params": _cast_stack(params["layers"], cfg)}
        if states is not None:
            xs["state"] = states
        (x, aux), new_states = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs
        )

    x = rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    if return_hidden:
        return x, (new_states if collect_state else None), aux
    return _head(params, x, cfg), (new_states if collect_state else None), aux


def _head(params, x, cfg):
    """Final-normed hidden rows -> logits over the vocabulary."""
    if cfg.tie_embeddings:
        return unembed_apply(params["embed"], x)
    return jnp.einsum(
        "...d,dv->...v", x, params["unembed"]["kernel"].astype(x.dtype)
    )


def _decode_in_place(params, x, states, cfg, positions):
    """Decode scan that carries the whole layer-stacked state tree and
    hands each layer its index into it (``layer_decode_at``): ops with a
    ``step_at`` (the KV cache) update their layer's part in place, so the
    stack is never sliced out and written back per layer — the scan over
    ``xs``/``ys`` would hold a second copy of every cache."""
    if cfg.layer_pattern:
        layout = _group_layout(cfg)
        stacked = _cast_stack(params["groups"], cfg)
        keys = [f"pos{i}" for i in range(len(layout))]
    else:
        layout = [(seq_op.op_for(cfg), cfg.moe is not None)]
        stacked = _cast_stack(params["layers"], cfg)
        keys = [None]
    pick = lambda tree, k: tree if k is None else tree[k]  # noqa: E731

    def body(carry, inp):
        x, aux, st = carry
        x = constrain(x, ("batch", "seq", "embed"))
        st = dict(st) if keys[0] is not None else st
        for k, (op, use_moe) in zip(keys, layout):
            x, new, a = layer_decode_at(
                pick(inp["params"], k), x, cfg, op, use_moe,
                positions=positions, stack=pick(st, k), index=inp["i"],
            )
            if k is None:
                st = new
            else:
                st[k] = new
            aux = aux + a
        return (x, aux, st), None

    n = jax.tree.leaves(stacked)[0].shape[0]
    (x, aux, states), _ = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), states),
        {"params": stacked, "i": jnp.arange(n, dtype=jnp.int32)},
    )
    return x, states, aux


def lm_prefill(params, tokens, cfg, *, states=None, positions=None):
    """Chunk-parallel prompt prefill for serving admission.

    Runs the whole prompt through ``mode="prefill"`` — for streaming ops
    (hla2/ahla/gla/...) each layer is ONE chunkwise call (the Pallas
    stateful kernel on TPU, jnp chunkwise on CPU), never a per-token
    Python loop — and returns ``(last_logits, states)``: the logits of the
    final prompt position (to sample the first generated token) plus the
    decode states.
    """
    hidden, states, _ = lm_apply(
        params, tokens, cfg, states=states, positions=positions,
        mode="prefill", return_hidden=True,
    )
    # the head at the last position only: full-vocabulary logits of a
    # long prompt would be the largest tensor of the program
    return _head(params, hidden[:, -1], cfg), states


def lm_score_block(params, tokens, cfg, *, states, positions):
    """Score a short token block against streaming states — the target-model
    side of speculative verification.

    One ``mode="prefill"`` pass (per layer ONE chunkwise call — the same
    chunk-parallel path as prompt admission) over ``tokens``
    ``(B, k+1) = [last committed token, draft_1..draft_k]`` resumed from
    ``states``.  Returns ``(logits, new_states)`` with logits for EVERY
    position: ``logits[:, j]`` is the target's next-token distribution
    after consuming ``tokens[:, :j+1]``, i.e. the distribution that judges
    ``draft_{j+1}`` (and, at ``j == k``, the bonus token).  ``new_states``
    have consumed the whole block — exactly the post-acceptance state when
    every draft is accepted; on rejection the caller rolls back instead
    (serving/spec/verify.py).
    """
    logits, new_states, _ = lm_apply(
        params, tokens, cfg, states=states, positions=positions,
        mode="prefill",
    )
    return logits, new_states


def lm_loss(params, tokens, labels, cfg, *, vis_embed=None, denom=None,
            aux_weight: float = 1.0):
    """Mean next-token CE (labels < 0 are ignored) + MoE aux.  fp32 loss.

    ``denom`` overrides the CE normalizer (default: this batch's valid-token
    count).  Microbatched gradient accumulation passes the GLOBAL
    valid-token count so summed microbatch gradients equal the full-batch
    mean gradient exactly — averaging per-microbatch means is biased when
    masking gives microbatches different valid counts.  ``aux_weight``
    scales the aux term (1/microbatches under accumulation).
    """
    logits, _, aux = lm_apply(
        params, tokens, cfg, mode="train", vis_embed=vis_embed
    )
    if vis_embed is not None:
        logits = logits[:, vis_embed.shape[1]:]
    logits = logits.astype(jnp.float32)
    mask = (labels >= 0).astype(jnp.float32)
    safe = jnp.maximum(labels, 0)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    d = jnp.maximum(jnp.sum(mask), 1.0) if denom is None else denom
    ce = jnp.sum((lse - ll) * mask) / d
    return ce + aux_weight * aux, (ce, aux)

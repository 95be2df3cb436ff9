"""Gradient compression: int8 error-feedback all-reduce (shard_map).

A wire-level compressed all-reduce in two phases, both moving int8:

  1. reduce-scatter phase: each rank quantizes its gradient (after adding
     the error-feedback buffer), ``all_to_all`` ships int8 chunks + fp32
     per-chunk scales, each rank dequantizes and sums its chunk;
  2. all-gather phase: the reduced chunk is re-quantized and
     ``all_gather``-ed with its scale.

Error feedback (residual = x_ef - dequant(q)) keeps SGD convergence
(Karimireddy et al.); the buffer lives in the caller's optimizer state.
Used by the opt-in manual-DP train step; numerics validated on an 8-device
host mesh in tests/test_distributed.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quantize(x):
    scale = jnp.max(jnp.abs(x)) / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def int8_allreduce_mean(x, axis_name: str, error=None):
    """Inside shard_map: mean over ``axis_name`` with int8 wire traffic.

    x: fp32 vector (flattened gradient slice), same shape on every rank.
    Returns (mean_estimate fp32, new_error).
    """
    n = jax.lax.psum(1, axis_name)
    size = x.shape[0]
    pad = (-size) % n
    xp = jnp.pad(x if error is None else x + error, (0, pad))
    chunks = xp.reshape(n, -1)  # row r -> destined to rank r

    # per-destination quantization
    qs, scales = jax.vmap(_quantize)(chunks)  # (n, c) int8, (n,) f32
    deq_local = qs.astype(jnp.float32) * scales[:, None]
    new_error = (xp - deq_local.reshape(-1))[: size] if pad else (
        xp - deq_local.reshape(-1)
    )
    if pad:
        new_error = new_error[:size]

    # phase 1: all_to_all int8 chunks + scales; local dequant-sum
    recv_q = jax.lax.all_to_all(qs, axis_name, 0, 0, tiled=False)
    recv_s = jax.lax.all_to_all(
        scales.reshape(n, 1), axis_name, 0, 0, tiled=False
    )
    part = jnp.sum(
        recv_q.astype(jnp.float32) * recv_s.reshape(n, 1), axis=0
    ) / n  # mean

    # phase 2: re-quantize the reduced chunk, all_gather int8
    q2, s2 = _quantize(part)
    gq = jax.lax.all_gather(q2, axis_name, axis=0, tiled=False)
    gs = jax.lax.all_gather(s2, axis_name, axis=0, tiled=False)
    full = (gq.astype(jnp.float32) * gs[:, None]).reshape(-1)
    return full[:size], new_error


def make_compressed_grad_allreduce(mesh, axis_name: str = "data"):
    """Returns f(grads_tree, error_tree) -> (mean_grads, new_error) that
    runs the int8 EF all-reduce per leaf over the data axis.  Leaves are
    expected *unreduced* (per-DP-rank) — use with the manual-DP step."""

    def per_leaf(g, e):
        flat = g.reshape(-1).astype(jnp.float32)
        ef = e.reshape(-1).astype(jnp.float32)
        red, new_e = int8_allreduce_mean(flat, axis_name, ef)
        return red.reshape(g.shape), new_e.reshape(g.shape)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), P(axis_name)),
        out_specs=(P(axis_name), P(axis_name)),
    )
    def _run(gstack, estack):
        # gstack: (n_dp, ...) stacked per-rank grads; inside shard_map each
        # rank sees its (1, ...) slice.
        g = gstack[0]
        e = estack[0]
        red, new_e = per_leaf(g, e)
        return red[None], new_e[None]

    def run_tree(grads, errors):
        outs = jax.tree.map(_run, grads, errors)
        red = jax.tree.map(lambda t: t[0], outs)
        return red

    return _run


def quantize_dequantize(x):
    """Straight int8 round-trip (compression-loss measurement helper)."""
    q, s = _quantize(x.reshape(-1).astype(jnp.float32))
    return (q.astype(jnp.float32) * s).reshape(x.shape)

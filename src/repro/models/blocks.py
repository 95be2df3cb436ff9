"""Elementary blocks: norms, dense, rope, MLPs, embedding — pure functional.

Every ``*_specs`` returns a Spec pytree; every ``*_apply`` consumes the
matching params.  Sharding constraints are applied by the caller via
``repro.distributed.sharding.constrain`` on activations.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .param import Spec


# ------------------------------ norms --------------------------------------


def rmsnorm_specs(d: int):
    return {"scale": Spec((d,), ("embed",), init="ones")}


def rmsnorm_apply(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_specs(d: int):
    return {
        "scale": Spec((d,), ("embed",), init="ones"),
        "bias": Spec((d,), ("embed",), init="zeros"),
    }


def layernorm_apply(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


# ------------------------------ dense --------------------------------------


def dense_specs(d_in: int, d_out: int, axes=("embed", "ff"), bias: bool = False):
    s = {"kernel": Spec((d_in, d_out), axes)}
    if bias:
        s["bias"] = Spec((d_out,), (axes[1],), init="zeros")
    return s


def dense_apply(p, x):
    y = jnp.einsum("...d,df->...f", x, p["kernel"].astype(x.dtype))
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


# ------------------------------ embedding ----------------------------------


def embed_specs(vocab: int, d: int):
    return {"embedding": Spec((vocab, d), ("vocab", "embed"), init="embed", scale=0.02)}


def embed_apply(p, ids):
    """Embedding lookup, sharding-aware.

    Large vocab tables are sharded ("vocab" -> model); a plain gather
    makes GSPMD all-gather the whole table, and a one-hot einsum
    materializes a (tokens, V) buffer (385 GiB/device at prefill_32k —
    dry-run finding).  Inside a mesh we therefore do the classic
    shard_map lookup: local take on the vocab shard with out-of-range
    masking, then psum over "model".  Falls back to jnp.take off-mesh or
    when the vocab doesn't divide the model axis.
    """
    from ..distributed.sharding import _current_mesh

    table = p["embedding"]
    V, D = table.shape
    mesh = _current_mesh()
    if (
        mesh is None
        or V <= 8192
        or "model" not in mesh.axis_names
        or V % mesh.shape["model"] != 0
    ):
        return jnp.take(table, ids, axis=0)

    import functools

    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding import spec_for

    # adaptive batch spec: shard_map in_specs are strict about
    # divisibility (B=1 long-context decode, small per-microbatch
    # batches), so resolve through the same divisibility-aware rules
    # as everything else.
    idspec = spec_for(
        ("batch",) + (None,) * (ids.ndim - 1), ids.shape, mesh
    )
    bspec = idspec[0] if len(idspec) else None

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P("model", None), idspec),
        out_specs=P(*((bspec,) + (None,) * ids.ndim)),
    )
    def lookup(tbl, ids_):
        vloc = tbl.shape[0]
        off = jax.lax.axis_index("model") * vloc
        loc = ids_ - off
        ok = (loc >= 0) & (loc < vloc)
        out = jnp.take(tbl, jnp.clip(loc, 0, vloc - 1), axis=0)
        out = jnp.where(ok[..., None], out, 0)
        return jax.lax.psum(out, "model")

    return lookup(table, ids)


def unembed_apply(p, x):
    """Logits; shares the embedding table when tied."""
    return jnp.einsum("...d,vd->...v", x, p["embedding"].astype(x.dtype))


# ------------------------------ RoPE ----------------------------------------


def rope(x: jax.Array, positions: jax.Array, theta: float = 1e4):
    """x: (..., n, h, dh) or (..., n, dh); positions: (..., n)."""
    dh = x.shape[-1]
    half = dh // 2
    freqs = 1.0 / (
        theta ** (jnp.arange(0, half, dtype=jnp.float32) / half)
    )
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., n, half)
    if x.ndim == ang.ndim + 1:  # extra heads dim
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)


def sinusoidal_pos(n: int, d: int, dtype=jnp.float32):
    pos = jnp.arange(n, dtype=jnp.float32)[:, None]
    dim = jnp.arange(d // 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, 2 * dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


# ------------------------------ MLPs ----------------------------------------


def mlp_specs(d: int, d_ff: int, act: str):
    if act == "swiglu":
        return {
            "wi_gate": dense_specs(d, d_ff),
            "wi_up": dense_specs(d, d_ff),
            "wo": dense_specs(d_ff, d, axes=("ff", "embed")),
        }
    if act in ("squared_relu", "gelu", "relu"):
        return {
            "wi": dense_specs(d, d_ff),
            "wo": dense_specs(d_ff, d, axes=("ff", "embed")),
        }
    raise ValueError(act)


def mlp_apply(p, x, act: str):
    from ..distributed.sharding import constrain

    if act == "swiglu":
        g = constrain(dense_apply(p["wi_gate"], x), ("batch", None, "ff"))
        u = constrain(dense_apply(p["wi_up"], x), ("batch", None, "ff"))
        return dense_apply(p["wo"], jax.nn.silu(g) * u)
    h = constrain(dense_apply(p["wi"], x), ("batch", None, "ff"))
    if act == "squared_relu":
        h = jnp.square(jax.nn.relu(h))
    elif act == "gelu":
        h = jax.nn.gelu(h)
    else:
        h = jax.nn.relu(h)
    return dense_apply(p["wo"], h)

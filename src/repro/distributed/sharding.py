"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

``RULES`` maps logical axis names to mesh axes.  ``spec_for`` resolves a
tuple of logical names into a PartitionSpec against a concrete mesh,
dropping (a) mesh axes that don't exist (single-pod meshes have no "pod")
and (b) assignments whose dimension is not divisible by the axis size
(e.g. 24 heads on a 16-wide model axis -> replicate), or whose shards
would not be a multiple of ``SHARD_MULTIPLE``.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.param import is_spec

# logical axis -> mesh axes (tuple = try in order, use all present)
RULES = {
    "batch": ("pod", "data"),
    "seq": "model",  # sequence parallelism on the residual stream
    "vocab": "model",
    # FSDP: weight-matrix input dims shard over the data axis; GSPMD
    # all-gathers one layer's params inside the layer scan (ZeRO-3 style).
    "embed": "data",
    "embed_out": "model",
    "q_heads": "model",
    "q_heads_flat": "model",
    "kv_heads": "model",
    "kv_heads_flat": "model",
    "head_dim": None,
    "ff": "model",
    "expert_ff": None,
    "experts": "model",
    "inner": "model",  # mamba d_inner
    "state": None,
    "conv": None,
    "layers": None,
    None: None,
}

# A logical axis sharded only when each shard keeps a multiple of this many
# entries.  The TPU tiles the second-minor dim in 8 rows; with a sequence
# shard that is not a multiple of 8 (300 tokens over 4 devices = 75), XLA's
# TPU compiler refuses the bitcast between the sequence-sharded residual
# stream and its feature-sharded projections ("Bitcast cannot have
# different shape sizes").  Such a sequence stays replicated.
SHARD_MULTIPLE = {"seq": 8}


def _axes_for(name, mesh: Mesh, dim: int, rules=None) -> Optional[Tuple[str, ...]]:
    rules = rules or RULES
    cand = rules.get(name, None)
    if cand is None:
        return None
    if isinstance(cand, str):
        cand = (cand,)
    present = tuple(a for a in cand if a in mesh.axis_names)
    if not present:
        return None
    multiple = SHARD_MULTIPLE.get(name, 1)
    # drop mesh axes from the left ("pod" first etc.) until the dim splits
    for i in range(len(present)):
        sub = present[i:]
        size = int(np.prod([mesh.shape[a] for a in sub]))
        if dim % (size * multiple) == 0:
            return sub
    return None


def spec_for(axes, shape, mesh: Mesh, rules=None) -> P:
    parts = []
    used = set()
    for name, dim in zip(axes, shape):
        ax = _axes_for(name, mesh, dim, rules)
        if ax is None or any(a in used for a in ax):
            parts.append(None)
        else:
            used.update(ax)
            parts.append(ax if len(ax) > 1 else ax[0])
    return P(*parts)


def param_shardings(specs, mesh: Mesh, rules=None):
    """Spec pytree -> NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, spec_for(s.axes, s.shape, mesh, rules)),
        specs,
        is_leaf=is_spec,
    )


def opt_state_shardings(specs, mesh: Mesh, *, zero1: bool = True, rules=None):
    """Moment shardings: like params, plus ZeRO-1 over the data axis.

    For each param whose sharding leaves a dimension replicated and
    divisible by the data axis, the first such dim is additionally sharded
    over ("data",) — distributing optimizer memory across DP ranks.
    """

    def one(s):
        p = spec_for(s.axes, s.shape, mesh, rules)
        parts = list(p) + [None] * (len(s.shape) - len(p))
        if zero1 and "data" in mesh.axis_names:
            dsize = mesh.shape["data"]
            used = {a for part in parts if part for a in (
                part if isinstance(part, tuple) else (part,))}
            if "data" not in used:
                for i, (part, dim) in enumerate(zip(parts, s.shape)):
                    if part is None and dim % dsize == 0 and dim >= dsize:
                        parts[i] = "data"
                        break
        return NamedSharding(mesh, P(*parts))

    return jax.tree.map(one, specs, is_leaf=is_spec)


def constrain(x, logical_axes, mesh: Mesh = None, rules=None):
    """with_sharding_constraint by logical names (no-op outside a mesh)."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    spec = spec_for(logical_axes, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _current_mesh():
    try:
        from jax._src.mesh import thread_resources

        m = thread_resources.env.physical_mesh
        return None if m.empty else m
    except Exception:
        return None


def batch_sharding(mesh: Mesh, shape) -> NamedSharding:
    """(batch, ...) inputs: batch over pod+data (with divisibility fallback)."""
    axes = ("batch",) + (None,) * (len(shape) - 1)
    return NamedSharding(mesh, spec_for(axes, shape, mesh))

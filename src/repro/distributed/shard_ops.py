"""shard_map dispatch of the fused HLA kernels over a (data, model) mesh.

The chunkwise Pallas kernels run on a ``(BH, n_chunks)`` grid whose rows —
(batch, head) pairs — are completely independent: the chunk scan carries
state only along time, never across rows.  Head-sharding therefore
commutes with the chunk scan (DESIGN.md §9), and the whole training /
prefill / decode family shards the same way:

* batch rows over the ("pod", "data") axes,
* head rows over the "model" axis,
* time and feature dims replicated (the scan is local to a row).

``call_sharded`` wraps any row-major kernel op (every array input/output
has leading ``(B, H)`` dims — q/k/v, gamma, state-tuple leaves) in a
``shard_map`` over the active mesh so each device runs the *fused Pallas
kernel on its local row block*.  Under ``jax.grad`` the kernels' custom
VJPs apply per shard, which is exact: dq/dk/dv/dgamma are row-local, so
no cross-shard reduction is needed inside the op (weight-gradient
reductions happen outside, in GSPMD-land).

Divisibility fallback mirrors ``sharding.spec_for``: axes that do not
divide the row grid are dropped (worst case: direct un-shard_map'd call,
which GSPMD handles as before).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from .sharding import _current_mesh


def row_axes(mesh, B: int, H: int):
    """(batch_axes, head_axes) for a (B, H, ...) row grid, or None when no
    present mesh axis divides it (caller should fall back to direct call)."""
    if mesh is None or mesh.empty:
        return None
    names = mesh.axis_names
    batch = tuple(a for a in ("pod", "data") if a in names)
    while batch and B % int(np.prod([mesh.shape[a] for a in batch])) != 0:
        batch = batch[1:]  # drop "pod" first, like sharding._axes_for
    head = ()
    if "model" in names and H % mesh.shape["model"] == 0:
        head = ("model",)
    if not batch and not head:
        return None
    return batch, head


def _row_spec(axes, ndim: int) -> P:
    batch, head = axes
    b = batch if len(batch) > 1 else (batch[0] if batch else None)
    h = head[0] if head else None
    return P(*((b, h) + (None,) * (ndim - 2)))


def call_sharded(fn, *args, mesh=None, out_ndims=None):
    """Run ``fn(*args)`` with (B, H) rows sharded over the active mesh.

    Every array leaf of ``args`` and of ``fn``'s output must carry leading
    ``(B, H)`` dims (scalars/None pass through as pytree non-leaves).
    Outside a mesh — or when neither axis divides the row grid — this is
    exactly ``fn(*args)``.

    ``out_ndims``: pytree matching ``fn``'s output structure with each
    leaf's rank as an int.  Callers that know their output structure pass
    it to skip the ``jax.eval_shape`` fallback, which would trace the
    whole kernel op a second time per compile (and double-count
    ``kernels.ops.TRACE_COUNTS``).
    """
    mesh = mesh if mesh is not None else _current_mesh()
    leaves = jax.tree.leaves(args)
    if not leaves:
        return fn(*args)
    B, H = leaves[0].shape[:2]
    axes = row_axes(mesh, B, H)
    if axes is None:
        return fn(*args)
    in_specs = jax.tree.map(lambda x: _row_spec(axes, x.ndim), args)
    if out_ndims is None:
        out_specs = jax.tree.map(
            lambda x: _row_spec(axes, x.ndim), jax.eval_shape(fn, *args)
        )
    else:
        out_specs = jax.tree.map(lambda nd: _row_spec(axes, nd), out_ndims)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )(*args)

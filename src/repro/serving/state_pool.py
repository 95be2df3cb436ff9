"""Per-slot decode-state pool with structural slot-axis detection.

The old serving loop snapshotted the whole state tree and "restored" other
slots with a shape heuristic (``leaf.shape[1] == slots``) — which misfires
whenever an unrelated state dimension happens to equal the slot count, and
silently skips leaves without a slot axis at position 1.  ``StatePool``
instead *derives* each leaf's slot axis structurally: it abstractly
evaluates the state template at ``slots`` and ``slots + 1`` and takes the
(unique) axis whose extent changed.  Leaves whose shape does not depend on
the slot count get no slot axis and are left untouched by per-slot writes.

Admission and eviction are **scatter-based**: one
``lax.dynamic_update_slice`` per leaf at the detected axis — no full-tree
snapshot/restore, no host round-trips.

``snapshot_slot``/``restore_slot`` expose per-slot O(state) checkpointing
for speculative-decoding rollback (DESIGN.md §10): with the paper's
constant-size streaming states the rollback unit is a few small tensors
per layer, independent of context length — not a KV-cache truncation.
"""

from __future__ import annotations

from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp


def tree_finite(tree) -> jnp.ndarray:
    """Scalar bool: every inexact leaf of ``tree`` is fully finite.

    Jit-safe; used by the engine's prefill to reject a poisoned admission
    on the sync that already fetches the first sampled token."""
    ok = jnp.asarray(True)
    for leaf in jax.tree.leaves(tree):
        if jnp.issubdtype(leaf.dtype, jnp.inexact):
            ok = ok & jnp.all(jnp.isfinite(leaf))
    return ok


def _slot_axis(shape_a, shape_b, slots: int) -> Optional[int]:
    """Axis along which ``shape_b`` (slots+1) grew out of ``shape_a`` (slots)."""
    if tuple(shape_a) == tuple(shape_b):
        return None
    if len(shape_a) != len(shape_b):
        raise ValueError(
            f"state leaf rank depends on the slot count: {shape_a} vs {shape_b}"
        )
    diffs = [i for i, (x, y) in enumerate(zip(shape_a, shape_b)) if x != y]
    if len(diffs) != 1 or shape_b[diffs[0]] != shape_a[diffs[0]] + 1:
        raise ValueError(
            f"ambiguous slot axis for state leaf {shape_a} -> {shape_b}"
        )
    return diffs[0]


class StatePool:
    """Owns the pooled decode states for ``slots`` concurrent requests.

    ``template_fn(n)`` builds the state pytree for ``n`` slots (e.g.
    ``lambda n: lm.lm_init_states(cfg, n, max_len)``).  It is evaluated
    abstractly (``jax.eval_shape``) at ``slots`` and ``slots + 1`` to
    detect slot axes, and concretely once at ``slots`` for the pool.

    ``shardings`` (optional NamedSharding pytree matching the pooled state
    tree, from ``distributed.steps.state_shardings_for``) places the pool
    explicitly on a mesh — slots over the data axis, heads over the model
    axis — and pins every scatter write's output layout so admissions
    never let GSPMD drift the pool back to replicated.
    """

    def __init__(self, template_fn: Callable[[int], Any], slots: int,
                 shardings=None, donate: bool = False):
        if slots < 1:
            raise ValueError("need at least one slot")
        self.slots = slots
        self._template_fn = template_fn
        self.shardings = shardings
        # built by one program: op by op, a stacked template holds a
        # layer's leaf, its copy and its broadcast at once (with KV caches
        # that overran the chip's memory), and sharded, the full pool
        # would be materialised replicated on one device first
        self.states = jax.jit(
            lambda: template_fn(slots), out_shardings=shardings
        )()
        self._zeros = jax.jit(
            lambda: jax.tree.map(jnp.zeros_like, template_fn(1)))
        shapes_n = jax.eval_shape(lambda: template_fn(slots))
        shapes_n1 = jax.eval_shape(lambda: template_fn(slots + 1))
        leaves_n, self._treedef = jax.tree.flatten(shapes_n)
        leaves_n1 = jax.tree.leaves(shapes_n1)
        self.slot_axes: List[Optional[int]] = [
            _slot_axis(a.shape, b.shape, slots)
            for a, b in zip(leaves_n, leaves_n1)
        ]

        axes = self.slot_axes

        def _write(pool_leaves, new_leaves, slot):
            zero = jnp.zeros_like(slot)
            out = []
            for ax, pooled, new in zip(axes, pool_leaves, new_leaves):
                if ax is None:
                    out.append(pooled)
                    continue
                starts = [zero] * pooled.ndim
                starts[ax] = slot
                out.append(
                    jax.lax.dynamic_update_slice(
                        pooled, new.astype(pooled.dtype), tuple(starts)
                    )
                )
            return out

        def _read(pool_leaves, slot):
            zero = jnp.zeros_like(slot)
            out = []
            for ax, pooled in zip(axes, pool_leaves):
                if ax is None:
                    out.append(pooled)
                    continue
                starts = [zero] * pooled.ndim
                starts[ax] = slot
                sizes = list(pooled.shape)
                sizes[ax] = 1
                out.append(
                    jax.lax.dynamic_slice(pooled, tuple(starts), tuple(sizes))
                )
            return out

        # ``donate``: a write updates the pool's buffers in place (a pool
        # of KV caches has no room for a second copy); the arrays of
        # ``states`` before a write are then gone
        donated = (0,) if donate else ()
        if shardings is None:
            self._write = jax.jit(_write, donate_argnums=donated)
        else:
            self._write = jax.jit(
                _write, out_shardings=jax.tree.leaves(shardings),
                donate_argnums=donated,
            )
        self._read = jax.jit(_read)

    # -- tree plumbing ------------------------------------------------------

    def _flatten(self, tree):
        leaves, td = jax.tree.flatten(tree)
        if td != self._treedef:
            raise ValueError(
                "state tree structure does not match the pool template"
            )
        return leaves

    def empty_slot_state(self):
        """A fresh single-slot state (what an admitted request starts from)."""
        return self._template_fn(1)

    # -- scatter admit / evict ---------------------------------------------

    def write_slot(self, slot: int, state) -> None:
        """Scatter a single-slot state (slot-dim 1 leaves) into ``slot``.

        Only the target slot's data changes; leaves without a slot axis
        (shared across slots) are left as-is.
        """
        new_leaves = self._write(
            self._flatten(self.states), self._flatten(state),
            jnp.int32(slot),
        )
        self.states = jax.tree.unflatten(self._treedef, new_leaves)

    def read_slot(self, slot: int, states=None):
        """Gather ``slot``'s state as a single-slot tree (slot dims = 1).

        ``states`` reads from an alternate pooled tree with the pool's
        structure (e.g. a snapshot taken before a speculative-verify
        round) instead of the live pool.
        """
        src = self.states if states is None else states
        leaves = self._read(self._flatten(src), jnp.int32(slot))
        return jax.tree.unflatten(self._treedef, leaves)

    def reset_slot(self, slot: int) -> None:
        """Zero a slot (eviction)."""
        self.write_slot(slot, self._zeros())

    # -- health -------------------------------------------------------------

    def finite_mask(self, states=None) -> jnp.ndarray:
        """``(slots,)`` bool: True where every inexact state leaf of that
        slot is fully finite — the fused device-side reduction behind
        poisoned-state quarantine (DESIGN.md §12).

        Jit-safe: the engine computes it INSIDE the decode block so the
        flags ride the block's existing once-per-block host transfer —
        detecting a NaN-poisoned slot costs zero extra round trips.
        Leaves without a slot axis are shared across slots, so a
        non-finite shared leaf poisons every slot (there is no smaller
        recovery unit).  Integer leaves cannot be non-finite and are
        skipped.
        """
        src = self.states if states is None else states
        ok = jnp.ones((self.slots,), bool)
        for ax, leaf in zip(self.slot_axes, self._flatten(src)):
            if not jnp.issubdtype(leaf.dtype, jnp.inexact):
                continue
            fin = jnp.isfinite(leaf)
            if ax is None:
                ok = ok & jnp.all(fin)
            else:
                ok = ok & jnp.all(
                    fin, axis=tuple(i for i in range(leaf.ndim) if i != ax)
                )
        return ok

    # -- snapshot / rollback (speculative decoding) -------------------------

    def snapshot_slot(self, slot: int, *, host: bool = False):
        """O(state) snapshot of one slot's decode state.

        This is what makes rejection in speculative decoding cheap for
        constant-state architectures: the entire rollback unit is one
        small state tuple per layer (KiB-scale), gathered with a
        ``dynamic_slice`` per leaf — no KV-cache truncation, no tree
        surgery, no growth with context length.

        ``host=True`` returns numpy leaves instead of device arrays:
        long-lived snapshots (the prefix/state cache holds hundreds of
        them) then live in host RAM and consume zero HBM — and they stay
        valid across pool resharding.  The transfer is a deliberate
        device sync; callers on the hot path should keep ``host=False``.
        """
        snap = self.read_slot(slot)
        if not host:
            return snap
        return jax.device_get(snap)  # sync-point: host-RAM state snapshot

    def restore_slot(self, slot: int, snapshot) -> None:
        """Roll ``slot`` back to ``snapshot`` (from ``snapshot_slot`` — host
        or device — or a replayed correction) in O(state): one scatter
        write per leaf.  Host (numpy) snapshots are re-placed as part of
        the write: the jitted scatter's ``out_shardings`` pin the result
        to the pool's NamedShardings, so a cache entry snapshotted from
        one mesh layout restores correctly onto the pool's current one.
        Other slots' states are untouched, so a rejected continuation
        never perturbs concurrently-decoding requests.
        """
        self.write_slot(slot, snapshot)

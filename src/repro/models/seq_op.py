"""The ``SequenceOp`` registry — ONE operator API across the whole stack.

The paper positions higher-order attention as one of several
interchangeable causal streaming mixers (§5.2, "drop-in attention
replacement").  This module makes that interchangeability structural:
every sequence-mixing operator — the HLA family, softmax attention,
Mamba, RWKV-6, GLA, and whatever comes next — registers **one record**
describing everything the rest of the system needs:

* ``specs(cfg)``         — parameter specs for the sublayer;
* ``forward(p, x, cfg, *, state, want_state, positions)``
                         — full-sequence apply (train / chunk-parallel
                           prefill); returns ``(y, new_state)``;
* ``step(p, x_t, state, cfg, *, positions)``
                         — one-token decode; returns ``(y, new_state)``;
* ``init_state(cfg, B, *, max_len, dtype)`` / ``state_axes(cfg)`` /
  ``state_ndims(cfg)``   — the decode-state tree, its logical sharding
                           axes (the single source of truth consumed by
                           ``distributed.steps`` and the serving
                           ``StatePool``), and per-leaf ranks (for
                           ``shard_ops.call_sharded`` without an
                           ``eval_shape`` re-trace);
* capability flags       — ``streaming`` (O(1)-state decode; the serving
                           engine derives admissibility from this, not a
                           hardcoded tuple), ``has_fused_kernels``
                           (Pallas train/prefill/decode paths — selected
                           INSIDE the record, callers never see
                           Pallas-vs-jnp), ``spec_decodable``
                           (snapshot/rollback-safe state, required for
                           speculative decoding), ``needs_positions``
                           (consumes absolute positions, e.g. RoPE),
                           ``self_contained`` (owns its norms + channel
                           mix, replacing the whole block — RWKV-6),
                           ``prealloc_state`` (prefill must write into a
                           preallocated state, e.g. a KV cache).

``models/lm.py``, ``models/whisper.py``, ``serving/engine.py``,
``serving/spec/*`` and ``distributed/steps.py`` program against this
interface only.  Before this registry the repo carried five hand-synced
``variant ==`` / ``kind ==`` ladders; two PR-4 serving crashes
(hla3_paper state-tree mismatch, rwkv6 dtype carry) came from exactly
those ladders drifting apart.  A CI grep-guard now keeps dispatch out of
every other module.

Adding an operator is a one-file change: write the module, call
``register_op`` at import time (see ``models/gla.py`` for the worked
example), and list it in ``_BUILTIN_MODULES`` (or import it from your
launcher).  ``lm``/``engine``/``steps`` pick it up untouched.
"""

from __future__ import annotations

import dataclasses
import difflib
import functools
import importlib
from typing import Any, Callable, Dict, Optional, Tuple

import jax


class SequenceOpError(KeyError):
    """Unknown / duplicate operator — message lists the registry contents."""


@dataclasses.dataclass(frozen=True, eq=False)
class SequenceOp:
    """One registered sequence-mixing operator (see module docstring).

    ``forward``/``step`` receive the operator's OWN param subtree (what
    ``specs(cfg)`` declared), the residual-stream input, and the model
    config; state trees are whatever ``init_state`` returns — opaque to
    every caller.
    """

    name: str
    specs: Callable[[Any], Any]
    forward: Callable[..., Tuple[jax.Array, Any]]
    init_state: Callable[..., Any]
    state_axes: Callable[[Any], Any]
    step: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    state_ndims: Optional[Callable[[Any], Any]] = None
    # capability flags
    streaming: bool = False
    has_fused_kernels: bool = False
    spec_decodable: bool = False
    needs_positions: bool = False
    self_contained: bool = False
    prealloc_state: bool = False
    # optional analytic-cost override consumed by ``repro.obs.costs``:
    # ``cost_model(cfg, *, mode, seq_len, batch) -> dict`` may return
    # ``state_flops_per_token`` and/or ``state_bytes_per_token`` to
    # replace the builtin family formula for this op's state math
    # (projection FLOPs and state bytes always derive from the record's
    # own specs/init_state).  See ``models/gla.py`` for the worked
    # example and ``docs/DESIGN.md`` §15 for the contract.
    cost_model: Optional[Callable[..., Dict[str, float]]] = None
    # optional one-token decode on the LAYER-STACKED state tree:
    # ``step_at(p, x_t, stack, index, cfg, *, positions) -> (y, stack)``
    # updates layer ``index``'s part of ``stack`` in place (a scatter into
    # the stacked leaves), so a decode scan can carry a state too large to
    # slice out and back per layer (a KV cache) — see ``lm.lm_apply``
    step_at: Optional[Callable[..., Tuple[jax.Array, Any]]] = None
    # key the operator's params live under inside a layer's param dict
    # (kept stable for existing checkpoints: HLA family -> "mixer")
    param_key: Optional[str] = None

    def __post_init__(self):
        if self.param_key is None:
            object.__setattr__(self, "param_key", self.name)
        if self.streaming and self.step is None:
            raise SequenceOpError(
                f"op {self.name!r}: streaming=True requires a step()"
            )

    def resolve_state_ndims(self, cfg):
        """Per-leaf ranks of the state tree (``state_ndims`` override, or
        derived abstractly from ``init_state`` — no allocation)."""
        if self.state_ndims is not None:
            return self.state_ndims(cfg)
        abstract = jax.eval_shape(
            functools.partial(self.init_state, cfg, 1, max_len=8)
        )
        return jax.tree.map(lambda leaf: leaf.ndim, abstract)


_REGISTRY: Dict[str, SequenceOp] = {}

# Modules imported (lazily, on first registry access) for their
# ``register_op`` side effect.  Each entry is the whole integration of an
# operator: lm / serving / distributed never name them.
_BUILTIN_MODULES = ("attention", "mixer", "ssm", "rwkv6", "gla")
_loaded_modules: set = set()
_loading = False


def register_op(op: SequenceOp) -> SequenceOp:
    """Register ``op`` under ``op.name`` (the public extension point).

    Raises ``SequenceOpError`` on duplicate names — two records for one
    name is exactly the drift the registry exists to prevent.
    """
    if not isinstance(op, SequenceOp):
        raise TypeError(f"register_op expects a SequenceOp, got {type(op)}")
    if op.name in _REGISTRY:
        raise SequenceOpError(
            f"sequence op {op.name!r} is already registered; "
            f"registered ops: {sorted(_REGISTRY)}"
        )
    _REGISTRY[op.name] = op
    return op


def _ensure_builtins() -> None:
    """Import the builtin operator modules for their ``register_op`` side
    effect.  Per-module success tracking: a failed import raises NOW and
    is retried on the next registry access — never silently leaving a
    partial registry behind an 'unknown op' error.  ``_loading`` guards
    re-entrancy (a builtin module calling back into the registry while
    its siblings are still importing)."""
    global _loading
    if _loading or len(_loaded_modules) == len(_BUILTIN_MODULES):
        return
    _loading = True
    try:
        for mod in _BUILTIN_MODULES:
            if mod not in _loaded_modules:
                importlib.import_module(f".{mod}", __package__)
                _loaded_modules.add(mod)
    finally:
        _loading = False


def _unknown(name: str) -> SequenceOpError:
    known = sorted(_REGISTRY)
    close = difflib.get_close_matches(str(name), known, n=1)
    hint = f" (did you mean {close[0]!r}?)" if close else ""
    return SequenceOpError(
        f"unknown sequence op {name!r}{hint}; registered ops: {known}"
    )


def get_op(name: str) -> SequenceOp:
    """Look up a registered operator; unknown names fail with the full
    registry listing and the closest match (config typos are actionable)."""
    _ensure_builtins()
    if name not in _REGISTRY:
        raise _unknown(name)
    return _REGISTRY[name]


def registered_op_names() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(sorted(_REGISTRY))


def streaming_op_names() -> Tuple[str, ...]:
    _ensure_builtins()
    return tuple(
        sorted(n for n, op in _REGISTRY.items() if op.streaming)
    )


def op_name_for(cfg) -> str:
    """The operator a ``ModelConfig`` requests.

    ``cfg.mixer`` names it directly ("softmax" is the legacy spelling of
    "attn").  There is deliberately NO silent fallback: a typo'd mixer
    used to fall through to ``cfg.hla.variant`` and train hla2 under a
    wrong name (the identical-losses bug noted in the old mixer module).
    """
    _ensure_builtins()
    name = "attn" if cfg.mixer == "softmax" else cfg.mixer
    if name not in _REGISTRY:
        raise _unknown(cfg.mixer)
    return name


def op_for(cfg) -> SequenceOp:
    """Resolve ``cfg`` to its registered ``SequenceOp``."""
    return _REGISTRY[op_name_for(cfg)]

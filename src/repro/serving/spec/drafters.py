"""Drafters: who proposes the k tokens the target model verifies.

A ``Drafter`` mirrors the engine's slot lifecycle (``admit`` / ``commit``
/ ``evict``) and produces, per round, ``k`` draft tokens for a batch of
active slots (``propose``).  Two implementations:

* ``NGramDrafter`` — model-free prompt-lookup (Saxena; HF
  "prompt lookup decoding"): match the trailing n-gram of the committed
  context against its own history and propose the continuation of the
  most recent earlier occurrence.  Zero FLOPs, surprisingly strong on
  repetitive / extractive workloads, and the baseline every learned
  drafter must beat.
* ``HLADrafter`` — a small HLA draft LM with its OWN parameters and its
  OWN ``StatePool`` slots (one per engine slot), loadable from any
  ``configs/`` registry entry.  Drafting is one jitted device call per
  round batched over all slots: first a masked scan consumes the tokens
  the verifier committed since the last round (so the draft state tracks
  the committed context without ever keeping speculative tokens — the
  draft model's OWN rollback is simply "don't commit the draft-time
  states"), then k greedy/sampled single-token steps propose the block.
  Under a mesh the draft pool's states are placed by the same per-module
  ``*_state_axes`` declarations the target uses
  (``distributed.steps.state_shardings_for``), and its kernel calls go
  through ``shard_ops.call_sharded`` exactly like the target's.

``propose`` may return jax arrays (device-resident; the engine feeds them
straight into the verify block without a host sync) or numpy arrays.
"""

from __future__ import annotations

import abc
import contextlib
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ...models import lm, seq_op
from ...models.param import init_params
from ..sampling import SamplingConfig, probs, sample
from ..state_pool import StatePool
from .verify import make_replay


class Drafter(abc.ABC):
    """Slot-parallel draft-token source for speculative decoding."""

    #: True when ``propose`` returns per-token draft distributions (the
    #: warped q of speculative sampling); False means deterministic drafts
    #: (q = one-hot) — the verifier's accept rule adapts accordingly.
    emits_probs: bool = False
    #: Token-id space drafts come from, or None when proposals are always
    #: drawn from the committed context (n-gram) and thus always valid.
    #: The engine rejects drafters whose vocab differs from the target's —
    #: out-of-range draft ids would index the target embedding OOB.
    vocab: Optional[int] = None
    #: True when ``propose`` returns rows for EVERY pool slot (device
    #: drafters batched over the whole pool; inactive rows are garbage the
    #: verify round masks out).  Saves the engine a gather-then-scatter
    #: round trip per round on the latency-critical path.  False (host
    #: drafters) means rows align with ``slot_ids``.
    full_width: bool = False

    @abc.abstractmethod
    def admit(self, slot: int, tokens: Sequence[int]) -> None:
        """A request entered ``slot``; ``tokens`` = prompt + first sampled
        token (the committed context so far)."""

    @abc.abstractmethod
    def commit(self, slot: int, tokens: Sequence[int]) -> None:
        """The verifier committed ``tokens`` (accepted prefix + the
        corrected/bonus token) to a live slot."""

    @abc.abstractmethod
    def propose(self, slot_ids: Sequence[int], k: int) -> Tuple:
        """Draft ``k`` tokens for each slot in ``slot_ids``.

        Returns ``(drafts, q)``: drafts ``(len(slot_ids), k)`` int32 (jax
        or numpy; ``(pool_slots, k)`` when ``full_width``), and ``q``
        either ``None`` (deterministic) or the matching
        ``(..., k, vocab)`` draft distributions.
        """

    def evict(self, slot: int) -> None:  # optional cleanup
        return None


# --------------------------------------------------------------------------
# model-free: prompt-lookup n-gram drafter
# --------------------------------------------------------------------------


class NGramDrafter(Drafter):
    """Propose the continuation of the last earlier occurrence of the
    trailing n-gram (n from ``max_n`` down to ``min_n``) of the committed
    context; fall back to repeating the last token.  O(len(ctx) * n) per
    proposal on the host — negligible next to a model forward at serving
    block sizes.
    """

    emits_probs = False

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if min_n < 1 or max_n < min_n:
            raise ValueError("need max_n >= min_n >= 1")
        self.max_n, self.min_n = max_n, min_n
        self._ctx = {}

    def admit(self, slot, tokens):
        self._ctx[slot] = list(int(t) for t in tokens)

    def commit(self, slot, tokens):
        self._ctx[slot].extend(int(t) for t in tokens)

    def evict(self, slot):
        self._ctx.pop(slot, None)

    def _draft_one(self, ctx: List[int], k: int) -> List[int]:
        for n in range(self.max_n, self.min_n - 1, -1):
            if len(ctx) < n + 1:
                continue
            pat = ctx[-n:]
            # rightmost earlier occurrence = most recent evidence (the
            # search range excludes the trailing n-gram itself, so every
            # match has a nonempty continuation)
            for i in range(len(ctx) - n - 1, -1, -1):
                if ctx[i:i + n] == pat:
                    cont = ctx[i + n:i + n + k]
                    while len(cont) < k:
                        cont.append(cont[-1])
                    return cont
        return [ctx[-1]] * k

    def propose(self, slot_ids, k):
        drafts = np.asarray(
            [self._draft_one(self._ctx[s], k) for s in slot_ids], np.int32
        )
        return drafts, None


# --------------------------------------------------------------------------
# model drafter: small HLA LM over its own state pool
# --------------------------------------------------------------------------


class HLADrafter(Drafter):
    """A small streaming-state draft LM sharing the engine's slot layout.

    ``cfg`` is any streaming-mixer ``ModelConfig`` (resolve one with
    ``configs.get_config(name, reduced=...)``); ``params`` its weights
    (randomly initialized from ``seed`` when omitted — fine for plumbing
    tests, useless acceptance: load trained draft weights for real
    serving).  ``sampling`` controls the draft law; non-greedy drafters
    emit their warped q so the verifier can run distribution-preserving
    speculative sampling.

    ``full_width``: proposals stay device-resident for ALL pool slots —
    the engine feeds them straight into the verify block with no host
    sync and no gather/scatter round trip.
    """

    full_width = True

    def __init__(self, cfg, params=None, *, slots: int, max_len: int,
                 k: int, sampling: SamplingConfig = SamplingConfig(),
                 seed: int = 0, mesh=None):
        ops = lm.stack_ops(cfg)
        if not all(op.streaming for op in ops):
            raise ValueError(
                f"HLADrafter needs streaming-state ops "
                f"{seq_op.streaming_op_names()}, got "
                f"{[op.name for op in ops]}"
            )
        self.cfg = cfg
        self.k = k
        self.sampling = sampling
        self.emits_probs = sampling.method != "greedy"
        self.vocab = cfg.vocab
        self.mesh = mesh
        if params is None:
            params = init_params(lm.lm_specs(cfg), jax.random.key(seed))
        self.params = params
        pool_shardings = None
        if mesh is not None:
            # draft-model states declared through the SAME per-module
            # *_state_axes scheme as the target's (DESIGN.md §9)
            from ...distributed import steps as steps_mod

            abstract = jax.eval_shape(
                lambda: lm.lm_init_states(cfg, slots, max_len)
            )
            pool_shardings = steps_mod.state_shardings_for(
                cfg, mesh, abstract
            )
        self.pool = StatePool(
            lambda n: lm.lm_init_states(cfg, n, max_len), slots,
            shardings=pool_shardings,
        )
        self.positions = jnp.zeros((slots, 1), jnp.int32)
        self.last = np.zeros(slots, np.int64)
        # committed tokens the draft state has not consumed yet (<= k+1
        # per slot between rounds: one round commits at most k+1 tokens)
        self._pending: List[List[int]] = [[] for _ in range(slots)]
        self.key = jax.random.key(seed + 1)

        scfg = sampling
        consume = make_replay(cfg)  # same masked scan as verify rollback

        def _propose(params, states, pending, pend_len, last_tok,
                     positions, key):
            # 1) masked consume of the last round's committed tokens
            states, positions = consume(
                params, states, pending, positions, pend_len
            )
            if pool_shardings is not None:
                states = jax.tree.map(
                    jax.lax.with_sharding_constraint, states, pool_shardings
                )

            # 2) k draft steps; the drafted-token state updates are NEVER
            # committed back (speculative state lives only in this scan —
            # the draft model's rollback is free)
            def draft(carry, key_j):
                st, pos, tok = carry
                logits, new_st, _ = lm.lm_apply(
                    params, tok, cfg, states=st, positions=pos,
                    mode="decode",
                )
                lg = logits[:, -1]
                nxt = sample(lg, key_j, scfg)
                qp = (probs(lg, scfg) if scfg.method != "greedy"
                      else jnp.zeros((lg.shape[0], 0), jnp.float32))
                return (new_st, pos + 1, nxt[:, None]), (nxt, qp)

            keys = jax.random.split(key, k)
            _, (drafts, qps) = jax.lax.scan(
                draft, (states, positions, last_tok), keys
            )
            # drafts: (k, slots) -> (slots, k); qps -> (slots, k, vocab)
            return states, positions, drafts.T, jnp.moveaxis(qps, 0, 1)

        self._propose = jax.jit(_propose)
        self._prefill = jax.jit(
            lambda params, prompt: lm.lm_prefill(params, prompt, cfg)[1]
        )

    def _mesh_ctx(self):
        return self.mesh if self.mesh is not None else (
            contextlib.nullcontext()
        )

    def admit(self, slot, tokens):
        toks = [int(t) for t in tokens]
        prompt = jnp.asarray(np.asarray(toks[:-1], np.int32)[None])
        with self._mesh_ctx():
            state1 = self._prefill(self.params, prompt)
            self.pool.write_slot(slot, state1)
        self.positions = self.positions.at[slot, 0].set(len(toks) - 1)
        self.last[slot] = toks[-1]
        self._pending[slot] = []

    def commit(self, slot, tokens):
        toks = [int(t) for t in tokens]
        # the draft state must end up having consumed everything except
        # the newest committed token (that token is the next model input)
        self._pending[slot].extend([int(self.last[slot])] + toks[:-1])
        if len(self._pending[slot]) > self.k + 1:
            raise RuntimeError(
                "draft state fell behind: propose() must run between "
                "commits (pending > k+1 tokens)"
            )
        self.last[slot] = toks[-1]

    def evict(self, slot):
        self._pending[slot] = []
        self.last[slot] = 0

    def propose(self, slot_ids, k):
        if k != self.k:
            raise ValueError(f"drafter built for k={self.k}, asked for {k}")
        slots = self.pool.slots
        width = self.k + 1
        pending = np.zeros((slots, width), np.int32)
        pend_len = np.zeros(slots, np.int32)
        for s in range(slots):
            p = self._pending[s]
            pending[s, :len(p)] = p
            pend_len[s] = len(p)
            self._pending[s] = []
        self.key, sub = jax.random.split(self.key)
        with self._mesh_ctx():
            states, positions, drafts, qps = self._propose(
                self.params, self.pool.states, jnp.asarray(pending),
                jnp.asarray(pend_len),
                jnp.asarray(self.last[:, None].astype(np.int32)),
                self.positions, sub,
            )
        self.pool.states = states
        self.positions = positions
        return drafts, (qps if self.emits_probs else None)


# --------------------------------------------------------------------------
# factory
# --------------------------------------------------------------------------


def build_drafter(spec, *, slots: int, max_len: int,
                  sampling: SamplingConfig, mesh=None,
                  target_cfg=None) -> Drafter:
    """Resolve ``SpecConfig.drafter`` to an instance.

    Accepts a ready ``Drafter`` instance, ``"ngram"``, or ``"lm"`` (loads
    ``spec.draft_arch`` from the configs registry; random params unless
    the caller hands the engine a prebuilt drafter).  ``target_cfg``
    enables the not-actually-smaller draft-model warning.
    """
    if isinstance(spec.drafter, Drafter):
        return spec.drafter
    if spec.drafter == "ngram":
        return NGramDrafter(max_n=spec.ngram_max, min_n=spec.ngram_min)
    if spec.drafter == "lm":
        from ...configs import get_config

        cfg = get_config(spec.draft_arch, reduced=spec.draft_reduced)
        if target_cfg is not None:
            draft_cost = cfg.n_layers * cfg.d_model**2
            target_cost = target_cfg.n_layers * target_cfg.d_model**2
            if draft_cost >= target_cost:
                import warnings

                warnings.warn(
                    f"draft model {cfg.name!r} "
                    f"({cfg.n_layers}L x {cfg.d_model}d) is not smaller "
                    f"than the target ({target_cfg.n_layers}L x "
                    f"{target_cfg.d_model}d): drafting costs as much as "
                    "decoding, so speculative decode cannot win — point "
                    "draft_arch at a smaller registry entry",
                    stacklevel=2,
                )
        return HLADrafter(
            cfg, params=None, slots=slots, max_len=max_len, k=spec.k,
            sampling=sampling, seed=spec.draft_seed, mesh=mesh,
        )
    raise ValueError(f"unknown drafter {spec.drafter!r}")

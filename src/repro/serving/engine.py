"""Continuous-batching inference engine over the streaming-state models.

The serving pattern the paper's O(1)-state decode enables (DESIGN.md §8):

* **Admission = chunk-parallel prefill.**  A new prompt runs through
  ``lm.lm_prefill`` — per layer ONE chunkwise kernel call (the stateful
  Pallas kernel on TPU) that returns the exact streaming state by the
  Section-4 identity — then the state is scatter-written into its slot.
  No per-token Python loop, no device round-trip per prompt token, and no
  touching of other slots' states (the pool write is a single
  ``dynamic_update_slice`` per leaf).
* **Decode = step-locked device blocks.**  All slots advance together
  through a jitted ``lax.scan`` of ``block`` fused decode steps with
  device-side sampling; generated tokens accumulate on device and transfer
  to the host ONCE per block (vs. one ``int(...)`` sync per slot per step).
  Inactive slots ride along masked (their sampled tokens are discarded and
  their positions frozen); their stale states are overwritten at the next
  admission.
* **Speculative decode (``spec=``)** swaps the block for a
  draft -> verify -> accept round (DESIGN.md §10): a ``Drafter`` proposes
  k tokens per active slot (batched), then ONE jitted round
  (``spec.verify.make_spec_round``) scores all of them chunk-parallel,
  commits accepted tokens in bulk — up to k+1 tokens per round for the
  serial cost of one wide prefill — and, on rejection only (a
  ``lax.cond`` arm), rolls the pool back to the pre-verify states
  advanced by each slot's accepted prefix, so speculative greedy decode
  is token-for-token identical to plain greedy decode.
  ``StatePool.snapshot_slot``/``restore_slot`` expose the same O(state)
  rollback primitive at the host level (external schedulers,
  preemption, tests).  One host sync per round, as in the plain block
  path.
* **Failure domains (DESIGN.md §12).**  The slot is the unit of failure,
  exactly because the paper's state is constant-size: quarantining a
  poisoned request is one O(1) scatter (``StatePool.reset_slot``), not a
  paged-KV reconstruction.  Every per-request failure — invalid
  admission, a non-finite slot state (detected by a fused finiteness
  reduction riding the block's existing host sync), an expired
  ``deadline_s``, an ``Engine.cancel`` — frees only its own slot and
  becomes a ``GenResult.status`` (``ok``/``error``/``timeout``/
  ``cancelled``); ``run()`` never raises out of its drive loop (a CI
  guard enforces this).  Drafter failures trip a circuit breaker from
  speculative to plain block decode — which preserves greedy output
  token-for-token, since both paths emit the same argmax stream — with a
  cooldown/half-open re-probe to recover.  All failure modes are
  injectable deterministically via ``runtime.faults.FaultPlan``.

KV-cache (softmax / hybrid) archs are rejected: their pooled cache keeps a
*shared* scalar ``length``, so per-slot admission would need per-slot
lengths threaded through attention — a follow-up, not a serving-engine
concern (the HLA family is the paper's point).
"""

from __future__ import annotations

import collections
import collections.abc
import contextlib
import dataclasses
import math
import time
from typing import Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import lm, seq_op
from ..obs import Obs
from ..runtime.faults import FaultPlan
from .cache import PrefixCache, tree_bytes
from .sampling import SamplingConfig, sample
from .scheduler import Scheduler, SchedulerConfig
from .spec import SpecConfig, build_drafter
from .spec.verify import make_spec_round
from .state_pool import StatePool, tree_finite, tree_finite_host

#: legacy ``Engine.stats`` keys -> unlabeled registry counters
_STATS_COUNTERS = {
    "prefill_s": "serving_prefill_seconds_total",
    "decode_s": "serving_decode_seconds_total",
    "prompt_tokens": "serving_prompt_tokens_total",
    "generated_tokens": "serving_generated_tokens_total",
    "spec_rounds": "serving_spec_rounds_total",
    "spec_drafted": "serving_spec_drafted_total",
    "spec_accepted": "serving_spec_accepted_total",
    "spec_replays": "serving_spec_replay_rounds_total",
    "quarantined": "serving_quarantined_total",
    "breaker_trips": "serving_breaker_trips_total",
}
#: legacy keys that were request-status tallies -> the status label on
#: ``serving_requests_total``
_STATS_STATUS = {"errors": "error", "timeouts": "timeout",
                 "cancelled": "cancelled"}
#: legacy keys holding float seconds (everything else was an int count)
_STATS_FLOAT = frozenset(("prefill_s", "decode_s"))


class _StatsShim(collections.abc.MutableMapping):
    """DEPRECATED dict view of the engine's metrics (DESIGN.md §13).

    The old ad-hoc ``Engine.stats`` dict is now backed by the obs
    registry: reads compute from the live metric series, writes forward
    to them (``stats.update(decode_s=0.0, ...)`` resets, as the old
    warmup code relied on).  ``stats["ttft_s"]`` returns the TTFT
    histogram's BOUNDED recent-sample reservoir, not an unbounded list —
    under sustained traffic it holds the newest ``sample_cap`` values.
    New code should use ``engine.obs`` directly.
    """

    def __init__(self, obs: Obs):
        self._obs = obs

    def _keys(self):
        return list(_STATS_COUNTERS) + list(_STATS_STATUS) + ["ttft_s"]

    def __getitem__(self, key):
        if key == "ttft_s":
            return self._obs.registry.get("serving_ttft_seconds").recent()
        if key in _STATS_STATUS:
            return int(self._obs.registry.get("serving_requests_total")
                       .value(status=_STATS_STATUS[key]))
        name = _STATS_COUNTERS[key]
        total = self._obs.registry.get(name).total()
        return total if key in _STATS_FLOAT else int(total)

    def __setitem__(self, key, value):
        if key == "ttft_s":
            hist = self._obs.registry.get("serving_ttft_seconds")
            hist.reset()
            for v in value:
                hist.observe(float(v))
            return
        if key in _STATS_STATUS:
            self._obs.registry.get("serving_requests_total")._set(
                float(value), status=_STATS_STATUS[key]
            )
            return
        self._obs.registry.get(_STATS_COUNTERS[key])._set(float(value))

    def __delitem__(self, key):
        raise TypeError("Engine.stats keys are fixed")

    def __iter__(self):
        return iter(self._keys())

    def __len__(self):
        return len(self._keys())

    def __repr__(self):
        return f"EngineStats({dict(self)})"


@dataclasses.dataclass
class GenRequest:
    rid: int
    prompt: np.ndarray  # (L,) int token ids
    max_new: int = 32
    eos_id: Optional[int] = None
    # wall-clock budget in seconds, measured from submission (run() entry
    # or direct admit()).  Checked once per block on the host — expiry
    # finishes the slot with status="timeout" and the partial stream.
    deadline_s: Optional[float] = None
    # per-request sampling override (None = the engine's default).  The
    # decode block re-traces when the SET of distinct configs across slots
    # changes; homogeneous traffic stays at one trace.
    sampling: Optional[SamplingConfig] = None
    # scheduler policy inputs (DESIGN.md §16): lower priority numbers
    # drain first; tenants within a priority class share slots fairly.
    priority: int = 1
    tenant: str = "default"


@dataclasses.dataclass
class GenResult:
    rid: int
    tokens: List[int]
    ttft_s: float  # admission -> first sampled token
    prompt_len: int
    # "ok" | "error" | "timeout" | "cancelled".  Non-ok results keep the
    # partial stream committed before the failure (possibly empty).
    status: str = "ok"
    error: Optional[str] = None


class Engine:
    """Slot-based continuous batching over a ``StatePool``."""

    def __init__(
        self,
        cfg,
        params,
        *,
        slots: int = 4,
        max_len: int = 4096,
        sampling: SamplingConfig = SamplingConfig(),
        block: int = 8,
        seed: int = 0,
        mesh=None,
        spec: Optional[SpecConfig] = None,
        faults: Optional[FaultPlan] = None,
        obs: Optional[Obs] = None,
        cache: Optional[PrefixCache] = None,
        sched: Optional[SchedulerConfig] = None,
    ):
        # serveability is a REGISTRY capability, not a hardcoded tuple:
        # any op registered with streaming=True (O(1) decode state) admits
        # per-slot continuous batching; KV-cache ops (attn) and hybrid
        # stacks share a pooled scalar length across slots and cannot.
        op = seq_op.op_for(cfg)
        if not op.streaming or cfg.group_size:
            raise ValueError(
                "Engine serves streaming-state ops "
                f"{seq_op.streaming_op_names()}; op {op.name!r} "
                f"(group_size={cfg.group_size}) decodes from a KV cache "
                "whose pooled scalar length is shared across slots — "
                "continuous batching needs per-slot lengths"
            )
        if spec is not None and not op.spec_decodable:
            raise ValueError(
                f"op {op.name!r} is not registered spec_decodable: its "
                "state cannot be snapshot/rolled back for speculative "
                "verification"
            )
        self.cfg = cfg
        self.params = params
        self.sampling = sampling
        self.block = block
        self.max_len = max_len
        self.mesh = mesh
        self.spec = spec
        self.faults = faults
        # slot-count autoscaling (DESIGN.md §16): the pool is allocated
        # at the scheduler's max_slots once; the autoscaler varies how
        # many of those physical slots admissions may fill.  Without a
        # scheduler config the engine behaves exactly as before: a
        # fixed-``slots`` FIFO (same-priority single-tenant ordering is
        # arrival order).
        if sched is not None:
            slots = sched.max_slots
        self.sched_cfg = sched if sched is not None else SchedulerConfig(
            min_slots=slots, max_slots=slots
        )
        # sharded serving: slot states get explicit shardings (slots on
        # the data axis, heads on the model axis) from the same source of
        # truth the train/dry-run steps use — never a replicated tree.
        pool_shardings = None
        if mesh is not None:
            from ..distributed import steps as steps_mod

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
        self.tokens = jnp.zeros((slots, 1), jnp.int32)
        self.positions = jnp.zeros((slots, 1), jnp.int32)
        self.active = np.zeros(slots, bool)
        self._slot_req: List[Optional[GenRequest]] = [None] * slots
        self._slot_out: List[List[int]] = [[] for _ in range(slots)]
        self._slot_ttft: List[float] = [0.0] * slots
        self._slot_scfg: List[SamplingConfig] = [sampling] * slots
        self._slot_deadline: List[float] = [math.inf] * slots
        self._enqueue_t: Dict[int, float] = {}
        self._cancelled: Set[int] = set()
        self._popped: Set[int] = set()  # rids holding a fair-share ticket
        self.results: Dict[int, GenResult] = {}
        # per-token streaming hook (serving/server.py): called on the
        # drive loop with (rid, new_tokens, result-or-None) after every
        # commit and once at the terminal result.  Must not raise.
        self.on_stream = None
        self.key = jax.random.key(seed)
        # spec circuit breaker: closed (speculating) -> open (plain
        # blocks, counting down cooldown) -> half_open (one probe round)
        self.breaker = {"state": "closed", "cooldown": 0, "zero_rounds": 0,
                        "reason": None}
        # observability (DESIGN.md §13): every number the engine reports
        # goes through one registry + tracer bundle.  All timings are
        # host wall-clock taken at syncs the engine already performs
        # (admission TTFT fetch, the once-per-block token transfer) — the
        # obs layer never adds a device round trip.
        self.obs = obs if obs is not None else Obs()
        m = self.obs
        self._m_ttft = m.histogram(
            "serving_ttft_seconds", "admission -> first sampled token")
        self._m_prefill_s = m.counter(
            "serving_prefill_seconds_total", "wall-clock in admissions")
        self._m_decode_s = m.counter(
            "serving_decode_seconds_total",
            "wall-clock in decode blocks / spec rounds")
        self._m_prompt_toks = m.counter(
            "serving_prompt_tokens_total", "prompt tokens prefilled")
        self._m_gen_toks = m.counter(
            "serving_generated_tokens_total", "tokens in terminal streams")
        self._m_requests = m.counter(
            "serving_requests_total", "terminal results by status label")
        self._m_quarantined = m.counter(
            "serving_quarantined_total", "slots reset on non-finite state")
        self._m_breaker = m.counter(
            "serving_breaker_trips_total", "spec -> plain breaker trips")
        self._m_spec_rounds = m.counter(
            "serving_spec_rounds_total", "completed speculative rounds")
        self._m_spec_drafted = m.counter(
            "serving_spec_drafted_total", "draft tokens proposed")
        self._m_spec_accepted = m.counter(
            "serving_spec_accepted_total", "draft tokens accepted")
        self._m_spec_replays = m.counter(
            "serving_spec_replay_rounds_total", "rounds with a rollback")
        self._m_queue = m.gauge(
            "serving_queue_depth", "requests waiting for a slot")
        self._m_slots = m.gauge(
            "serving_slots_active", "slots currently decoding")
        self.stats = _StatsShim(self.obs)  # legacy dict view (DEPRECATED)
        # serving front-end (DESIGN.md §16): the admission scheduler owns
        # queue order + the slot target; the optional prefix/state cache
        # turns shared prompt prefixes into O(1) snapshot resumes.  Build
        # the cache with THIS engine's obs bundle so its hit/miss/bytes
        # counters land in the same registry snapshot.
        self.scheduler = Scheduler(self.sched_cfg, obs=self.obs,
                                   faults=faults)
        self.cache = cache
        if cache is not None and cache._own_obs:
            cache.bind_obs(self.obs)
        self._m_ttft_cold = m.histogram(
            "serving_ttft_cold_seconds", "TTFT of cache-miss admissions")
        self._m_ttft_hit = m.histogram(
            "serving_ttft_hit_seconds",
            "TTFT of admissions resumed from a cached prefix snapshot")

        pool = self.pool

        def _prefill(params, prompt, key, scfg):
            last_logits, states = lm.lm_prefill(params, prompt, cfg)
            tok = sample(last_logits, key, scfg)
            # admission health check: rides the sync that already fetches
            # the first sampled token (no extra round trip)
            finite = tree_finite(states) & jnp.all(
                jnp.isfinite(last_logits)
            )
            return tok, states, finite

        def _prefill_from(params, prompt, positions, states, key, scfg):
            # suffix prefill resumed from a cached prefix snapshot: exact
            # by the chunkwise carry identity (DESIGN.md §8/§16) — the
            # same ``lm_prefill(states=...)`` carry the spec verifier and
            # the incremental-prefill tests already rely on
            last_logits, states = lm.lm_prefill(
                params, prompt, cfg, states=states, positions=positions
            )
            tok = sample(last_logits, key, scfg)
            finite = tree_finite(states) & jnp.all(
                jnp.isfinite(last_logits)
            )
            return tok, states, finite

        def _carry_cold(params, prompt):
            # prompt[:aligned] -> the chunk-boundary state the cache keeps
            _, states = lm.lm_prefill(params, prompt, cfg)
            return states

        def _carry_from(params, prompt, positions, states):
            _, states = lm.lm_prefill(
                params, prompt, cfg, states=states, positions=positions
            )
            return states

        def _decode_block(params, states, tokens, positions, active, key,
                          sel, n_steps, scfgs):
            # scfgs: the (static) canonically-ordered DISTINCT sampling
            # configs; sel: traced (slots,) index into them.  Sampling once
            # per distinct config keeps homogeneous traffic at the old
            # single-sampler cost, and keying the jit on the distinct SET
            # (not the per-slot assignment) means slot churn never
            # recompiles — only genuinely new configs do.
            def body(carry, _):
                states, tok, pos, key = carry
                logits, states, _ = lm.lm_apply(
                    params, tok, cfg, states=states, positions=pos,
                    mode="decode",
                )
                key, *subs = jax.random.split(key, len(scfgs) + 1)
                cand = jnp.stack(
                    [sample(logits[:, -1], sk, c)
                     for c, sk in zip(scfgs, subs)]
                )  # (n_uniq, slots)
                nxt = jnp.take_along_axis(cand, sel[None, :], axis=0)[0]
                tok = jnp.where(active[:, None], nxt[:, None], tok)
                pos = pos + active[:, None].astype(pos.dtype)
                return (states, tok, pos, key), nxt

            (states, tok, pos, _), toks = jax.lax.scan(
                body, (states, tokens, positions, key), length=n_steps
            )
            if pool_shardings is not None:
                # pin the block's state output to the pool layout — the
                # scatter writes pin admissions, this pins the hot path,
                # so GSPMD never drifts the pool and re-lowers
                states = jax.tree.map(
                    jax.lax.with_sharding_constraint, states, pool_shardings
                )
            # fused per-slot finiteness reduction over the post-block
            # states: the quarantine flags ride the block's one host sync
            finite = pool.finite_mask(states)
            return states, tok, pos, toks, finite  # toks: (n_steps, slots)

        self._prefill = jax.jit(_prefill, static_argnames="scfg")
        self._prefill_from = jax.jit(_prefill_from, static_argnames="scfg")
        self._carry_cold = jax.jit(_carry_cold)
        self._carry_from = jax.jit(_carry_from)
        self._decode_block = jax.jit(
            _decode_block, static_argnames=("n_steps", "scfgs")
        )

        if spec is not None:
            self.drafter = build_drafter(
                spec, slots=slots, max_len=max_len, sampling=sampling,
                mesh=mesh, target_cfg=cfg,
            )
            if self.drafter.vocab is not None and \
                    self.drafter.vocab != cfg.vocab:
                raise ValueError(
                    f"drafter vocab {self.drafter.vocab} != target vocab "
                    f"{cfg.vocab}: draft ids would index the target "
                    "embedding out of range"
                )
            self._spec_step = jax.jit(make_spec_round(
                cfg, sampling, draft_probs=self.drafter.emits_probs,
                pool_shardings=pool_shardings,
            ))
        else:
            self.drafter = None

    def _mesh_ctx(self):
        """Activate the engine's mesh (mixer shard_map dispatch + logical
        sharding constraints resolve against the ambient mesh)."""
        return self.mesh if self.mesh is not None else (
            contextlib.nullcontext()
        )

    # -- fault injection ----------------------------------------------------

    def _bind_faults(self) -> Optional[FaultPlan]:
        """Fired injections self-document through the engine's tracer
        (the plan may be attached after construction, e.g. post-warmup).
        The scheduler (``sched.stall``) and prefix cache
        (``cache.corrupt``) share the engine's plan so one ``--inject``
        schedule covers the whole front-end."""
        if self.faults is not None and self.faults.obs is None:
            self.faults.obs = self.obs
        self.scheduler.faults = self.faults
        if self.cache is not None and self.cache.faults is None:
            self.cache.faults = self.faults
        return self.faults

    def _raise_fault(self, point: str) -> None:
        if self._bind_faults() is not None:
            self.faults.raise_if(point)

    def _inject_block_faults(self) -> None:
        """Hit the once-per-block injection points (no-ops without a plan)."""
        if self._bind_faults() is None:
            return
        slow = self.faults.hit("engine.slow_block")
        if slow is not None:
            time.sleep(slow.arg if slow.arg is not None else 0.05)
        nan = self.faults.hit("engine.nan_state")
        if nan is not None:
            slot = int(nan.arg) if nan.arg is not None else 0
            poison = jax.tree.map(
                lambda x: jnp.full_like(x, jnp.nan)
                if jnp.issubdtype(x.dtype, jnp.inexact) else x,
                self.pool.read_slot(slot),
            )
            self.pool.write_slot(slot, poison)

    # -- admission ----------------------------------------------------------

    def free_slots(self) -> List[int]:
        return [s for s in range(self.pool.slots) if not self.active[s]]

    def _validate(self, req: GenRequest) -> np.ndarray:
        """Admission control: reject malformed requests before they touch
        the pool.  Returns the prompt as an int32 array."""
        prompt = np.asarray(req.prompt)
        if prompt.ndim != 1 or prompt.size == 0:
            raise ValueError(
                f"request {req.rid}: prompt must be a non-empty 1-D token "
                f"array, got shape {prompt.shape}"
            )
        if not np.issubdtype(prompt.dtype, np.integer):
            raise ValueError(
                f"request {req.rid}: prompt dtype {prompt.dtype} is not "
                "integer token ids"
            )
        lo, hi = int(prompt.min()), int(prompt.max())
        if lo < 0 or hi >= self.cfg.vocab:
            raise ValueError(
                f"request {req.rid}: token ids [{lo}, {hi}] outside the "
                f"vocab [0, {self.cfg.vocab})"
            )
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")
        if len(prompt) + req.max_new > self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(prompt)}) + max_new "
                f"({req.max_new}) exceeds the engine's max_len "
                f"{self.max_len}"
            )
        return prompt.astype(np.int32)

    def admit(self, slot: int, req: GenRequest) -> int:
        """Prefill ``req`` into ``slot``; returns the first sampled token.
        The ``engine.admit`` span covers all of it, from validation to the
        slot going live (see ``_admit``)."""
        with self.obs.span("engine.admit", rid=req.rid, slot=slot):
            return self._admit(slot, req)

    def _admit(self, slot: int, req: GenRequest) -> int:
        """The admission behind ``admit``.

        Cold path: ONE chunk-parallel prefill call + one scatter write.
        With a prefix cache attached (DESIGN.md §16) admission becomes:
        longest-prefix lookup -> resume from the cached O(1) snapshot
        and prefill only the uncached suffix (exact by the chunkwise
        carry identity) -> snapshot the longest chunk-aligned prompt
        boundary for future requests.  Live slots are never read or
        written.  Raises on invalid requests and on prefill failure —
        everything that can raise happens BEFORE the slot is activated,
        so a failed admission leaves the engine untouched (``run()``
        converts the raise into a ``status="error"`` result).
        """
        if self.active[slot]:
            raise ValueError(f"slot {slot} is busy")
        prompt_np = self._validate(req)
        scfg = req.sampling if req.sampling is not None else self.sampling
        if self.spec is not None and scfg != self.sampling:
            raise ValueError(
                "speculative mode verifies against ONE sampling law; "
                "per-request overrides would need per-slot accept rules "
                f"(engine={self.sampling}, request={scfg})"
            )
        t0 = time.perf_counter()
        L = len(prompt_np)
        hit_len = 0
        insert_at = 0
        carry_state = None
        with self.obs.span("engine.prefill", rid=req.rid, slot=slot,
                           prompt_len=L):
            self._raise_fault("engine.prefill")
            self.key, sub = jax.random.split(self.key)
            prompt = jnp.asarray(prompt_np[None])
            done = 0  # tokens already summarized into carry_state
            if self.cache is not None:
                self._bind_faults()  # cache.corrupt may fire in lookup
                with self.obs.span("engine.cache_lookup") as lab:
                    found = self.cache.lookup(prompt_np, max_prefix=L - 1)
                    if found is not None:
                        hit_len, host_snap = found
                        done, carry_state = hit_len, host_snap
                    lab["hit_len"] = hit_len
                aligned = self.cache.aligned_len(L)
                if aligned > done:
                    # advance to the chunk-aligned boundary first so its
                    # state can be cached for future shared prefixes;
                    # still chunk-parallel (one extra kernel call, both
                    # calls together cover the prompt exactly once)
                    seg = prompt[:, done:aligned]
                    with self._mesh_ctx():
                        if done == 0:
                            carry_state = self._carry_cold(self.params, seg)
                        else:
                            carry_state = self._carry_from(
                                self.params, seg,
                                jnp.arange(done, aligned)[None],
                                carry_state,
                            )
                    done, insert_at = aligned, aligned
            with self._mesh_ctx():
                if done == 0:
                    first, state1, finite = self._prefill(
                        self.params, prompt, sub, scfg
                    )
                else:
                    first, state1, finite = self._prefill_from(
                        self.params, prompt[:, done:],
                        jnp.arange(done, L)[None], carry_state, sub, scfg,
                    )
                self.pool.write_slot(slot, state1)
            # one sync per admission (TTFT endpoint); the health flag —
            # and, on insertion admissions, the host copy of the
            # boundary snapshot — ride it, and the span closes right
            # after this existing sync
            fetch = (first[0], finite) if insert_at == 0 else (
                first[0], finite, carry_state)
            got = jax.device_get(fetch)  # sync-point: admission TTFT endpoint
            first_host, finite_host = got[0], got[1]
        if not bool(finite_host):
            self._m_quarantined.inc()
            self.pool.reset_slot(slot)
            raise RuntimeError(
                f"request {req.rid}: admission prefill produced a "
                "non-finite state — slot quarantined"
            )
        if insert_at:
            with self.obs.span("engine.cache_insert",
                               nbytes=tree_bytes(got[2])):
                # insert-on-prefill-complete, AFTER the health gate: a
                # poisoned boundary state must never become a cache entry
                if tree_finite_host(got[2]):
                    self.cache.insert(prompt_np[:insert_at], got[2])
        first_tok = int(first_host)
        ttft = time.perf_counter() - t0
        (self._m_ttft_hit if hit_len else self._m_ttft_cold).observe(ttft)
        self.tokens = self.tokens.at[slot, 0].set(first_tok)
        self.positions = self.positions.at[slot, 0].set(len(prompt_np))
        self.active[slot] = True
        self._slot_req[slot] = req
        self._slot_out[slot] = []
        self._slot_ttft[slot] = ttft
        self._slot_scfg[slot] = scfg
        t_start = self._enqueue_t.pop(req.rid, t0)
        self._slot_deadline[slot] = (
            t_start + req.deadline_s if req.deadline_s is not None
            else math.inf
        )
        self._m_prefill_s.inc(ttft)
        self._m_prompt_toks.inc(len(prompt_np))
        self._m_ttft.observe(ttft)
        self._m_slots.set(float(self.active.sum()))
        self.obs.event("request.admitted", rid=req.rid, slot=slot,
                       prompt_len=len(prompt_np), cached_prefix=hit_len)
        self.obs.event("request.first_token", rid=req.rid,
                       ttft_s=round(ttft, 6))
        # the admission token goes through the ONE commit path, so a
        # first-token EOS or max_new=1 finishes here instead of wasting a
        # full decode block on an already-complete request
        finished = self._commit(slot, [first_tok])
        if not finished and self.drafter is not None \
                and self.breaker["state"] == "closed":
            try:
                self.drafter.admit(
                    slot, [int(t) for t in prompt_np] + [first_tok]
                )
            except Exception as e:  # drafter failure never fails admission
                self._trip_breaker(f"drafter.admit failed: {e!r}")
        return first_tok

    def _commit(self, slot: int, toks) -> bool:
        """Append generated tokens to ``slot``'s stream with max_new/eos
        truncation; finish the slot when its stop condition hits.  The
        ONE place commit semantics live — plain blocks and speculative
        rounds must truncate identically or their streams diverge.
        Returns True when the slot finished (and was freed)."""
        req = self._slot_req[slot]
        out = self._slot_out[slot]
        n_before = len(out)
        for t in toks:
            if len(out) >= req.max_new or (
                req.eos_id is not None and out and out[-1] == req.eos_id
            ):
                break
            out.append(int(t))
        self._emit_stream(req.rid, out[n_before:], None)
        if len(out) >= req.max_new or (
            req.eos_id is not None and req.eos_id in out
        ):
            self._finish(slot)
            return True
        return False

    def _emit_stream(self, rid: int, toks: List[int],
                     result: Optional[GenResult]) -> None:
        """Feed the per-token streaming hook (serving/server.py).  A
        broken hook must not poison the drive loop: its error is logged
        as an event and streaming is disabled for the rest of the run."""
        if self.on_stream is None:
            return
        try:
            self.on_stream(rid, toks, result)
        except Exception as e:  # pragma: no cover - defensive
            self.obs.event("stream.hook_error", rid=rid, error=repr(e))
            self.on_stream = None

    def _finish(self, slot: int, status: str = "ok",
                error: Optional[str] = None) -> None:
        req = self._slot_req[slot]
        out = self._slot_out[slot][: req.max_new]
        if req.eos_id is not None and req.eos_id in out:
            out = out[: out.index(req.eos_id) + 1]
        self.results[req.rid] = GenResult(
            rid=req.rid, tokens=out, ttft_s=self._slot_ttft[slot],
            prompt_len=len(req.prompt), status=status, error=error,
        )
        self._m_requests.inc(status=status)
        self._m_gen_toks.inc(len(out))
        self.obs.event("request.done", rid=req.rid, status=status,
                       tokens=len(out),
                       ttft_s=round(self._slot_ttft[slot], 6))
        if req.rid in self._popped:
            self.scheduler.release(req)  # return the tenant's fair share
            self._popped.discard(req.rid)
        self._emit_stream(req.rid, [], self.results[req.rid])
        self.active[slot] = False
        self._m_slots.set(float(self.active.sum()))
        self._slot_req[slot] = None
        self._slot_deadline[slot] = math.inf
        # drop any per-request sampling override so the freed slot stops
        # contributing a stale config to the decode block's distinct set
        self._slot_scfg[slot] = self.sampling
        if self.drafter is not None:
            self.drafter.evict(slot)

    def _fail(self, req: GenRequest, status: str, error: str) -> None:
        """Record a terminal result for a request that never held a slot
        (failed admission / pre-admission expiry / queued cancellation)."""
        self._enqueue_t.pop(req.rid, None)
        self.results[req.rid] = GenResult(
            rid=req.rid, tokens=[], ttft_s=0.0,
            prompt_len=len(np.atleast_1d(np.asarray(req.prompt))),
            status=status, error=error,
        )
        self._m_requests.inc(status=status)
        self.obs.event("request.done", rid=req.rid, status=status,
                       tokens=0, ttft_s=0.0)
        if req.rid in self._popped:
            self.scheduler.release(req)
            self._popped.discard(req.rid)
        self._emit_stream(req.rid, [], self.results[req.rid])

    def _quarantine(self, slot: int) -> None:
        """A slot's state went non-finite: reset the state (O(state), one
        scatter — untouched neighbours keep decoding) and fail only that
        request.  The paper's constant-size state is what makes this the
        cheap path: recovery never reconstructs a KV arena."""
        self._m_quarantined.inc()
        self.pool.reset_slot(slot)
        self._finish(
            slot, status="error",
            error="non-finite decode state: slot quarantined and reset",
        )

    # -- lifecycle ----------------------------------------------------------

    def cancel(self, rid: int) -> bool:
        """Cancel a request: a live slot finishes immediately with
        ``status="cancelled"`` and its partial stream; a scheduler-queued
        rid is dropped from the queue and finalized at once; an unknown
        rid is marked and rejected at its admission attempt.  Returns
        False when the request already finished (nothing to cancel)."""
        for s in range(self.pool.slots):
            req = self._slot_req[s]
            if self.active[s] and req is not None and req.rid == rid:
                self._finish(s, status="cancelled",
                             error="cancelled while decoding")
                return True
        queued = self.scheduler.cancel(rid)
        if queued is not None:
            self._fail(queued, "cancelled", "cancelled while queued")
            return True
        if rid in self.results:
            return False
        self._cancelled.add(rid)
        return True

    def _expired(self, req: GenRequest) -> bool:
        if req.deadline_s is None:
            return False
        t0 = self._enqueue_t.get(req.rid)
        return t0 is not None and \
            time.perf_counter() - t0 > req.deadline_s

    def _sweep_deadlines(self) -> None:
        """Once-per-block deadline enforcement (host side, no sync)."""
        now = time.perf_counter()
        for s in range(self.pool.slots):
            if self.active[s] and now >= self._slot_deadline[s]:
                req = self._slot_req[s]
                self._finish(
                    s, status="timeout",
                    error=f"deadline_s={req.deadline_s} exceeded",
                )

    # -- circuit breaker (speculative -> plain fallback) --------------------

    def _trip_breaker(self, reason: str) -> None:
        cooldown = (self.spec.breaker_cooldown_blocks
                    if self.spec is not None else 0)
        self.breaker.update(state="open", cooldown=cooldown,
                            zero_rounds=0, reason=reason)
        self._m_breaker.inc()
        self.obs.event("breaker.tripped", reason=reason)

    def reset_breaker(self) -> None:
        """Re-close the breaker for a fresh traffic epoch.  Benchmarks and
        the serve CLI call this together with their post-warmup stats
        reset: a random-weights warmup can legitimately trip on
        zero-acceptance rounds, and the measured run should start from
        the closed state."""
        self.breaker.update(state="closed", cooldown=0, zero_rounds=0,
                            reason=None)

    def _breaker_gate(self) -> bool:
        """Advance the breaker state machine once per block; True when
        this block may run a speculative round."""
        b = self.breaker
        if b["state"] == "closed":
            return True
        if b["state"] == "open":
            if b["cooldown"] > 0:
                b["cooldown"] -= 1
                return False
            b["state"] = "half_open"
        return True  # half_open: probe this block

    def _resync_drafter(self) -> None:
        """Re-admit every live slot's committed context into the drafter
        (it went stale while the breaker was open)."""
        for s in range(self.pool.slots):
            if self.active[s]:
                req = self._slot_req[s]
                ctx = [int(t) for t in req.prompt] + self._slot_out[s]
                self.drafter.admit(s, ctx)

    def _try_spec_round(self) -> bool:
        """One breaker-supervised speculative round.  Returns True when
        the round ran (or nothing was active); False means the breaker
        tripped before any state mutation and the caller must fall back
        to a plain block for this step."""
        b = self.breaker
        if b["state"] == "half_open":
            try:
                self._resync_drafter()
            except Exception as e:
                self._trip_breaker(f"drafter resync failed: {e!r}")
                return False
        try:
            ran, accepted = self._spec_round()
        except Exception as e:
            # propose-phase failure: nothing was mutated yet, a plain
            # block this step keeps the stream exact
            self._trip_breaker(f"drafter crashed: {e!r}")
            return False
        if not ran:
            return True  # no active slots: nothing to decode either way
        if b["state"] == "half_open":
            if accepted > 0:
                b.update(state="closed", zero_rounds=0, reason=None)
            else:
                self._trip_breaker("half-open probe round accepted nothing")
        elif b["state"] == "closed":
            if accepted == 0:
                b["zero_rounds"] += 1
                if b["zero_rounds"] >= self.spec.breaker_zero_rounds:
                    self._trip_breaker(
                        f"{b['zero_rounds']} consecutive zero-acceptance "
                        "rounds"
                    )
            else:
                b["zero_rounds"] = 0
        return True

    # -- decode -------------------------------------------------------------

    def step_block(self, n_steps: Optional[int] = None) -> None:
        """Advance every active slot: ``n_steps`` plain decode tokens, or
        ONE draft->verify->accept round (up to ``spec.k + 1`` tokens) in
        speculative mode.  Either way: one host transfer.  With the
        circuit breaker open (or tripping on this very call) speculative
        engines degrade to plain blocks — greedy output is unchanged."""
        self._inject_block_faults()
        if self.spec is not None and self._breaker_gate():
            if self._try_spec_round():
                self._sweep_deadlines()
                return
        n_steps = self.block if n_steps is None else n_steps
        if n_steps <= 0:
            return
        self.key, sub = jax.random.split(self.key)
        active_dev = jnp.asarray(self.active)
        uniq = tuple(sorted(set(self._slot_scfg), key=repr))
        sel = jnp.asarray([uniq.index(c) for c in self._slot_scfg])
        t0 = time.perf_counter()
        with self.obs.span("engine.decode_block", steps=n_steps,
                           slots_active=int(self.active.sum())):
            with self._mesh_ctx():
                states, tok, pos, toks, finite = self._decode_block(
                    self.params, self.pool.states, self.tokens,
                    self.positions, active_dev, sub, sel, n_steps=n_steps,
                    scfgs=uniq,
                )
            self.pool.states = states
            self.tokens, self.positions = tok, pos
            # the block sync: tokens + quarantine flags in ONE transfer —
            # the span (and the timing below) closes on this existing
            # sync, never adding one
            toks_host, finite_host = jax.device_get(
                (toks, finite))  # sync-point: the once-per-block transfer
        toks_host = np.asarray(toks_host)
        self._m_decode_s.inc(time.perf_counter() - t0)
        with self.obs.span("engine.commit",
                           slots=int(self.active.sum())):
            for s in range(self.pool.slots):
                if not self.active[s]:
                    continue
                if not bool(finite_host[s]):
                    self._quarantine(s)
                    continue
                self._commit(s, toks_host[:, s])
            self._sweep_deadlines()

    # -- speculative decode -------------------------------------------------

    def _spec_round(self) -> Tuple[bool, int]:
        """draft -> verify -> accept for every active slot.

        The drafter proposes k tokens per slot (batched across slots);
        then ONE jitted call (``spec.verify.make_spec_round``) scores the
        k+1-wide block chunk-parallel for all slots, computes per-slot
        acceptance, rolls rejected continuations back to the pre-verify
        state advanced by only their accepted prefix (a ``lax.cond`` arm
        that executes exclusively on rejection rounds — full-acceptance
        rounds keep the verify pass's own final states for free), and
        advances tokens/positions on device.  One host transfer per round
        (the packed accept/commit array + quarantine flags), like the
        plain block path.

        Returns ``(ran, accepted)``: whether any slot was active, and the
        total number of accepted draft tokens (the breaker's health
        signal).  Drafter exceptions in the propose phase propagate (the
        caller trips the breaker — nothing was mutated); commit-phase
        drafter exceptions trip the breaker here but never lose verified
        tokens.
        """
        k = self.spec.k
        slots_active = [s for s in range(self.pool.slots) if self.active[s]]
        if not slots_active:
            return False, 0
        t0 = time.perf_counter()
        # manual span: a propose-phase crash propagates to the breaker
        # before the round completes, so only completed rounds record
        timer = self.obs.timer("engine.spec_round", k=k,
                               slots_active=len(slots_active))
        self._raise_fault("drafter.propose")
        drafts, qp = self.drafter.propose(slots_active, k)
        if self.drafter.full_width:
            # device drafter, rows for every slot: feed straight through
            draft_full, q_full = drafts.astype(jnp.int32), qp
        elif isinstance(drafts, np.ndarray):  # host drafter: host scatter
            draft_full = np.zeros((self.pool.slots, k), np.int32)
            draft_full[slots_active] = drafts
            draft_full, q_full = jnp.asarray(draft_full), None
            if qp is not None:
                vocab = self.cfg.vocab
                q_np = np.full((self.pool.slots, k, vocab), 1.0 / vocab,
                               np.float32)
                q_np[slots_active] = np.asarray(qp, np.float32)
                q_full = jnp.asarray(q_np)
        else:  # device drafter with active-row output: device scatter
            ids = jnp.asarray(np.asarray(slots_active, np.int32))
            draft_full = jnp.zeros((self.pool.slots, k), jnp.int32)
            draft_full = draft_full.at[ids].set(drafts.astype(jnp.int32))
            q_full = None
            if qp is not None:
                vocab = self.cfg.vocab
                q_full = jnp.full(
                    (self.pool.slots, k, vocab), 1.0 / vocab, jnp.float32
                ).at[ids].set(jnp.asarray(qp, jnp.float32))
        self.key, sub = jax.random.split(self.key)
        args = (self.params, self.pool.states, self.tokens, self.positions,
                jnp.asarray(self.active), draft_full, sub)
        if self.drafter.emits_probs:
            args = args + (q_full,)
        with self._mesh_ctx():
            packed, finite, new_states, new_tokens, new_positions = \
                self._spec_step(*args)
        self.pool.states = new_states
        self.tokens, self.positions = new_tokens, new_positions
        # ONE host transfer per round: commits + quarantine flags together
        packed_h, finite_h = jax.device_get(
            (packed, finite))  # sync-point: one transfer per spec round
        packed_h = np.asarray(packed_h)
        self._m_spec_rounds.inc()
        healthy = [s for s in slots_active if bool(finite_h[s])]
        if any(int(packed_h[s, 0]) < k for s in healthy):
            self._m_spec_replays.inc()  # the rollback arm ran
        accepted_total = 0
        for s in slots_active:
            if not bool(finite_h[s]):
                self._quarantine(s)
                continue
            m = int(packed_h[s, 0])
            committed = [int(t) for t in packed_h[s, 1:m + 2]]
            self._m_spec_drafted.inc(k)
            self._m_spec_accepted.inc(m)
            accepted_total += m
            if self._commit(s, committed):
                continue  # finished: state is stale but the slot is free
            if self.breaker["state"] != "closed":
                continue  # drafter already failed: skip its bookkeeping
            try:
                self.drafter.commit(s, committed)
            except Exception as e:
                self._trip_breaker(f"drafter.commit failed: {e!r}")
        timer.close(accepted=accepted_total)
        self._m_decode_s.inc(time.perf_counter() - t0)
        return True, accepted_total

    # -- driver -------------------------------------------------------------

    def submit(self, req: GenRequest) -> None:
        """Queue one request with the admission scheduler.  Safe to call
        between drive ticks (the async server submits as traffic
        arrives); order of service is the scheduler's policy — priority
        class, deadline slack, tenant fair share — not call order."""
        now = time.perf_counter()
        self._enqueue_t.setdefault(req.rid, now)
        self.scheduler.submit(req, now=now)
        self.obs.event("request.queued", rid=req.rid,
                       priority=req.priority, tenant=req.tenant)

    def _drive_tick(self) -> None:
        """One drive-loop iteration: expire queued deadlines, honor a
        ``sched.stall``, autoscale the usable slot count, admit scheduler
        winners into free slots, advance one decode block.  Never raises
        — every failure becomes a per-request status (the ``run()``
        while-loop's no-raise contract, CI-enforced, lives here).  The
        ``engine.tick`` span covers it, labelled with the admissions it
        made."""
        with self.obs.span("engine.tick") as lab:
            lab["admitted"] = self._tick()

    def _tick(self) -> int:
        """The body of ``_drive_tick``; returns the number admitted."""
        n_admitted = 0
        self._bind_faults()
        # queued-deadline expiry FIRST: an expired request must never
        # consume a prefill, and learns its fate THIS tick even when no
        # slot is free (starvation regression test)
        for req in self.scheduler.expire():
            self._fail(
                req, "timeout",
                f"deadline_s={req.deadline_s} expired before admission",
            )
        self._m_queue.set(float(len(self.scheduler)))
        if not self.scheduler.stalled():
            target = self.scheduler.target_slots()
            for s in self.free_slots():
                if int(self.active.sum()) >= target:
                    break
                admitted = False
                while len(self.scheduler) and not admitted:
                    req = self.scheduler.pop()
                    if req is None:
                        break
                    self._popped.add(req.rid)
                    if req.rid in self._cancelled:
                        self._cancelled.discard(req.rid)
                        self._fail(req, "cancelled",
                                   "cancelled before admission")
                        continue
                    if self._expired(req):
                        self._fail(
                            req, "timeout",
                            f"deadline_s={req.deadline_s} expired before "
                            "admission",
                        )
                        continue
                    try:
                        self.admit(s, req)
                        admitted = True
                        n_admitted += 1
                    except Exception as e:
                        self._fail(req, "error", f"admission failed: {e}")
        if self.active.any():
            try:
                self.step_block()
            except Exception as e:
                # a failed block leaves every live slot's device state
                # suspect: fail them all (keeping partial streams) and
                # let the queue drain through fresh admissions
                for s in range(self.pool.slots):
                    if self.active[s]:
                        self._finish(
                            s, status="error",
                            error=f"decode block failed: {e!r}",
                        )
        return n_admitted

    def run(self, requests: List[GenRequest]) -> List[GenResult]:
        """Serve ``requests`` to completion with continuous batching.

        Every request gets a terminal ``GenResult`` — per-request
        failures (invalid admission, poisoned state, expired deadline,
        cancellation, even a decode-block crash) become non-``ok``
        statuses on their own results while unaffected slots keep
        decoding; the drive loop itself never raises (CI-enforced).
        Admission order is the scheduler's: equal-priority single-tenant
        no-deadline traffic drains in arrival order (the old FIFO), and
        priorities/deadlines/tenants reorder beyond that."""
        rids = [r.rid for r in requests]
        if len(set(rids)) != len(rids):
            raise ValueError("request rids must be unique")
        for r in requests:
            self.submit(r)
        while len(self.scheduler) or self.active.any():
            self._drive_tick()
        self._m_queue.set(0.0)
        return [self.results[r.rid] for r in requests]

"""Content-addressed prefix/state cache — the HLA serving advantage.

Softmax prefix caching is a memory-management problem: a cached prompt
is a paged KV arena that grows with its length, so production servers
build radix trees over block tables.  For the paper's streaming ops the
entire prefix is summarized by a **constant-size sufficient statistic**
(PAPER §2–3): a cached prefix is ONE O(1) state snapshot — a few small
tensors per layer, independent of prefix length — so the cache is a
dict of host arrays with a byte budget, not an allocator.

Mechanics (DESIGN.md §16):

* **Keying** — a polynomial rolling hash over the prompt token ids,
  materialized at **chunk-granularity** prefix lengths (``granularity``
  tokens, default the op's chunk width).  The key is pure token
  content + the cache's ``namespace`` (model/params fingerprint), so
  two tenants sharing a system prompt share the entry.  Hash collisions
  cannot produce wrong tokens: every probe verifies the stored token
  ids before hitting.
* **Lookup** — longest-prefix: probe chunk-aligned prefix lengths from
  the longest candidate down; the first verified entry wins.  Exactness
  of resuming from the snapshot is the chunkwise carry identity the
  prefill kernels already guarantee (``lm_prefill(states=...)``,
  DESIGN.md §8) — tested token-for-token against cold decode.
* **Insertion** — on prefill completion the engine snapshots the state
  at the longest chunk-aligned prompt boundary and inserts it here.
  Snapshots are HOST trees (``StatePool.snapshot_slot(host=True)``
  semantics): hundreds of cached prefixes consume RAM, never HBM.  The
  program that computes the boundary state also computes its
  finiteness flag and ``tree_digest``; both ride the admission's one
  ``device_get`` with the host copy, so the insert gate (a non-finite
  state never becomes an entry) and the entry's digest cost no host
  pass over the snapshot's bytes.
* **Eviction** — LRU under an explicit byte budget.  Per-entry bytes
  are measured from the actual leaves and cross-checked against the
  analytic ``repro.obs.costs`` state-bytes model
  (``state_bytes_for(cfg)``), which is also how a budget is sized
  ("N cached prefixes" -> bytes).
* **Integrity** — every entry carries ``tree_digest`` of its state: two
  32-bit lanes, each a sum mod 2^32 over every 32-bit word of the leaves
  of a bijective hash of the word keyed by its position and leaf index,
  so any change to one word (a single flipped bit included) changes both
  lanes, and changes to several words cancel with probability about
  2^-32 per lane.  The engine verifies a hit on the device: the first
  program that consumes the uploaded snapshot also returns the digest of
  its input, so the check covers the host copy and the host-to-device
  upload; after the admission's sync ``confirm`` compares it with the
  stored digest.  On a mismatch (``cache.corrupt`` fault point, or a real
  bit flip) the entry is dropped, nothing of the attempt is kept, and
  the admission runs again, falling through to shorter prefixes / cold
  prefill.  A cache used without an engine confirms a hit with
  ``tree_digest`` of the host state.  A corrupt cache can cost latency,
  never correctness.
"""

from __future__ import annotations

import collections
import dataclasses
import zlib
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import Obs

# polynomial rolling hash over token ids: h_{i+1} = h_i * _BASE + tok
# mod 2^61-1.  Deterministic across processes (unlike hash()), cheap to
# extend one token at a time, and collision-checked by token comparison.
_MOD = (1 << 61) - 1
_BASE = 1_000_003


def rolling_hashes(tokens: np.ndarray, lengths: List[int]) -> List[int]:
    """Hashes of ``tokens[:n]`` for each n in ``lengths`` (ascending),
    in one O(len) pass."""
    out, h, done = [], 0, 0
    for n in lengths:
        for t in tokens[done:n]:
            h = (h * _BASE + int(t) + 1) % _MOD
        done = n
        out.append(h)
    return out


def tree_bytes(tree) -> int:
    """Total bytes of a host state snapshot's leaves."""
    return int(sum(  # sync-point: host snapshot leaves, nbytes never syncs
        np.asarray(leaf).nbytes for leaf in jax.tree.leaves(tree)))


def _mix32(x):
    """A 32-bit integer hash (lowbias32) of uint32 ``x``, elementwise; a
    bijection of the 32-bit words."""
    x = x ^ (x >> 16)
    x = x * np.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * np.uint32(0x846CA68B)
    return x ^ (x >> 16)


def _words(leaf) -> jax.Array:
    """A leaf's bits as uint32 words of the leaf's own shape: 4-byte items
    bitcast, 2- and 1-byte items bitcast to their unsigned type and
    widened."""
    unsigned = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[leaf.dtype.itemsize]
    return jax.lax.bitcast_convert_type(leaf, unsigned).astype(jnp.uint32)


def _positions(shape) -> jax.Array:
    """Each item's row-major index mod 2^32, built from iotas over
    ``shape`` itself: no reshape, so a sharded leaf stays sharded."""
    pos = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in reversed(range(len(shape))):
        pos = pos + jax.lax.broadcasted_iota(jnp.uint32, shape, d) \
            * np.uint32(stride % (1 << 32))
        stride *= shape[d]
    return pos


@jax.jit
def _tree_digest(tree) -> jax.Array:
    lanes = jnp.zeros((2,), jnp.uint32)
    for i, leaf in enumerate(jax.tree.leaves(tree)):
        w = _words(leaf)
        h = _mix32(_positions(w.shape) ^ _mix32(jnp.uint32(2 * i + 1)))
        h2 = _mix32(h + np.uint32(0x9E3779B9))
        lanes = lanes + jnp.stack([
            jnp.sum(_mix32(w ^ h), dtype=jnp.uint32),
            jnp.sum(_mix32(w + h2), dtype=jnp.uint32),
        ])
    return lanes


def tree_digest(tree) -> jax.Array:
    """uint32[2] integrity digest of a state tree, in one fused read.

    Each lane is the sum mod 2^32, over every word of every leaf, of a
    32-bit hash of the word keyed by a hash of its position and its
    leaf's index (a different key per lane).  The hash is a bijection of
    the word, so any change to one word, a single flipped bit included,
    changes each lane; changes to several words cancel in a lane with
    probability about 2^-32.  Jittable: the engine's admission programs
    call it on device states, and on a mesh the reduction runs on each
    shard.  Called on a host (numpy) tree it uploads the tree and runs
    the same program.  Leaves of 1-, 2- and 4-byte dtypes only: a
    program without x64 would narrow an 8-byte host leaf before reading
    it, so those and bool are refused."""
    for leaf in jax.tree.leaves(tree):
        dt = np.dtype(leaf.dtype)
        if dt == np.bool_ or dt.itemsize not in (1, 2, 4):
            raise TypeError(f"tree_digest reads 1-, 2- and 4-byte leaves, "
                            f"not {dt}")
    return _tree_digest(tree)


def state_bytes_for(cfg, *, max_len: int = 64) -> int:
    """Per-entry byte estimate from the analytic cost model: the whole
    LM's decode-state bytes for one sequence (``repro.obs.costs``).
    Sizing a budget as ``n_entries * state_bytes_for(cfg)`` caches
    about n_entries prefixes regardless of their token lengths — the
    O(1)-state property that makes this cache a dict, not an arena."""
    from ..obs.costs import model_cost

    return int(model_cost(cfg, mode="decode_step", seq_len=max_len)
               .state_bytes)


@dataclasses.dataclass
class CacheEntry:
    key: Tuple[int, int]          # (prefix_len, rolling hash)
    tokens: np.ndarray            # the exact prefix ids (collision guard)
    state: Any                    # host state pytree (numpy leaves)
    nbytes: int
    digest: np.ndarray            # tree_digest(state) at insertion
    hits: int = 0

    @property
    def prefix_len(self) -> int:
        return self.key[0]


class PrefixCache:
    """Longest-prefix -> state-snapshot cache with LRU byte budgeting.

    ``granularity`` is the chunk width prefixes are keyed at; the engine
    passes its op's chunk so cache boundaries coincide with the chunkwise
    kernels' natural resume points.  ``budget_bytes`` bounds HOST memory
    (entries are numpy trees); inserting past it evicts least-recently-
    used entries first.  ``namespace`` scopes keys to one model+params
    identity — always set it when one process serves several models.

    Thread-compat: all mutation happens on the engine drive loop; the
    async server shares that loop, so no lock is needed (same contract
    as ``Engine`` itself).
    """

    def __init__(self, *, granularity: int = 256,
                 budget_bytes: int = 1 << 30, namespace: str = "",
                 obs: Optional[Obs] = None, faults=None):
        if granularity < 1:
            raise ValueError(f"granularity must be >= 1: {granularity}")
        if budget_bytes < 0:
            raise ValueError(f"budget_bytes must be >= 0: {budget_bytes}")
        self.granularity = granularity
        self.budget_bytes = budget_bytes
        self.namespace = namespace
        self.faults = faults
        # key -> entry, ordered oldest-used first (OrderedDict LRU)
        self._entries: "collections.OrderedDict[Tuple[int, int], CacheEntry]" \
            = collections.OrderedDict()
        self._lengths: collections.Counter = collections.Counter()
        self.bytes = 0
        self._own_obs = obs is None
        self._declare_metrics(obs if obs is not None else Obs())

    def bind_obs(self, obs: Obs) -> None:
        """Re-home the cache's metric series into ``obs``.  The engine
        calls this for caches built without an explicit bundle, so one
        ``--metrics-out`` snapshot carries engine + scheduler + cache
        counters together."""
        self._own_obs = False
        self._declare_metrics(obs)

    def _declare_metrics(self, obs: Obs) -> None:
        self.obs = obs
        m = obs
        self._m_hits = m.counter(
            "cache_hits_total", "lookups that resumed from a snapshot")
        self._m_misses = m.counter(
            "cache_misses_total", "lookups with no usable prefix")
        self._m_inserts = m.counter(
            "cache_insertions_total", "entries inserted")
        self._m_evicted = m.counter(
            "cache_evicted_bytes_total", "bytes LRU-evicted over budget")
        self._m_corrupt = m.counter(
            "cache_corrupt_dropped_total",
            "entries dropped on digest mismatch")
        self._m_entries = m.gauge("cache_entries", "live entries")
        self._m_bytes = m.gauge("cache_bytes", "live host bytes")
        self._m_hit_toks = m.histogram(
            "cache_hit_prefix_tokens", "prefix tokens served from cache",
            buckets=(16, 64, 256, 1024, 4096, 16384))

    def __len__(self) -> int:
        return len(self._entries)

    def clear(self) -> None:
        """Drop every entry (warmup epochs, tests).  Counters are
        cumulative and unaffected; the entry/byte gauges go to zero."""
        self._entries.clear()
        self._lengths.clear()
        self.bytes = 0
        self._m_entries.set(0.0)
        self._m_bytes.set(0.0)

    # -- keying -------------------------------------------------------------

    def _ns_seed(self) -> int:
        return zlib.crc32(self.namespace.encode()) % _MOD

    def _candidate_lengths(self, n_tokens: int,
                           max_prefix: Optional[int]) -> List[int]:
        """Chunk-aligned prefix lengths to probe, ascending.  Only
        lengths that exist in the cache are worth hashing."""
        cap = n_tokens if max_prefix is None else min(n_tokens, max_prefix)
        return [n for n in sorted(self._lengths)
                if n <= cap and self._lengths[n] > 0]

    def aligned_len(self, n_tokens: int) -> int:
        """Longest chunk-aligned prefix length strictly usable for a
        prompt of ``n_tokens`` (at least one token must remain to
        produce the first sampled logits)."""
        return ((n_tokens - 1) // self.granularity) * self.granularity

    # -- lookup / insert ----------------------------------------------------

    def _drop(self, entry: CacheEntry) -> None:
        self._entries.pop(entry.key, None)
        self._lengths[entry.key[0]] -= 1
        self.bytes -= entry.nbytes
        self._m_entries.set(float(len(self._entries)))
        self._m_bytes.set(float(self.bytes))

    def _corrupt_if_injected(self, entry: CacheEntry) -> None:
        """The ``cache.corrupt`` fault point: flip bytes in one leaf of
        the entry the lookup is about to return."""
        if self.faults is None:
            return
        if self.faults.hit("cache.corrupt") is None:
            return
        # snapshot leaves may be read-only (jax.device_get): corrupt a
        # writable copy and splice it back into the entry's tree
        flat, treedef = jax.tree.flatten(entry.state)
        leaf = np.array(flat[0])
        buf = leaf.view(np.uint8).reshape(-1)
        buf[: max(1, buf.size // 16)] ^= 0xFF
        flat[0] = leaf
        entry.state = jax.tree.unflatten(treedef, flat)

    def lookup(self, tokens, *, max_prefix: Optional[int] = None
               ) -> Optional[CacheEntry]:
        """Longest cached prefix of ``tokens`` whose stored ids match.

        Returns the entry (``prefix_len``, host ``state``, stored
        ``digest``) or None.  ``max_prefix`` caps the usable length (the
        engine passes ``len(prompt) - 1`` so at least one suffix token
        remains to sample from).  Hash-colliding entries are skipped.
        The state's bytes are not read here: the caller digests the
        state as it uses it (the engine on the device, a host caller with
        ``tree_digest``) and settles the hit with ``confirm``; after a
        failed confirm, looking up again falls through to the
        next-shorter entry.
        """
        toks = np.asarray(tokens).reshape(-1)
        lengths = self._candidate_lengths(len(toks), max_prefix)
        if not lengths:
            self._m_misses.inc()
            return None
        hashes = rolling_hashes(toks, lengths)
        seed = self._ns_seed()
        for n, h in zip(reversed(lengths), reversed(hashes)):
            entry = self._entries.get((n, (h + seed) % _MOD))
            if entry is None:
                continue
            if not np.array_equal(entry.tokens, toks[:n]):
                continue  # hash collision: content mismatch, keep probing
            self._corrupt_if_injected(entry)
            self._entries.move_to_end(entry.key)  # LRU touch
            return entry
        self._m_misses.inc()
        return None

    def confirm(self, entry: CacheEntry, digest) -> bool:
        """Settle a hit: ``digest`` is ``tree_digest`` of the entry's
        state as it was read.  A match counts the hit; a mismatch drops
        the entry and counts it corrupt.  Returns whether they matched."""
        if not np.array_equal(np.asarray(digest), entry.digest):
            if self._entries.get(entry.key) is entry:
                self._drop(entry)
            self._m_corrupt.inc()
            self.obs.event("cache.corrupt_dropped",
                           prefix_len=entry.prefix_len)
            return False
        entry.hits += 1
        self._m_hits.inc()
        self._m_hit_toks.observe(float(entry.prefix_len))
        self.obs.event("cache.hit", prefix_len=entry.prefix_len,
                       hits=entry.hits)
        return True

    def insert(self, tokens, state, digest=None, *,
               nbytes: Optional[int] = None) -> bool:
        """Insert a host state snapshot for the chunk-aligned prefix
        ``tokens`` (insert-on-prefill-complete).  ``digest`` is
        ``tree_digest(state)``, which the engine computes on the device
        with the state; left out, it is computed here.  ``nbytes`` is
        ``tree_bytes(state)`` where the caller already has it.  Refreshes
        LRU on re-insertion of a live key.  Returns False when the entry
        was rejected (misaligned length or larger than the whole
        budget)."""
        toks = np.asarray(tokens).reshape(-1).astype(np.int64)
        n = len(toks)
        if n == 0 or n % self.granularity != 0:
            return False
        if nbytes is None:
            nbytes = tree_bytes(state)
        if nbytes > self.budget_bytes:
            return False
        h = (rolling_hashes(toks, [n])[0] + self._ns_seed()) % _MOD
        key = (n, h)
        old = self._entries.get(key)
        if old is not None and np.array_equal(old.tokens, toks):
            self._entries.move_to_end(key)
            return True  # already cached: refresh recency, keep the entry
        if old is not None:
            self._drop(old)  # same key, different tokens: collision — replace
        if digest is None:
            digest = tree_digest(state)
        entry = CacheEntry(key=key, tokens=toks, state=state, nbytes=nbytes,
                           digest=np.asarray(digest))  # sync-point: 8 bytes
        self._entries[key] = entry
        self._lengths[n] += 1
        self.bytes += nbytes
        self._m_inserts.inc()
        while self.bytes > self.budget_bytes and len(self._entries) > 1:
            _, lru = next(iter(self._entries.items()))
            if lru is entry:
                break
            self._drop(lru)
            self._m_evicted.inc(lru.nbytes)
            self.obs.event("cache.evicted", prefix_len=lru.key[0],
                           nbytes=lru.nbytes)
        self._m_entries.set(float(len(self._entries)))
        self._m_bytes.set(float(self.bytes))
        return True

    def stats(self) -> Dict[str, float]:
        hits = self._m_hits.total()
        misses = self._m_misses.total()
        return {
            "entries": float(len(self._entries)),
            "bytes": float(self.bytes),
            "hits": hits,
            "misses": misses,
            "hit_rate": hits / max(hits + misses, 1.0),
            "evicted_bytes": self._m_evicted.total(),
        }

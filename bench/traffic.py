"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and makes its inputs from the seed.

Every seed gets the same work and its own token contents.  A closed or
train mix gets the same multiset of sizes in another order: seeds then
differ in which request meets which, not in how much work they bring.  A
sessions mix gets the same plan for every seed (arrivals, documents, user
message lengths and think times): its open-loop tail follows how bursts
of arrivals meet long turns, so a plan in another order is other work.

Kinds of mix:

* ``train``: ``batch`` x ``seq_len`` random tokens per step.
* ``sessions``: open-loop multi-turn sessions.  Sessions arrive by a
  Poisson process of ``rate_per_s``; each opens on one of ``docs``
  documents of ``doc_len`` tokens (Zipf ``zipf_s`` popularity) and runs
  ``turns`` turns.  A turn's prompt is the previous prompt, the previous
  answer and a user message of a length from ``user_len`` ([lo, hi,
  step]); answers are ``answer_len`` greedy tokens; a turn is due an
  exponential think time (mean ``think_mean_s``) after the previous
  answer ended.
* ``closed``: ``clients`` clients, each sending its next request when the
  previous one has finished.  Unshared prompts of ``prompt_len`` and
  outputs of ``out_len`` ([lo, hi, step]).  Each client's first request
  stops at a point spread evenly over the output lengths, so finishes
  are spread from the start.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) % (1 << 63), stream])


def grid(lo_hi_step) -> List[int]:
    lo, hi, step = lo_hi_step
    return list(range(lo, hi + 1, step))


def spread(values: List[int], n: int, rng) -> np.ndarray:
    """``n`` draws that cycle through ``values`` evenly, in seeded order."""
    out = np.array([values[i % len(values)] for i in range(n)])
    rng.shuffle(out)
    return out


def exp_quantiles(n: int, mean: float, rng) -> np.ndarray:
    """``n`` exponential draws of ``mean`` as the same set of quantiles for
    every seed, in seeded order."""
    q = -mean * np.log(1.0 - (np.arange(n) + 0.5) / n)
    rng.shuffle(q)
    return q


def zipf_counts(n: int, k: int, s: float) -> List[int]:
    """Integer counts over ``k`` items summing to ``n`` in Zipf(s)
    proportions (largest remainder)."""
    w = np.array([1.0 / (i + 1) ** s for i in range(k)])
    raw = n * w / w.sum()
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts.tolist()


def train_batches(t: dict, seed: int, vocab: int, n: int) -> np.ndarray:
    """(n, batch, seq_len + 1) int32 token rows, all rows distinct."""
    rng = rng_for(seed, 1)
    return rng.integers(0, vocab, (n, t["batch"], t["seq_len"] + 1),
                        dtype=np.int32)


@dataclasses.dataclass
class Session:
    sid: int
    arrival: float  # seconds after the generator's start
    doc: int
    user: List[np.ndarray]  # one user message per turn
    think: List[float]  # think time before turns 2..


def sessions(t: dict, seed: int, vocab: int, horizon_s: float):
    """(documents, sessions): about ``rate_per_s * horizon_s`` sessions,
    the last arriving near ``horizon_s``."""
    rng = rng_for(seed, 2)
    plan = rng_for(0, 2)  # the same plan for every seed
    docs = [rng.integers(0, vocab, t["doc_len"], dtype=np.int32)
            for _ in range(t["docs"])]
    n = int(math.ceil(t["rate_per_s"] * horizon_s)) + 1
    gaps = exp_quantiles(n, 1.0 / t["rate_per_s"], plan)
    arrivals = np.cumsum(gaps) - gaps[0]
    doc_of = np.repeat(np.arange(t["docs"]), zipf_counts(n, t["docs"],
                                                         t["zipf_s"]))
    plan.shuffle(doc_of)
    turns = t["turns"]
    ulen = spread(grid(t["user_len"]), n * turns, plan).reshape(n, turns)
    think = exp_quantiles(n * (turns - 1), t["think_mean_s"], plan).reshape(
        n, turns - 1)
    out = []
    for i in range(n):
        user = [rng.integers(0, vocab, int(m), dtype=np.int32)
                for m in ulen[i]]
        out.append(Session(i, float(arrivals[i]), int(doc_of[i]), user,
                           [float(x) for x in think[i]]))
    return docs, out


def closed_requests(t: dict, seed: int, vocab: int, per_client: int):
    """Per client, a list of (prompt, max_new) pairs."""
    rng = rng_for(seed, 3)
    c = t["clients"]
    plen = spread(grid(t["prompt_len"]), c * per_client, rng)
    olen = spread(grid(t["out_len"]), c * per_client, rng)
    lo, hi, step = t["out_len"]
    first = np.linspace(step, hi, c).astype(int) // step * step
    rng.shuffle(first)
    out = []
    for i in range(c):
        reqs = []
        for j in range(per_client):
            k = i * per_client + j
            prompt = rng.integers(0, vocab, int(plen[k]), dtype=np.int32)
            reqs.append((prompt, int(first[i] if j == 0 else olen[k])))
        out.append(reqs)
    return out

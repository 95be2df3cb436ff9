"""Operations and bytes of the hybrid HLA2 + softmax model's serving work,
computed from its shapes (the configuration's own keys, as run).

These counts are the yardstick of ``mfu_hybrid``: they depend on the
configuration and on the shapes of the work, never on how the program
implements it.

* A prefill of ``n`` tokens: every layer's projections and SwiGLU over the
  ``n`` tokens and the output head at the last position only (two
  operations per multiply-add); per linear-attention layer and head,
  ``n / w`` chunks of the HLA2 chunk form at key width ``dk`` and value
  width ``dv`` (``costs.hla2_chunk_flops``); per full-attention layer, the
  causal scores and their weighted sum, ``2 x 2 x H x dh x n (n + 1) / 2``.
  Its bytes: the weights once, the prompt's keys and values written once,
  the HLA2 state written once.
* A decode step over ``a`` active sequences: the weights once; per
  sequence the HLA2 state read and written and every cached key and value
  read once.  Each sequence's cache is counted at ``kv_floor`` positions,
  the shortest prompt the traffic sends: a lower bound on what any
  implementation must read, so the least time stays below the real one.
"""

from __future__ import annotations

from bench import costs
from bench.models.hla2_hybrid_lm_ref import LINEAR, dims


def _counts(c: dict):
    m = dims(c)
    return m, m["types"].count(LINEAR), len(m["types"]) - m["types"].count(
        LINEAR)


def layer_params(c: dict) -> int:
    """Parameters of the layers that take part in a matrix product per
    token (projections and SwiGLU)."""
    m, nl, nf = _counts(c)
    d, ff = m["d"], m["ff"]
    lin = d * m["Hl"] * (2 * m["dk"] + m["dv"]) + m["Hl"] * m["dv"] * d
    full = d * m["dh"] * (m["H"] + 2 * m["Hk"]) + m["H"] * m["dh"] * d
    return nl * (lin + 3 * d * ff) + nf * (full + 3 * d * ff)


def head_params(c: dict) -> int:
    m = dims(c)
    return m["d"] * m["V"]


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes of the weights a model step reads (bfloat16)."""
    return float((layer_params(c) + head_params(c)) * itemsize)


def hla2_state_bytes(c: dict) -> int:
    """HLA2 decode state of one sequence: float32 S (dk x dk), C and G
    (dk x dv), m and h (dk) per head and linear layer."""
    m, nl, _ = _counts(c)
    dk, dv = m["dk"], m["dv"]
    return nl * m["Hl"] * (dk * dk + 2 * dk * dv + 2 * dk) * 4


def kv_bytes_per_token(c: dict, itemsize: int = 2) -> int:
    """Keys and values of one position over the full-attention layers."""
    m, _, nf = _counts(c)
    return nf * 2 * m["Hk"] * m["dh"] * itemsize


def prefill_flops(c: dict, n: int) -> float:
    m, nl, nf = _counts(c)
    w = c["chunk"]
    mix = nl * m["Hl"] * (n / w) * costs.hla2_chunk_flops(w, m["dk"], m["dv"])
    attn = nf * 2.0 * 2.0 * m["H"] * m["dh"] * n * (n + 1) / 2.0
    return 2.0 * layer_params(c) * n + 2.0 * head_params(c) + mix + attn


def prefill_bytes(c: dict, n: int) -> float:
    return weight_bytes(c) + n * kv_bytes_per_token(c) + hla2_state_bytes(c)


def decode_flops(c: dict, active: int, ctx: int) -> float:
    m, nl, nf = _counts(c)
    per_seq = (2.0 * (layer_params(c) + head_params(c))
               + nl * costs.decode_step_flops(m["Hl"], m["dk"], m["dv"])
               + nf * 4.0 * m["H"] * m["dh"] * ctx)
    return active * per_seq


def decode_bytes(c: dict, active: int, ctx: int) -> float:
    return weight_bytes(c) + active * (2 * hla2_state_bytes(c)
                                       + ctx * kv_bytes_per_token(c))


def serve_least_seconds(c: dict, peak: dict, prefills, decode_steps,
                        kv_floor: int) -> float:
    """Least time of a serving window's work: ``prefills`` lists the
    tokens of each prompt prefill, ``decode_steps`` the active sequences
    of each decode step; each program is bounded by the larger of its
    operations over the peak rate and its bytes over the memory rate."""
    f, b = peak["flops_per_s"], peak["bytes_per_s"]
    total = 0.0
    for n in prefills:
        total += max(prefill_flops(c, n) / f, prefill_bytes(c, n) / b)
    for a in decode_steps:
        total += max(decode_flops(c, a, kv_floor) / f,
                     decode_bytes(c, a, kv_floor) / b)
    return total

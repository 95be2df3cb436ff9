"""Operations and bytes of the work, computed from its shapes.

These counts are the yardstick of every roofline share and MFU: they depend
on the configuration and on the shapes of the work, never on how the
program implements it.

HLA2 chunk kernels (masked second-order HLA, chunk width ``w``, key width
``d``, value width ``dv``): one chunk of the chunkwise form costs fifteen
matrix products (arXiv 2510.27258, Sec. 5, chunk-parallel training):

* four of ``w x d`` by ``d x w``: the scores ``Q K^T`` and ``K Q^T`` of the
  output, ``(Q S0) Q^T``, and ``K Q^T`` of the carry update;
* one ``w x w`` by ``w x w``: the second-order product;
* two ``w x d`` by ``d x d``: ``Q S0`` and ``K^T K`` of the carry;
* two ``d x d`` by ``d x dv``: ``S0 C0`` and the carry's ``S_w C0``;
* three ``w x d`` by ``d x dv``: ``Q (S0 C0 - G0)``, ``Q^T V``, ``K^T (N V)``;
* three ``w x w`` by ``w x dv``: the three weighted sums of ``V``.

A product of ``m x k`` by ``k x n`` is ``2 m k n`` operations.  The
backward pass is counted as twice the forward (each product has two
adjoint products); recomputation is not counted.  A decode step costs
``4 d^2 + 10 d dv`` per head (state update and read-out, as the
streaming recurrence of Sec. 4 does it).
"""

from __future__ import annotations


def dims(c: dict):
    d = c["d_model"]
    H = c["n_heads"]
    dh = c.get("head_dim") or d // H
    return c["n_layers"], d, H, dh, c["d_ff"], c["vocab"]


def hla2_chunk_flops(w: int, d: int, dv: int) -> float:
    """Forward operations of one chunk of one head."""
    return 2.0 * (4 * w * w * d + w ** 3 + 2 * w * d * d + 2 * d * d * dv
                  + 3 * w * d * dv + 3 * w * w * dv)


def hla2_fwd_flops(rows: int, n: int, w: int, d: int, dv: int) -> float:
    """Forward operations of ``rows`` (batch x heads) sequences of ``n``
    tokens (``n`` a multiple of ``w``)."""
    return rows * (n // w) * hla2_chunk_flops(w, d, dv)


def hla2_bwd_flops(rows: int, n: int, w: int, d: int, dv: int) -> float:
    return 2.0 * hla2_fwd_flops(rows, n, w, d, dv)


def decode_step_flops(rows: int, d: int, dv: int) -> float:
    return rows * (4.0 * d * d + 10.0 * d * dv)


def matmul_params(c: dict) -> int:
    """Parameters that take part in a matrix product per token: the
    layers' projections and MLP, and the output head (the embedding is a
    lookup)."""
    L, d, H, dh, ff, V = dims(c)
    return L * (4 * d * H * dh + 3 * d * ff) + d * V


def state_bytes(c: dict) -> int:
    """Decode-state bytes of one sequence (float32 S, C, G and m, h per
    head and layer)."""
    L, d, H, dh, ff, V = dims(c)
    return L * H * (3 * dh * dh + 2 * dh) * 4


def train_flops_per_token(c: dict) -> float:
    """Model operations per trained token: forward and backward of every
    matrix product (6 per parameter) and of the HLA2 mixer (3 times its
    forward).  Recomputation is not counted."""
    L, d, H, dh, ff, V = dims(c)
    w = c["chunk"]
    mixer = H * hla2_chunk_flops(w, dh, dh) / w
    return 6.0 * matmul_params(c) + 3.0 * L * mixer


def prefill_flops_per_token(c: dict) -> float:
    L, d, H, dh, ff, V = dims(c)
    w = c["chunk"]
    return 2.0 * matmul_params(c) + L * H * hla2_chunk_flops(w, dh, dh) / w


def decode_flops_per_token(c: dict) -> float:
    L, d, H, dh, ff, V = dims(c)
    return 2.0 * matmul_params(c) + L * decode_step_flops(H, dh, dh)


def weight_bytes(c: dict, itemsize: int = 2) -> float:
    """Bytes of the weights one model step reads, at the precision the
    configuration computes in (bfloat16)."""
    return float(matmul_params(c) * itemsize)


def serve_least_seconds(c: dict, peak: dict, prefills, decode_steps) -> float:
    """Least time of a serving window's work.  ``prefills`` lists the
    tokens of each prompt prefill; ``decode_steps`` lists the active
    sequences of each decode step.  Each program is bounded by the larger
    of its operations over the peak rate and its bytes over the memory
    rate: its weights read once, each sequence's state read and written."""
    f, b = peak["flops_per_s"], peak["bytes_per_s"]
    wb, sb = weight_bytes(c), state_bytes(c)
    total = 0.0
    for n in prefills:
        total += max(n * prefill_flops_per_token(c) / f, (wb + 2 * sb) / b)
    for a in decode_steps:
        total += max(a * decode_flops_per_token(c) / f, (wb + 2 * a * sb) / b)
    return total

"""Arithmetic the per-layer metric readers share.

A reader (``bench/metrics/<name>.py``) takes the run's observation (a dict
a loop fills) and returns a number, or ``None`` where it finds nothing to
read: the harness then leaves the metric out of the line.
"""

from __future__ import annotations

from typing import Callable, Optional

from bench import costs, peaks, trace_reduce


def _peak(obs) -> dict:
    return peaks.peak(obs["device_kind"])


def idle_share(obs) -> Optional[float]:
    """Per cent of the traced window in which no operation ran."""
    tr = obs.get("trace")
    return None if tr is None else 100.0 * tr.idle_share


def train_mfu(obs) -> Optional[float]:
    """Model operations per token x tokens per second of the traced steps,
    over chips x peak."""
    tr = obs.get("trace")
    if tr is None or obs.get("steps_traced", 0) == 0:
        return None
    tok_s = obs["steps_traced"] * obs["tokens_per_step"] / (tr.window_ns / 1e9)
    flops = costs.train_flops_per_token(obs["cfg"]) * tok_s
    return 100.0 * flops / (obs["chips"] * _peak(obs)["flops_per_s"])


def serve_mfu(obs) -> Optional[float]:
    """Least time of the traced window's prefills and decode steps (each
    bounded by its operations or its bytes) over the window."""
    tr = obs.get("trace")
    if tr is None or not (obs.get("prefills") or obs.get("decode_steps")):
        return None
    least = costs.serve_least_seconds(obs["cfg"], _peak(obs),
                                      obs["prefills"], obs["decode_steps"])
    return 100.0 * least / (obs["chips"] * tr.window_ns / 1e9)


def operand_bytes(outs, operands) -> float:
    """A call's operands read once and its outputs written once."""
    return float(trace_reduce.nbytes(outs) + trace_reduce.nbytes(operands))


def kernel_roofline(obs, match: Callable[[list], bool],
                    flops: Callable[[list, dict], float],
                    byts: Callable[[list, list], float] = operand_bytes
                    ) -> Optional[float]:
    """Least time of every traced call of one kernel over the device time
    of those calls.  ``match`` tells the kernel by its output shapes;
    ``flops`` counts a call's operations from them, ``byts`` its bytes
    from its output and operand shapes."""
    tr = obs.get("trace")
    if tr is None:
        return None
    pk = _peak(obs)
    least = dur = 0.0
    for ev in tr.kernels:
        outs = trace_reduce.output_shapes(ev.name)
        if not outs or not match(outs):
            continue
        b = byts(outs, trace_reduce.operand_shapes(ev.name))
        least += max(flops(outs, obs["cfg"]) / pk["flops_per_s"],
                     b / pk["bytes_per_s"])
        dur += ev.dur / 1e9
    return None if dur == 0.0 else 100.0 * least / dur


# kernels told apart by their outputs: (rows, tokens, width) first
def _rows_tokens(outs):
    return outs[0][1][0], outs[0][1][1], outs[0][1][2]


def is_hla2_train_fwd(outs) -> bool:
    """Forward of training: the output and per-chunk saved states."""
    return len(outs[0][1]) == 3 and outs[0][1][1] > 1 and any(
        len(s) == 4 for _, s in outs)


def is_hla2_bwd(outs) -> bool:
    """Backward: dq, dk, dv and the decay's gradient (rows, 1, 1)."""
    return (len(outs) == 4 and outs[-1][1][1:] == (1, 1)
            and all(len(s) == 3 and s[1] > 1 for _, s in outs[:3]))


def is_hla2_prefill(outs) -> bool:
    """Prefill: the output over n > 1 tokens and the five final states."""
    return (len(outs) == 6 and len(outs[0][1]) == 3 and outs[0][1][1] > 1
            and not any(len(s) == 4 for _, s in outs))


def is_decode_step(outs) -> bool:
    """Decode step: one token's output per row and the five states."""
    return len(outs) == 6 and len(outs[0][1]) == 3 and outs[0][1][1] == 1


def _key_width(outs) -> int:
    """Key width of a chunk or decode call: the last dimension of its
    first state output, S (rows, dk, dk)."""
    return outs[1][1][-1]


def hla2_fwd_flops(outs, cfg) -> float:
    R, n, dv = _rows_tokens(outs)
    return costs.hla2_fwd_flops(R, n, cfg["chunk"], _key_width(outs), dv)


def hla2_bwd_flops(outs, cfg) -> float:
    """From dq (rows, n, dk) and dv (rows, n, dv)."""
    R, n, dk = _rows_tokens(outs)
    return costs.hla2_bwd_flops(R, n, cfg["chunk"], dk, outs[2][1][2])


def decode_flops(outs, cfg) -> float:
    R, _, dv = _rows_tokens(outs)
    return costs.decode_step_flops(R, _key_width(outs), dv)


def decode_step_bytes(outs, operands) -> float:
    """A decode step's bytes per row: ``R`` rows from its token output
    (R, 1, dv), the widths from that output and S.  The states' leading
    dimension is left out, so a call that updates one layer of a stack
    of states in place counts the rows it touches, not the whole stack."""
    R, _, dv = _rows_tokens(outs)
    return float(costs.decode_step_bytes(
        R, _key_width(outs), dv, trace_reduce.DTYPE_BYTES[outs[0][0]]))

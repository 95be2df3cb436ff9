"""Share of the chip's peak that the traced window's work of the hybrid
HLA2 + softmax model would take at best: the least time of the window's
prefills and decode steps (each the larger of its operations over peak and
its bytes over HBM bandwidth, ``bench/costs_hybrid.py``), over the window.
A decode step's KV reads are counted at the traffic's shortest prompt, a
lower bound, so the share cannot read above what the work allows."""

from bench import costs_hybrid, peaks


def read(obs):
    tr = obs.get("trace")
    if tr is None or not (obs.get("prefills") or obs.get("decode_steps")):
        return None
    least = costs_hybrid.serve_least_seconds(
        obs["cfg"], peaks.peak(obs["device_kind"]), obs["prefills"],
        obs["decode_steps"], obs["traffic"]["prompt_len"][0])
    return 100.0 * least / (obs["chips"] * tr.window_ns / 1e9)

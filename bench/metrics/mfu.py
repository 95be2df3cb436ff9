"""Share of the chip's peak that the traced window's work would take at
best.  Training: model operations per token (forward and backward,
recomputation not counted) x tokens per second of the traced steps, over
chips x peak.  Serving: the least time of the window's prefills and decode
steps (each the larger of its operations over peak and its bytes over HBM
bandwidth), over the window."""

from bench import readers


def read(obs):
    return (readers.train_mfu if obs["kind"] == "train"
            else readers.serve_mfu)(obs)

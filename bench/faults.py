"""Faults planted in the program, to show that the comparison catches them.

Each fault patches one function of the program for the length of a run:

* ``frozen_step``: the optimizer returns the parameters and its state
  unchanged (a training step that leaves its state as it was);
* ``half_batch``: the loss sees the first half of the batch only, its mean
  taken over that half;
* ``frozen_decode``: a decode step returns the states it was given;
* ``altered_token``: every sampled token is replaced by the next id of the
  vocabulary where the sampler produces it.

The benchmark's own runs plant nothing; the control script and the tests
do.
"""

from __future__ import annotations

import contextlib

FAULTS = ("frozen_step", "half_batch", "frozen_decode", "altered_token")


@contextlib.contextmanager
def _patch(module, name, new):
    old = getattr(module, name)
    setattr(module, name, new(old))
    try:
        yield
    finally:
        setattr(module, name, old)


def _frozen_step(old):
    def adamw_update(params, grads, state, cfg):
        _, _, metrics = old(params, grads, state, cfg)
        return params, state, metrics

    return adamw_update


def _half_batch(old):
    def loss_fn(params, batch, cfg, *a, **k):
        half = {key: v[: v.shape[0] // 2] for key, v in batch.items()}
        return old(params, half, cfg, *a, **k)

    return loss_fn


def _frozen_decode(old):
    def lm_apply(params, tokens, cfg, *, states=None, mode="train", **k):
        logits, new, aux = old(params, tokens, cfg, states=states, mode=mode,
                               **k)
        return logits, (states if mode == "decode" else new), aux

    return lm_apply


def _altered_token(old):
    def sample(logits, key, cfg):
        return (old(logits, key, cfg) + 1) % logits.shape[-1]

    return sample


@contextlib.contextmanager
def planted(fault):
    """Plant ``fault`` (one of ``FAULTS``) for the block; ``None`` plants
    nothing."""
    if fault is None:
        yield
        return
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    from repro.distributed import steps
    from repro.models import lm
    from repro.optim import adamw
    from repro.serving import engine

    target = {
        "frozen_step": (adamw, "adamw_update", _frozen_step),
        "half_batch": (steps, "_loss_fn", _half_batch),
        "frozen_decode": (lm, "lm_apply", _frozen_decode),
        "altered_token": (engine, "sample", _altered_token),
    }[fault]
    with _patch(*target):
        yield

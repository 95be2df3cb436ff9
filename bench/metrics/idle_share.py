"""Per cent of the traced window with no device operation."""

from bench import readers


def read(obs):
    return readers.idle_share(obs)

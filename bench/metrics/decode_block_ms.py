"""Mean host duration of the engine's ``engine.decode_block`` span (one
block of decode steps, ending at its token transfer) over the window."""


def read(obs):
    d = obs.get("decode_block_s")
    return 1e3 * sum(d) / len(d) if d else None

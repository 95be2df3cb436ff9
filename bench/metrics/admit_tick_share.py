"""Per cent of the engine's drive ticks spent admitting: the
``engine.admit`` spans summed over the ``engine.tick`` spans summed, among
the spans wholly inside the traced window.  A decode block waits for
every admission of its tick.  Nothing to read where the program has no
``engine.tick`` span."""


def read(obs):
    tr = obs.get("trace")
    ticks = tr.host_spans.get("engine.tick") if tr is not None else None
    if not ticks:
        return None
    return 100.0 * sum(tr.host_spans.get("engine.admit", ())) / sum(ticks)

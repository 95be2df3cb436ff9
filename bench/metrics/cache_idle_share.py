"""Per cent of the traced window in which the device sat idle while the
host was inside ``engine.cache_lookup`` or ``engine.cache_insert`` (the
idle gaps attributed to those spans, over the same window as
``idle_share``).  Nothing to read where the program has neither span."""

SPANS = ("engine.cache_lookup", "engine.cache_insert")


def read(obs):
    tr = obs.get("trace")
    if tr is None or not any(name in tr.host_spans for name in SPANS):
        return None
    gaps = dict(tr.idle_gaps)
    idle_s = sum(gaps.get(name, 0.0) for name in SPANS)
    return 100.0 * idle_s * 1e9 / tr.busy_window_ns

"""Host milliseconds the prefix cache takes per admission: the engine's
``engine.cache_lookup`` and ``engine.cache_insert`` spans summed, over the
number of ``engine.admit`` spans, among the spans wholly inside the traced
window.  Nothing to read where the program has no ``engine.admit`` span."""


def read(obs):
    tr = obs.get("trace")
    admits = tr.host_spans.get("engine.admit") if tr is not None else None
    if not admits:
        return None
    ns = sum(sum(tr.host_spans.get(name, ()))
             for name in ("engine.cache_lookup", "engine.cache_insert"))
    return ns / 1e6 / len(admits)

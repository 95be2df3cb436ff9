"""Per cent of prompt tokens served from the prefix cache over the
window, from the cache's hit-token histogram and the engine's prompt-token
counter."""


def read(obs):
    c = obs.get("counters") or {}
    if not c.get("prompt_tokens"):
        return None
    return 100.0 * c["hit_tokens"] / c["prompt_tokens"]

"""95th percentile of the time to first token over the turns due in the
window, as the serving loop measures it on the host clock."""


def read(obs):
    return obs.get("ttft_p95_ms")

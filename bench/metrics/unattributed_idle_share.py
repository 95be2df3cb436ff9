"""Per cent of the traced window in which the device sat idle while the
host was inside no span of the program or the harness (the idle gaps under
"no host span", over the same window as ``idle_share``).

The reduction lists only the ten names with the most idle time.  Where
"no host span" is absent from a shorter list it had no gap, and the share
is 0; absent from a full list, it may have been cut, and there is nothing
to read."""

NAME = "no host span"
LISTED = 10  # names trace_reduce.reduce_trace keeps in idle_gaps


def read(obs):
    tr = obs.get("trace")
    if tr is None:
        return None
    gaps = dict(tr.idle_gaps)
    if NAME in gaps:
        return 100.0 * gaps[NAME] * 1e9 / tr.busy_window_ns
    return 0.0 if len(gaps) < LISTED else None

"""The one traffic generator: seeded, the same work for every seed."""

import json
import os

import numpy as np
import pytest

from bench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))


def _mix(name):
    with open(os.path.join(HERE, "..", "traffic", name + ".json")) as f:
        return json.load(f)


def test_train_rows_repeat_for_a_seed_and_differ_across_seeds():
    t = {"batch": 2, "seq_len": 16}
    a = traffic.train_batches(t, 5, 100, 3)
    assert np.array_equal(a, traffic.train_batches(t, 5, 100, 3))
    assert not np.array_equal(a, traffic.train_batches(t, 6, 100, 3))
    rows = a.reshape(-1, 17)
    assert len({r.tobytes() for r in rows}) == len(rows)
    # a longer run starts with the same rows
    assert np.array_equal(a, traffic.train_batches(t, 5, 100, 5)[:3])


def test_sessions_same_work_in_another_order():
    t = _mix("sessions-8k")
    seeds = (1, 2 ** 33 + 7)
    runs = [traffic.sessions(t, s, 1000, 30.0) for s in seeds]
    a, b = runs
    assert not np.array_equal(a[0][0], b[0][0])  # documents differ
    for docs, sess in runs:
        assert [len(d) for d in docs] == [t["doc_len"]] * t["docs"]
        arr = [s.arrival for s in sess]
        assert arr == sorted(arr) and arr[0] == 0.0
    n = min(len(a[1]), len(b[1]))
    full = [traffic.sessions(t, s, 1000, 1e4)[1] for s in seeds]
    sizes = [sorted(len(u) for s in f for u in s.user) for f in full]
    assert sizes[0] == sizes[1]
    # the plan is the same for every seed, not only its multiset; the
    # token contents are the seed's own
    plan = [[(s.arrival, s.doc, [len(u) for u in s.user], s.think)
             for s in sess] for _, sess in runs]
    assert plan[0] == plan[1]
    assert not np.array_equal(a[1][0].user[0], b[1][0].user[0])
    thinks = [sorted(x for s in f for x in s.think) for f in full]
    assert np.allclose(thinks[0], thinks[1])
    gaps = [np.sort(np.diff([s.arrival for s in f])) for f in full]
    # the same quantiles of the exponential, less the one before the first
    q = np.sort(traffic.exp_quantiles(len(gaps[0]) + 1, 1 / t["rate_per_s"],
                                      np.random.default_rng(0)))
    for g in gaps:
        near = np.minimum(abs(g - q[:-1]), abs(g - q[1:]))
        assert near.max() < 1e-6
    assert n > 0


def test_sessions_arrival_rate_and_zipf_documents():
    t = _mix("sessions-8k")
    _, sess = traffic.sessions(t, 3, 1000, 400.0)
    span = sess[-1].arrival - sess[0].arrival
    assert abs(len(sess) / span - t["rate_per_s"]) < 0.1 * t["rate_per_s"]
    counts = np.bincount([s.doc for s in sess], minlength=t["docs"])
    assert list(counts) == sorted(counts, reverse=True)


def test_closed_clients_spread_their_first_finishes():
    t = _mix("decode-long")
    a = traffic.closed_requests(t, 11, 1000, 3)
    b = traffic.closed_requests(t, 12, 1000, 3)
    firsts = sorted(r[0][1] for r in a)
    assert firsts == sorted(r[0][1] for r in b)
    assert len(set(firsts)) == t["clients"]
    lo, hi, step = t["out_len"]
    for reqs in a:
        for prompt, m in reqs:
            assert len(prompt) in traffic.grid(t["prompt_len"])
            assert m % step == 0 and m <= hi


def test_zipf_counts_sum():
    c = traffic.zipf_counts(10, 4, 1.2)
    assert sum(c) == 10 and c == sorted(c, reverse=True)


def test_open_loop_due_times_and_lateness():
    """Requests are handed out once due, in due order, and the generator's
    lateness is the hand-out time less the due time."""
    from bench.loops import serve

    load = serve.Schedule()
    for due in (1.0, 0.0, 0.5):
        load.add(due, prompt=None)
    got = load.pop_due(0.6, now=0.7)
    assert [r["due"] for _, r in got] == [0.0, 0.5]
    assert load.lateness([rid for rid, _ in got]) == pytest.approx([0.7, 0.2])
    assert load.lateness([0]) == [] and load.pending == [(1.0, 0)]
    assert [rid for rid, _ in load.pop_due(5.0, now=5.0)] == [0]


def test_recorder_times_tokens_by_the_engine_hook():
    from bench.loops import serve

    rec = serve.Recorder()
    rec.on_stream(1, [5, 6], None)
    rec.on_stream(1, [7], None)

    class R:
        status = "ok"
        tokens = [5, 6, 7]

    rec.on_stream(1, [], R())
    assert rec.tokens == 3
    assert rec.first[1] <= rec.last[1] <= rec.done[1][0]
    assert rec.finished == [1]

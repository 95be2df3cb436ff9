"""Observability substrate tests (DESIGN.md §13).

Four contracts:

* **Registry semantics** — labeled series, fixed bucket edges, bounded
  reservoirs, quantiles, snapshot/merge, Prometheus exposition.
* **Tracer semantics** — span nesting depth, bounded ring, error spans,
  JSONL write-through.
* **Engine timeline completeness** — under fault injection, every
  terminal ``GenResult`` has a matching ``request.done`` event and the
  status-labeled counters agree with the returned results.
* **Overhead guard** — attaching sinks to a decode run adds ZERO host
  syncs (counted by wrapping ``jax.device_get``): all obs timings ride
  transfers the engine already performs.
"""

import dataclasses
import io
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointError, CheckpointManager
from repro.configs import get_config
from repro.models import lm
from repro.models.param import init_params
from repro.obs import (
    JsonlSink,
    Obs,
    Registry,
    Tracer,
    check_timelines,
    console_summary,
    prometheus_text,
    read_jsonl,
    request_timelines,
    terminal_events,
)
from repro.obs.validate import (
    check_requests,
    counter_total,
    main as validate_main,
    validate_events,
    validate_metrics,
)
from repro.runtime.faults import FaultPlan, FaultSpec
from repro.runtime.ft import FaultTolerantLoop
from repro.serving import Engine, GenRequest
from repro.serving.cache import PrefixCache


def _cfg():
    base = get_config("hla-1b", reduced=True).replace(mixer="hla2")
    return base.replace(
        n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, d_ff=64, vocab=64,
        hla=dataclasses.replace(base.hla, chunk=16),
    )


@pytest.fixture(scope="module")
def cfg():
    return _cfg()


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(lm.lm_specs(cfg), jax.random.key(0))


def _requests(cfg, lens=(5, 11, 7, 9), max_new=10, **kw):
    return [
        GenRequest(rid=i,
                   prompt=np.random.RandomState(10 + i).randint(
                       2, cfg.vocab, ln),
                   max_new=max_new, **kw)
        for i, ln in enumerate(lens)
    ]


def _engine(cfg, params, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_len", 96)
    kw.setdefault("block", 4)
    return Engine(cfg, params, **kw)


# -- registry ---------------------------------------------------------------


class TestRegistry:
    def test_counter_labels_and_total(self):
        reg = Registry()
        c = reg.counter("reqs_total", "requests")
        c.inc(status="ok")
        c.inc(status="ok")
        c.inc(3, status="error")
        assert c.value(status="ok") == 2
        assert c.value(status="error") == 3
        assert c.value(status="timeout") == 0
        assert c.total() == 5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_declaration_idempotent_but_kind_checked(self):
        reg = Registry()
        a = reg.counter("x_total")
        assert reg.counter("x_total") is a
        with pytest.raises(ValueError):
            reg.gauge("x_total")

    def test_gauge(self):
        reg = Registry()
        g = reg.gauge("depth")
        g.set(4.0)
        g.inc()
        assert g.value() == 5.0

    def test_histogram_bucket_edges(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(1.0, 2.0, 4.0))
        for v in (0.5, 1.0, 2.5, 5.0, 100.0):
            h.observe(v)
        (series,) = h.snapshot_series()
        # bisect_left: a value equal to an edge lands in that edge's
        # bucket; values past the last edge go to the overflow bucket
        assert series["bucket_counts"] == [2, 0, 1, 2]
        assert series["count"] == 5
        assert series["min"] == 0.5 and series["max"] == 100.0
        with pytest.raises(ValueError):
            reg.histogram("bad", buckets=(2.0, 1.0))

    def test_histogram_reservoir_bounded(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(1.0,), sample_cap=64)
        for i in range(5000):
            h.observe(float(i))
        assert len(h.recent()) == 64
        (series,) = h.snapshot_series()
        assert series["count"] == 5000
        # the ring keeps the NEWEST samples
        assert min(h.recent()) >= 5000 - 64

    def test_quantile_exact_under_cap(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=(10.0,))
        for v in range(1, 11):
            h.observe(float(v))
        assert h.quantile(0.0) == 1.0
        assert h.quantile(0.5) == 6.0
        assert h.quantile(1.0) == 10.0
        assert reg.histogram("empty", buckets=(1.0,)).quantile(0.5) is None

    def test_quantile_interpolated_past_cap(self):
        reg = Registry()
        h = reg.histogram("lat", buckets=tuple(float(i) for i in range(1, 10)),
                          sample_cap=8)
        rng = np.random.RandomState(0)
        for v in rng.uniform(0.0, 9.0, 500):
            h.observe(float(v))
        q25, q50, q75 = (h.quantile(q) for q in (0.25, 0.5, 0.75))
        assert 0.0 <= q25 <= q50 <= q75 <= 9.0
        assert abs(q50 - 4.5) < 1.5  # uniform: median near the middle

    def test_snapshot_merge(self):
        a, b = Registry(), Registry()
        a.counter("c_total").inc(2, status="ok")
        a.gauge("g").set(1.0)
        a.histogram("h", buckets=(1.0, 2.0)).observe(0.5)
        b.counter("c_total").inc(3, status="ok")
        b.gauge("g").set(7.0)
        b.histogram("h", buckets=(1.0, 2.0)).observe(1.5)
        a.merge(b.snapshot())
        assert a.get("c_total").value(status="ok") == 5
        assert a.get("g").value() == 7.0  # last-write-wins
        (series,) = a.get("h").snapshot_series()
        assert series["count"] == 2
        assert series["bucket_counts"] == [1, 1, 0]
        with pytest.raises(ValueError):
            a.merge({"schema": "nope"})

    def test_snapshot_validates_and_renders(self):
        reg = Registry()
        reg.counter("c_total", "help text").inc(status="ok")
        reg.histogram("h_seconds", buckets=(0.1, 1.0)).observe(0.05)
        snap = reg.snapshot()
        validate_metrics(snap)  # raises on malformed snapshots
        assert json.loads(json.dumps(snap)) == snap  # JSON-able
        text = prometheus_text(snap)
        assert '# TYPE c_total counter' in text
        assert 'c_total{status="ok"} 1.0' in text
        assert 'h_seconds_bucket{le="0.1"} 1' in text
        assert 'h_seconds_count 1' in text
        assert "c_total" in console_summary(snap)
        assert counter_total(snap, "c_total") == 1.0
        with pytest.raises(ValueError):
            counter_total(snap, "h_seconds")

    def test_reset_keeps_declarations(self):
        reg = Registry()
        c = reg.counter("c_total")
        c.inc(5)
        reg.reset()
        assert reg.get("c_total") is c
        assert c.total() == 0


# -- tracer -----------------------------------------------------------------


class TestTracer:
    def test_span_nesting_depth(self):
        t = Tracer(annotate=False)
        with t.span("outer"):
            with t.span("inner", rid=1):
                pass
        inner, outer = t.events(kind="span")
        assert (inner["name"], inner["depth"], inner["rid"]) == ("inner", 1, 1)
        assert (outer["name"], outer["depth"]) == ("outer", 0)
        assert 0.0 <= inner["dur_s"] <= outer["dur_s"]
        assert inner["seq"] < outer["seq"]  # inner closes first

    def test_ring_bounded(self):
        t = Tracer(ring=8, annotate=False)
        for i in range(50):
            t.event("tick", i=i)
        evs = t.events()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(42, 50))
        with pytest.raises(ValueError):
            Tracer(ring=0)

    def test_error_span_recorded_and_raises(self):
        t = Tracer(annotate=False)
        with pytest.raises(RuntimeError):
            with t.span("boom"):
                raise RuntimeError("x")
        (rec,) = t.events(kind="span")
        assert rec["error"] is True

    def test_jsonl_write_through_roundtrip(self, tmp_path):
        path = str(tmp_path / "e.jsonl")
        t = Tracer(annotate=False)
        sink = JsonlSink(path)
        t.attach(sink)
        t.event("before.close", rid=1)
        with t.span("work", rid=1):
            pass
        sink.close()
        evs = read_jsonl(path)  # drops + validates the header line
        assert [e["name"] for e in evs] == ["before.close", "work"]
        validate_events(evs)
        with open(path) as f:
            header = json.loads(f.readline())
        assert header["schema"] == "repro.obs.events/v1"
        assert "epoch_offset" in header

    def test_span_yields_labels_set_at_the_end(self):
        t = Tracer(annotate=False)
        with t.span("engine.tick", fixed=1) as lab:
            lab["admitted"] = 3
        (rec,) = t.events(kind="span")
        assert (rec["fixed"], rec["admitted"]) == (1, 3)

    def test_anchor_maps_span_ts_onto_the_profiler_trace(self, tmp_path):
        """A span's ``ts``, mapped through the ``profile.start`` anchor,
        lands within 1 ms of its annotation's start in the xplane."""
        from repro.obs import profile_capture

        obs = Obs(annotate=True)
        with profile_capture(str(tmp_path), obs=obs):
            for i in range(3):
                with obs.span("work.step", i=i):
                    (jnp.ones((32, 32)) @ jnp.ones((32, 32))
                     ).block_until_ready()
        (path,) = tmp_path.rglob("*.xplane.pb")
        starts = {}
        for plane in jax.profiler.ProfileData.from_file(str(path)).planes:
            if plane.name.startswith("/host:CPU"):
                for line in plane.lines:
                    for e in line.events:
                        starts.setdefault(e.name, []).append(e.start_ns)
        (anchor,) = obs.events(name="profile.start")
        assert "wall_ns" not in anchor
        (anchor_ns,) = starts["profile.start"]
        spans = obs.events(name="work.step")
        assert len(spans) == len(starts["work.step"]) == 3
        for rec, start_ns in zip(spans, sorted(starts["work.step"])):
            mapped = (rec["ts"] - anchor["ts"]) * 1e9 + anchor_ns
            assert abs(mapped - start_ns) < 1e6
        (stop,) = obs.events(name="profile.stop")
        mapped = (stop["ts"] - anchor["ts"]) * 1e9 + anchor_ns
        assert abs(mapped - starts["profile.stop"][0]) < 1e6

    def test_obs_reset_clears_both(self):
        obs = Obs(annotate=False)
        obs.counter("c_total").inc()
        obs.event("e")
        obs.reset()
        assert obs.registry.get("c_total").total() == 0
        assert obs.events() == []


# -- engine integration -----------------------------------------------------


class TestEngineTimelines:
    def test_timeline_completeness_under_faults(self, cfg, params):
        # 3 valid requests + 1 invalid; NaN-poison slot 0 at block hit 1
        eng = _engine(
            cfg, params,
            faults=FaultPlan(FaultSpec("engine.nan_state", at=1, arg=0)),
        )
        reqs = _requests(cfg, lens=(5, 11, 7))
        reqs.append(GenRequest(rid=9, prompt=np.asarray([cfg.vocab + 5]),
                               max_new=4))
        results = eng.run(reqs)
        evs = eng.obs.events()
        # every terminal result has a matching-status request.done event
        check_timelines(evs, results)
        # the lifecycle is complete: queued -> ... -> done for every rid
        tls = request_timelines(evs)
        for r in results:
            names = [e["name"] for e in tls[r.rid]]
            assert names[0] == "request.queued"
            assert names[-1] == "request.done"
            if r.status == "ok":
                assert "request.admitted" in names
                assert "request.first_token" in names
        # status-labeled counters agree with the returned results
        m = eng.obs.registry.get("serving_requests_total")
        import collections
        by_status = collections.Counter(r.status for r in results)
        for status, n in by_status.items():
            assert m.value(status=status) == n
        assert m.total() == len(results)
        assert by_status["error"] == 2  # quarantine + invalid admission
        assert eng.obs.registry.get(
            "serving_quarantined_total").total() == 1
        # the fired injection self-documented through the engine's obs
        assert eng.obs.registry.get("faults_fired_total").value(
            point="engine.nan_state") == 1
        (fired,) = eng.obs.events(name="fault.fired")
        assert fired["point"] == "engine.nan_state"
        # block spans closed with the fields the docs promise
        spans = eng.obs.events(name="engine.decode_block")
        assert spans and all(s["dur_s"] > 0 for s in spans)
        assert eng.obs.registry.get("serving_ttft_seconds").count() == 3

    def test_stats_shim_compat(self, cfg, params):
        eng = _engine(cfg, params)
        results = eng.run(_requests(cfg, lens=(5, 7)))
        st = eng.stats
        gen = sum(len(r.tokens) for r in results)
        assert st["generated_tokens"] == gen
        assert isinstance(st["generated_tokens"], int)
        assert st["errors"] == 0
        assert len(st["ttft_s"]) == 2 and st["decode_s"] > 0
        assert dict(st)["prompt_tokens"] == 5 + 7  # MutableMapping view
        # the legacy post-warmup reset idiom still zeroes the registry
        eng.stats.update(prefill_s=0.0, decode_s=0.0, prompt_tokens=0,
                         generated_tokens=0, ttft_s=[])
        assert st["generated_tokens"] == 0 and st["ttft_s"] == []
        assert eng.obs.registry.get(
            "serving_generated_tokens_total").total() == 0

    def test_engines_do_not_share_obs(self, cfg, params):
        a, b = _engine(cfg, params), _engine(cfg, params)
        assert a.obs is not b.obs
        a.obs.counter("serving_quarantined_total").inc()
        assert b.obs.registry.get("serving_quarantined_total").total() == 0

    def test_sinks_add_zero_host_syncs(self, cfg, params):
        """The overhead contract: obs never adds a device round trip.
        Count ``jax.device_get`` calls for identical traffic with and
        without a write-through sink attached — they must be EQUAL."""
        real = jax.device_get

        def run_once(sink):
            eng = _engine(cfg, params)
            if sink is not None:
                eng.obs.attach(sink)
            n = [0]

            def counting(x):
                n[0] += 1
                return real(x)

            jax.device_get = counting
            try:
                results = eng.run(_requests(cfg))
            finally:
                jax.device_get = real
            return n[0], [r.tokens for r in results]

        bare_syncs, bare_toks = run_once(None)
        sink_syncs, sink_toks = run_once(JsonlSink(io.StringIO()))
        assert bare_syncs > 0
        assert sink_syncs == bare_syncs
        assert sink_toks == bare_toks  # sinks never perturb decode either


class TestEngineSpans:
    """Every host interval of a drive tick falls inside a named span, the
    spans nest as documented, and they add no host sync."""

    SLACK = 1e-9  # float rounding of ts + dur_s

    def _run(self, cfg, params):
        eng = _engine(cfg, params, cache=PrefixCache(
            granularity=cfg.hla.chunk, budget_bytes=1 << 26))
        base = np.random.RandomState(0).randint(2, cfg.vocab, 2 * 16 + 1)
        reqs = [GenRequest(rid=i, prompt=np.concatenate([base, np.random.
                RandomState(20 + i).randint(2, cfg.vocab, 3 + i)]),
                max_new=6) for i in range(4)]
        real = jax.device_get
        stack = lambda: list(getattr(eng.obs.tracer._local, "stack", []))  # noqa: E731,E501
        calls = []

        def counting(x):
            calls.append((stack(),
                          len(eng.obs.events(name="engine.admit")),
                          len(eng.obs.events(name="engine.decode_block"))))
            return real(x)

        jax.device_get = counting
        try:
            results = eng.run(reqs)
        finally:
            jax.device_get = real
        assert all(r.status == "ok" for r in results)
        return eng, calls

    def _inside(self, child, parent):
        return (parent["ts"] - self.SLACK <= child["ts"] and
                child["ts"] + child["dur_s"]
                <= parent["ts"] + parent["dur_s"] + self.SLACK)

    def test_spans_nest_and_depth_agrees(self, cfg, params):
        eng, _ = self._run(cfg, params)
        spans = eng.obs.events(kind="span")
        by = lambda n: [s for s in spans if s["name"] == n]  # noqa: E731
        ticks, admits = by("engine.tick"), by("engine.admit")
        assert ticks and len(admits) == 4
        assert sum(t["admitted"] for t in ticks) == 4
        assert eng.obs.registry.get("cache_hits_total").total() >= 1

        def held(parent, name):
            return [s for s in by(name) if self._inside(s, parent)]

        for a in admits:
            (look,) = held(a, "engine.cache_lookup")
            (pre,) = held(a, "engine.prefill")
            assert len(held(a, "engine.cache_insert")) <= 1
            assert self._inside(look, pre)
            assert (pre["depth"], look["depth"]) == (a["depth"] + 1,
                                                     a["depth"] + 2)
            assert pre["rid"] == a["rid"] and "hit_len" in look
        for ins in by("engine.cache_insert"):
            (a,) = [a for a in admits if self._inside(ins, a)]
            assert ins["depth"] == a["depth"] + 1 and ins["nbytes"] > 0
        for t in ticks:
            assert t["depth"] == 0
            assert len(held(t, "engine.admit")) == t["admitted"]
            for name in ("engine.admit", "engine.decode_block",
                         "engine.commit"):
                assert all(s["depth"] == 1 for s in held(t, name))
            blocks = held(t, "engine.decode_block")
            assert len(blocks) <= 1
            assert len(held(t, "engine.commit")) == len(blocks)
        # every span of the engine lies inside some tick
        for s in spans:
            if s["name"] != "engine.tick":
                assert any(self._inside(s, t) for t in ticks), s["name"]
        hits = [s["hit_len"] for s in by("engine.cache_lookup")]
        assert hits[0] == 0 and max(hits) == 2 * cfg.hla.chunk

    def test_one_device_get_per_admission_and_per_block(self, cfg, params):
        eng, calls = self._run(cfg, params)
        n_admit = len(eng.obs.events(name="engine.admit"))
        n_block = len(eng.obs.events(name="engine.decode_block"))
        in_admit = [a for st, a, _ in calls if "engine.admit" in st]
        in_block = [b for st, _, b in calls if "engine.decode_block" in st]
        # the k-th call inside each kind happens while k spans are closed
        assert in_admit == list(range(n_admit))
        assert in_block == list(range(n_block))
        assert len(calls) == n_admit + n_block
        assert all("engine.prefill" in st or "engine.decode_block" in st
                   for st, _, _ in calls)


# -- checkpoint + training-loop integration ---------------------------------


class TestCkptMetrics:
    def test_save_restore_metrics(self, tmp_path):
        mgr = CheckpointManager(str(tmp_path), async_save=False)
        tree = {"w": np.arange(6.0), "b": np.zeros(2)}
        mgr.save(0, tree)
        mgr.restore(tree)
        reg = mgr.obs.registry
        assert reg.get("ckpt_saves_total").total() == 1
        assert reg.get("ckpt_restores_total").total() == 1
        assert reg.get("ckpt_save_seconds").count() == 1
        assert reg.get("ckpt_restore_seconds").count() == 1
        assert reg.get("ckpt_save_failures_total").total() == 0
        names = [e["name"] for e in mgr.obs.events(kind="span")]
        assert names == ["ckpt.save", "ckpt.restore"]

    def test_checksum_failure_counted(self, tmp_path):
        mgr = CheckpointManager(
            str(tmp_path), async_save=False,
            faults=FaultPlan(FaultSpec("ckpt.corrupt", at=0)),
        )
        tree = {"w": np.arange(64.0)}
        mgr.save(0, tree)
        with pytest.raises(CheckpointError, match="checksum"):
            mgr.restore(tree)
        assert mgr.obs.registry.get(
            "ckpt_checksum_failures_total").total() == 1
        assert mgr.obs.registry.get("ckpt_restores_total").total() == 0

    def test_save_failure_counted(self, tmp_path):
        mgr = CheckpointManager(
            str(tmp_path), async_save=False,
            faults=FaultPlan(FaultSpec("ckpt.save", at=0)),
        )
        with pytest.raises(Exception):
            mgr.save(0, {"w": np.zeros(2)})
        assert mgr.obs.registry.get("ckpt_save_failures_total").total() == 1
        assert mgr.obs.registry.get("ckpt_saves_total").total() == 0


class _Stream:
    def batch(self, step):
        return {"tokens": np.ones((2, 8), np.int32),
                "labels": np.ones((2, 8), np.int32)}


def _toy_step(params, opt_state, batch):
    return params, opt_state, {"loss": jnp.asarray(0.5)}


class TestLoopMetrics:
    def test_step_and_restart_metrics(self, tmp_path):
        quiet = lambda *a, **k: None  # noqa: E731
        p, o = {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}
        loop = FaultTolerantLoop(
            _toy_step, _Stream(), str(tmp_path), ckpt_every=2, log=quiet,
        )
        loop.run(p, o, 4)
        reg = loop.obs.registry
        assert reg.get("train_steps_total").total() == 4
        assert reg.get("train_tokens_total").total() == 4 * 2 * 8
        assert reg.get("train_step_seconds").count() == 4
        assert reg.get("train_loss").value() == 0.5
        assert reg.get("train_restarts_total").total() == 0
        assert reg.get("ckpt_saves_total").total() == 2  # steps 1 and 3
        assert len(loop.obs.events(name="train.step")) == 4

        # a second loop over the same dir auto-resumes: restart counted,
        # and only the remaining steps run
        loop2 = FaultTolerantLoop(
            _toy_step, _Stream(), str(tmp_path), ckpt_every=2, log=quiet,
        )
        loop2.run(p, o, 6)
        reg2 = loop2.obs.registry
        assert reg2.get("train_restarts_total").total() == 1
        assert reg2.get("train_steps_total").total() == 2  # steps 4, 5
        (ev,) = loop2.obs.events(name="train.resumed")
        assert ev["step"] == 3


# -- validator CLI ----------------------------------------------------------


class TestValidateCli:
    def _artifacts(self, tmp_path):
        obs = Obs(annotate=False)
        obs.counter("serving_quarantined_total").inc()
        for rid in (0, 1, 2):
            obs.event("request.queued", rid=rid)
            obs.event("request.done", rid=rid,
                      status="ok" if rid else "error")
        mpath, epath = str(tmp_path / "m.json"), str(tmp_path / "e.jsonl")
        with open(mpath, "w") as f:
            json.dump(obs.snapshot(), f)
        sink = JsonlSink(epath)
        for e in obs.events():
            sink.emit(e)
        sink.close()
        return mpath, epath

    def test_main_ok_and_fail(self, tmp_path, capsys):
        mpath, epath = self._artifacts(tmp_path)
        assert validate_main([
            "--metrics", mpath, "--events", epath,
            "--expect-counter", "serving_quarantined_total=1",
            "--expect-requests", "3",
            "--expect-terminal-statuses", "ok,error",
        ]) == 0
        assert validate_main([
            "--metrics", mpath,
            "--expect-counter", "serving_quarantined_total=7",
        ]) == 1
        assert validate_main([
            "--events", epath, "--expect-requests", "4",
        ]) == 1
        capsys.readouterr()

    def test_vanished_request_detected(self):
        events = [
            {"kind": "event", "name": "request.queued", "rid": 0,
             "ts": 0.0, "seq": 0},
        ]
        with pytest.raises(ValueError, match="vanished"):
            check_requests(events, 0)
        assert terminal_events(events) == {}

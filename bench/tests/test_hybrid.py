"""The hybrid HLA2 + softmax model (Olmo Hybrid's 3:1 layer pattern) at a
small size on the CPU: its plain reference against the definition, the
program against the reference (the forward, and the Engine's prefill and
decode through per-slot KV caches), a whole harness run of a tiny hybrid
cell, and the operation counts behind ``mfu_hybrid`` and
``decode_step_roofline`` at the published widths.

Tolerances.  Program and reference both compute in float32 here; they
differ in the order of their sums (the program's chunked and recurrent
HLA2 forms and blocked softmax against the reference's chunk form at
"highest" precision), which moves logits of size about 1 by a few 1e-6.
``TOL`` = 2e-4 leaves that room many times over, while one bfloat16
rounding of the activations moves the same logits by about 1e-2.
"""

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import control, costs, costs_hybrid, peaks, readers, run
from bench.models import hla2_hybrid_lm as prog
from bench.models import hla2_hybrid_lm_ref as ref
from bench.tests import tiny
from bench.tests.test_reference import _naive

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
TOL = 2e-4
SEED = 2 ** 32 + 9


def _config(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def tiny_model():
    c = _config("tiny-hybrid.json")
    W = ref.make_weights(c, ref.seed_key(SEED))
    pcfg = prog.program_config(c)
    params = prog.to_program(W)
    prog.check_tree(pcfg, params)
    return c, W, pcfg, params


def _ref_logits(c, W, tokens, positions):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits_at(c, W, jnp.asarray(tokens),
                                        jnp.asarray(positions)))


def test_chunked_hla2_matches_the_definition_at_unequal_widths():
    rng = np.random.default_rng(3)
    H, n, d, dv = 2, 11, 3, 5  # key width != value width, ragged n
    q, k = (rng.standard_normal((H, n, d)).astype(np.float32) * 0.5
            for _ in range(2))
    v = rng.standard_normal((H, n, dv)).astype(np.float32)
    g = np.array([0.9, 0.6], np.float32)
    with jax.default_matmul_precision("highest"):
        got = ref.hla2(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                       jnp.asarray(g), chunk=4)
    want = _naive(*(a.astype(np.float64) for a in (q, k, v, g)))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-4)


def test_blocked_softmax_matches_one_pass():
    rng = np.random.default_rng(4)
    q, k, v = (jnp.asarray(rng.standard_normal((3, 37, 8)), jnp.float32)
               for _ in range(3))
    with jax.default_matmul_precision("highest"):
        got = ref.softmax_attention(q, k, v, block=16)
        s = jnp.einsum("hqd,hkd->hqk", q, k) / np.sqrt(8)
        s = jnp.where(jnp.tril(jnp.ones((37, 37), bool)), s, -jnp.inf)
        want = jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(s, -1), v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_program_forward_matches_the_reference(tiny_model):
    from repro.models import lm

    c, W, pcfg, params = tiny_model
    assert pcfg.layer_pattern == ("mixer", "mixer", "mixer", "attn")
    assert pcfg.hla_widths() == (8, 16)
    toks = np.random.default_rng(1).integers(0, c["vocab"], 40)
    want = _ref_logits(c, W, toks, np.arange(40))
    with jax.default_matmul_precision("highest"):
        got, _, _ = lm.lm_apply(params, jnp.asarray(toks)[None], pcfg)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=TOL, atol=TOL)


def _serve(pcfg, params, prompts, max_new, monkeypatch):
    """Serve ``prompts`` together through the Engine (greedy, 2 slots);
    returns, per prompt, its served tokens and the logits the Engine
    sampled each of them from (its prefill's, then its decode steps')."""
    from repro.serving import engine as engine_mod
    from repro.serving import Engine, GenRequest, SamplingConfig

    seen = []
    real = engine_mod.sample

    def sample(logits, key, scfg):
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), logits,
                           ordered=True)
        return real(logits, key, scfg)

    monkeypatch.setattr(engine_mod, "sample", sample)
    eng = Engine(pcfg, params, slots=2, max_len=64, block=4,
                 sampling=SamplingConfig("greedy"))
    reqs = [GenRequest(rid=i, prompt=np.asarray(p, np.int32),
                       max_new=max_new) for i, p in enumerate(prompts)]
    res = eng.run(reqs)
    jax.effects_barrier()
    slot = {e["rid"]: e["slot"] for e in eng.obs.events("request.admitted")}
    prefills = [x for x in seen if x.shape[0] == 1]
    steps = [x for x in seen if x.shape[0] == 2]
    assert len(prefills) == len(prompts)
    out = []
    for i in range(len(prompts)):
        toks = res[i].tokens
        rows = [prefills[i][0]] + [s[slot[i]] for s in steps[: len(toks) - 1]]
        out.append((toks, np.stack(rows)))
    return out


def test_engine_prefill_then_decode_matches_the_reference(tiny_model,
                                                          monkeypatch):
    """Engine admission (prefill into a slot's KV cache and HLA2 state),
    then decode blocks through the per-slot caches: the logits at every
    served position agree with the reference's full forward."""
    c, W, pcfg, params = tiny_model
    prompt = np.random.default_rng(5).integers(0, c["vocab"], 21)
    [(toks, got)] = _serve(pcfg, params, [prompt], 14, monkeypatch)
    assert len(toks) == 14
    seq = np.concatenate([prompt, toks[:-1]])
    want = _ref_logits(c, W, seq, np.arange(20, 20 + len(toks)))
    np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


def test_two_slots_at_different_lengths_match_each_alone(tiny_model,
                                                         monkeypatch):
    """Two requests admitted at different prompt lengths share decode
    blocks, each row appending and attending at its own length: each gets
    the logits it gets alone."""
    c, W, pcfg, params = tiny_model
    rng = np.random.default_rng(6)
    prompts = [rng.integers(0, c["vocab"], n) for n in (9, 30)]
    both = _serve(pcfg, params, prompts, 12, monkeypatch)
    for p, (toks, got) in zip(prompts, both):
        [(alone_toks, alone)] = _serve(pcfg, params, [p], 12, monkeypatch)
        assert list(toks) == list(alone_toks)
        np.testing.assert_allclose(got, alone, rtol=1e-5, atol=1e-5)
        seq = np.concatenate([p, toks[:-1]])
        want = _ref_logits(c, W, seq, np.arange(len(p) - 1, len(seq)))
        np.testing.assert_allclose(got, want, rtol=TOL, atol=TOL)


# --------------------------------------------------------------------------
# a whole run of the harness on a tiny hybrid cell
# --------------------------------------------------------------------------

CELL = "tiny-hybrid-closed"


@pytest.fixture(scope="module")
def hybrid_root(tmp_path_factory):
    """The tiny benchmark tree with a hybrid configuration and one closed
    serving cell on it, added by files and entries alone."""
    d = str(tmp_path_factory.mktemp("bench_hybrid"))
    spec = tiny.make_root(d)
    dest = os.path.join(d, "bench")
    c = _config("tiny-hybrid.json")
    with open(os.path.join(dest, "configs", "tiny-hybrid.json"), "w") as f:
        json.dump(c, f)
    with open(os.path.join(dest, "limits", CELL + ".json"), "w") as f:
        json.dump({"logit_gap_mean": 1e-4}, f)
    with open(os.path.join(HERE, "data", "tiny-closed.json")) as f:
        # enough requests that the plan outlasts the window on any host
        t = dict(json.load(f), loop="serve_fixed", per_client=400)
    with open(os.path.join(dest, "traffic", "tiny-closed-fixed.json"),
              "w") as f:
        json.dump(t, f)
    spec["configs"].append({"name": "tiny-hybrid", "source": "tests",
                            "file": "bench/configs/tiny-hybrid.json",
                            "reduced": [], "why": "tests"})
    spec["workloads"].append({"name": CELL, "config": "tiny-hybrid",
                              "traffic": "tiny-closed-fixed", "chips": 1,
                              "why": "tests"})
    for m in spec["end_to_end"]:
        if m["name"] in ("tpot_p95_ms", "gen_tok_s"):
            m["workloads"].append(CELL)
    with open(os.path.join(d, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f, indent=1)
    return d, spec


@pytest.mark.parametrize("fault", [None, "altered_token", "frozen_decode"])
def test_hybrid_cell_correct_only_without_a_fault(hybrid_root, fault):
    path, spec = hybrid_root
    res = run.run_cell(spec, CELL, seed=2 ** 33 + 5, seconds=1.0,
                       trace=False, root=path, require_chip=False,
                       fault=fault, t_start=time.perf_counter())
    assert res["correct"] is (fault is None), res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert res["extra"]["compiles_in_window"] == 0
    assert {"setup_s", "tpot_p95_ms", "gen_tok_s"} <= set(res["metrics"])
    # the window is whole rounds of the plan (a round: one admission per
    # client), to the tick that holds a round's first admission, and at
    # least the seconds asked for
    clients = run.resolve(spec, CELL, path)["traffic"]["clients"]
    rounds = res["extra"]["window_rounds"]
    assert rounds >= 1
    assert abs(res["extra"]["window_admissions"] - rounds * clients) < clients
    assert res["extra"]["window_s"] >= 1.0


def test_fixed_plan_sends_the_same_sizes_for_every_seed():
    """``serve_fixed``: one plan of prompt and output lengths for every
    seed, the tokens from the seed; ``serve`` reorders the sizes."""
    from bench import traffic
    from bench.loops import serve_fixed

    t = _config("../../traffic/longdoc-24k.json")
    a, b = (serve_fixed.closed_requests(t, s, 1000, 3)
            for s in (2 ** 33 + 1, 7))
    sizes = lambda reqs: [[(len(p), m) for p, m in c] for c in reqs]  # noqa: E731,E501
    assert sizes(a) == sizes(b)
    assert not np.array_equal(a[0][0][0], b[0][0][0])
    assert sizes(a) == sizes(traffic.closed_requests(t, 0, 1000, 3))
    assert sizes(traffic.closed_requests(t, 7, 1000, 3)) != sizes(a)


def test_hybrid_control_fails_the_limit(hybrid_root):
    path, spec = hybrid_root
    nums = control.control_readings(spec, CELL, 5, 1.0, root=path,
                                    require_chip=False)
    limits = run.resolve(spec, CELL, path)["limits"]
    assert nums["logit_gap_mean"] > limits["logit_gap_mean"], nums
    assert nums["program_logit_gap_mean"] <= limits["logit_gap_mean"], nums


# --------------------------------------------------------------------------
# operation and byte counts at the published widths
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def olmo():
    return _config("../../configs/olmo-hybrid-7b-hla2-8l.json")


def test_counts_match_the_program_tree(olmo):
    """2.30 B parameters, of which the layers' projections and SwiGLU and
    the head take part in matrix products; 33.3 MB of HLA2 state and
    30,720 B of keys and values per token for one sequence."""
    from repro.distributed import steps
    from repro.models import lm
    from repro.models.param import abstract_params

    pcfg = prog.program_config(olmo)
    tree = abstract_params(steps.model_specs(pcfg))
    prog.check_tree(pcfg, tree)
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))
    dense = sum(int(np.prod(a.shape))
                for path, a in jax.tree_util.tree_leaves_with_path(tree)
                if "kernel" in jax.tree_util.keystr(path) and a.ndim == 3)
    m = ref.dims(olmo)
    assert dense == costs_hybrid.layer_params(olmo)
    assert costs_hybrid.head_params(olmo) == m["d"] * m["V"]
    assert round(total / 1e9, 2) == 2.30
    assert costs_hybrid.hla2_state_bytes(olmo) == 6 * 30 * 185_088
    assert costs_hybrid.kv_bytes_per_token(olmo) == 30_720
    states = jax.eval_shape(lambda: lm.lm_init_states(pcfg, 1, 16))
    hla = sum(int(np.prod(a.shape)) * a.dtype.itemsize
              for path, a in jax.tree_util.tree_leaves_with_path(states)
              if "pos3" not in jax.tree_util.keystr(path))
    assert hla == costs_hybrid.hla2_state_bytes(olmo)


def test_decode_step_roofline_stays_bytes_bound_at_96_192():
    """``decode_step_roofline`` counts the kernel's operations at the key
    width of its S output and the value width of its token output, and at
    the linear layers' 96 / 192 the kernel stays bound by its bytes by
    two orders of magnitude."""
    rows, d, dv = 8 * 30, 96, 192
    pk = peaks.peak("TPU v5 lite")
    outs = [("bf16", (rows, 1, dv)), ("f32", (rows, d, d)),
            ("f32", (rows, d, dv)), ("f32", (rows, d)),
            ("f32", (rows, d, dv)), ("f32", (rows, d))]
    counted = readers.decode_flops(outs, {})
    assert counted == costs.decode_step_flops(rows, d, dv)
    byts = readers.decode_step_bytes(outs, [])
    assert byts > 2 * rows * (d * d + 2 * d * dv + 2 * d) * 4
    t_bytes = byts / pk["bytes_per_s"]
    assert t_bytes / (counted / pk["flops_per_s"]) > 100


def _synthetic_obs(olmo, window_s, prefills, steps_active):
    class Trace:
        window_ns = window_s * 1e9

    t = _config("../../traffic/longdoc-24k.json")
    return {"trace": Trace(), "cfg": olmo, "traffic": t, "chips": 1,
            "device_kind": "TPU v5 lite", "prefills": prefills,
            "decode_steps": steps_active}


def test_mfu_hybrid_reads_under_100_on_a_synthetic_window(olmo):
    """Six seconds of the cell's work as a run could trace it: two 16k
    prefills and 400 decode steps over 8 slots.  Its least time lies
    inside the window."""
    mfu_hybrid = run.load_reader(os.path.join(ROOT, "bench", "metrics",
                                              "mfu_hybrid.py"))
    obs = _synthetic_obs(olmo, 6.0, [16384, 16384], [8] * 400)
    share = mfu_hybrid(obs)
    assert 0.0 < share < 100.0
    least = costs_hybrid.serve_least_seconds(
        olmo, peaks.peak("TPU v5 lite"), [16384, 16384], [8] * 400, 8192)
    assert share == pytest.approx(100.0 * least / 6.0)
    # a decode step over 8 slots reads 3.83 GB of weights, 2.01 GB of KV
    # at 8,192 positions each and 0.53 GB of HLA2 state: 7.8 ms at HBM
    # speed, bound by its bytes
    one = costs_hybrid.serve_least_seconds(
        olmo, peaks.peak("TPU v5 lite"), [], [8], 8192)
    assert one == pytest.approx(
        costs_hybrid.decode_bytes(olmo, 8, 8192) / 819e9)
    assert 7.5e-3 < one < 8.1e-3
    assert mfu_hybrid(dict(obs, trace=None)) is None
    assert mfu_hybrid(dict(obs, prefills=[], decode_steps=[])) is None

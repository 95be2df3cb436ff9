"""Summarize benchmarks/results/*.json into §Dry-run / §Roofline tables.

    PYTHONPATH=src python -m benchmarks.report [--md EXPERIMENTS_tables.md]

MODEL_FLOPS convention: train = 6*N*D (N = active params for MoE, D =
tokens), prefill = 2*N*D, decode = 2*N*B (one token per sequence);
all divided by device count for the per-device ratio against the
loop-aware HLO FLOPs.
"""

from __future__ import annotations

import argparse
import glob
import json
import os

RESULTS = os.path.join(os.path.dirname(__file__), "results")

_SUGGEST = {
    "compute_s": "increase arithmetic intensity (bigger chunk width / fuse "
    "HLA terms in the Pallas kernel); compute-bound is the goal state",
    "memory_s": "cut HBM traffic: bf16 residuals, larger fusion regions, "
    "avoid fp32 round-trips of gathered weights",
    "collective_s": "reduce gather/reduce volume: bf16 FSDP gathers, fewer "
    "microbatches, reuse gathered weights across fwd/bwd, overlap via LHS",
}


def _active_params(cfg):
    """Total and active (MoE top-k) parameter counts from the spec tree."""
    from repro.distributed.steps import model_specs
    from repro.models.param import _leaf_paths
    import numpy as np

    specs = model_specs(cfg)
    total = active = 0
    for path, sp in _leaf_paths(specs):
        n = int(np.prod(sp.shape))
        total += n
        if sp.axes and sp.axes[0] == "layers" and len(sp.axes) > 1 and \
                sp.axes[1] == "experts":
            frac = cfg.moe.top_k / cfg.moe.n_experts
            active += int(n * frac)
        elif "experts" in (sp.axes or ()):
            frac = cfg.moe.top_k / cfg.moe.n_experts
            active += int(n * frac)
        else:
            active += n
    return total, active


def model_flops(arch, shape_name, mixer, devices):
    from repro.configs import get_config
    from repro.models.config import get_shape

    cfg = get_config(arch, mixer=None if mixer == "rwkv6" else mixer)
    shape = get_shape(shape_name)
    total, active = _active_params(cfg)
    B, n = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        f = 6.0 * active * B * n
    elif shape.kind == "prefill":
        f = 2.0 * active * B * n
    else:
        f = 2.0 * active * B  # one token per sequence
    return f / devices, total, active


def load_results():
    rows = []
    for p in sorted(glob.glob(os.path.join(RESULTS, "*.json"))):
        with open(p) as f:
            r = json.load(f)
        if r.get("ok"):
            rows.append(r)
    return rows


def render_train_step():
    """§Train-step table from results/train_step.json (benchmarks.run)."""
    path = os.path.join(RESULTS, "train_step.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    sh = r["shape"]
    out = [
        "\n### §Train-step — fwd+bwd per step "
        f"(backend={r['backend']}, B={sh['B']} H={sh['H']} n={sh['n']} "
        f"d={sh['d']} chunk={sh['chunk']})\n",
        "| path | us/step | tok/s |",
        "|---|---|---|",
    ]
    for name, e in r["entries"].items():
        out.append(f"| {name} | {e['us_per_step']:.1f} | {e['tok_per_s']} |")
    ent = r["entries"]
    if "hla2_fused" in ent and "hla2_recompute" in ent:
        sp = ent["hla2_recompute"]["us_per_step"] / max(
            ent["hla2_fused"]["us_per_step"], 1e-9
        )
        out.append(
            f"\nhla2 fused-bwd speedup over recompute-in-backward: "
            f"**{sp:.2f}x** (interpret-mode numbers on CPU are not "
            "indicative — compare on TPU)"
        )
    return "\n".join(out)


def render_serving():
    """§Serving-trace table from results/serving.json (benchmarks.run
    bench_serving): the multi-tenant Zipf-prefix / Poisson-arrival trace
    over the cached engine — sustained req/s, TTFT cold vs hit, cache
    hit rate."""
    path = os.path.join(RESULTS, "serving.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    if "req_per_s" not in r:
        return None  # pre-trace artifact (older bench schema): re-run
    sh = r["shape"]
    return "\n".join([
        "\n### §Serving-trace — multi-tenant Zipf-prefix trace "
        f"(backend={r['backend']}, slots={sh['slots']} "
        f"prefix={sh['prefix_len']} gen={sh['gen_len']} "
        f"block={sh['block']} chunk={sh['granularity']} "
        f"requests={sh['requests']} over {sh['prefixes']} "
        "shared prefixes)\n",
        "| metric | value |",
        "|---|---|",
        f"| sustained throughput | {r['req_per_s']:.1f} req/s |",
        f"| TTFT cold p50 / p99 | {r['ttft_cold_ms_p50']:.1f} / "
        f"{r['ttft_cold_ms_p99']:.1f} ms |",
        f"| TTFT hit p50 / p99 | {r['ttft_hit_ms_p50']:.1f} / "
        f"{r['ttft_hit_ms_p99']:.1f} ms |",
        f"| cache hit rate | {100 * r['cache_hit_rate']:.0f}% "
        f"({r['cache_hits']} hits / {r['cache_misses']} misses) |",
        f"| steady-state decode | {r['decode_tok_per_s']:.1f} tok/s |",
        f"| prefill throughput | {r['prefill_tok_per_s']:.1f} tok/s |",
        "\n(hit-path TTFT resumes the shared prefix from one O(1) "
        "state snapshot and prefills only the suffix — the gap vs cold "
        "p50 is the cache's whole value; interpret-mode numbers on CPU "
        "are not indicative — compare on TPU.)",
    ])


def render_spec():
    """§Speculative table from results/spec.json (benchmarks.run
    bench_spec): n-gram-drafted speculative decode vs plain block decode
    on the trained repetitive-text workload."""
    path = os.path.join(RESULTS, "spec.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    sh = r["shape"]
    out = [
        "\n### §Speculative — draft/verify/rollback vs plain decode "
        f"(backend={r['backend']}, {sh['model']}, {sh['workload']}, "
        f"slots={sh['slots']} gen={sh['gen_len']} "
        f"drafter={sh['drafter']})\n",
        f"plain block decode baseline: **{r['plain_tok_per_s']:.1f} "
        "tok/s**\n",
        "| k | tok/s | speedup | acceptance | rounds | rollback rounds |",
        "|---|---|---|---|---|---|",
    ]
    for e in r["entries"]:
        out.append(
            f"| {e['k']} | {e['tok_per_s']} | {e['speedup']}x | "
            f"{e['acceptance']} | {e['rounds']} | {e['rollback_rounds']} |"
        )
    out.append(
        "\n(speculative greedy output is asserted token-for-token equal "
        "to plain greedy; interpret-mode numbers on CPU are not "
        "indicative — compare on TPU.)"
    )
    return "\n".join(out)


def render_ops():
    """§Operator table from results/ops.json (benchmarks.run bench_ops):
    train-fwd and decode tok/s for every registered SequenceOp on the
    same reduced backbone — the registry-dispatch perf trajectory."""
    path = os.path.join(RESULTS, "ops.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    sh = r["shape"]
    out = [
        "\n### §Operator — per-SequenceOp throughput "
        f"(backend={r['backend']}, {sh['arch']}, B={sh['B']} n={sh['n']} "
        f"decode_steps={sh['decode_steps']})\n",
        "| op | train-fwd tok/s | decode tok/s | streaming | fused "
        "kernels | spec-decodable |",
        "|---|---|---|---|---|---|",
    ]
    for name, e in sorted(r["entries"].items()):
        flag = lambda b: "yes" if b else "no"  # noqa: E731
        out.append(
            f"| {name} | {e['train_fwd_tok_per_s']} | "
            f"{e['decode_tok_per_s']} | {flag(e['streaming'])} | "
            f"{flag(e['has_fused_kernels'])} | "
            f"{flag(e['spec_decodable'])} |"
        )
    out.append(
        "\n(all ops run the identical backbone through the SequenceOp "
        "registry — differences are the operators themselves plus any "
        "dispatch overhead; interpret-mode numbers on CPU are not "
        "indicative — compare on TPU.)"
    )
    return "\n".join(out)


def render_utilization():
    """§Utilization from results/ops.json (benchmarks.run bench_ops):
    achieved-vs-roofline for every registered SequenceOp — measured
    tok/s x analytic whole-model FLOPs/token (repro.obs.costs) against
    the device peak (repro.obs.perf.device_peak)."""
    path = os.path.join(RESULTS, "ops.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    peak = r.get("peak")
    if peak is None:  # off the chip: utilization not measured
        return None
    sh = r["shape"]
    out = [
        "\n### §Utilization — achieved vs roofline per SequenceOp "
        f"(backend={r['backend']}, {sh['arch']}, B={sh['B']} n={sh['n']}; "
        f"peak {peak['flops_per_s']/1e9:.0f} GFLOP/s / "
        f"{peak['bytes_per_s']/1e9:.0f} GB/s, {peak['source']} "
        f"[{peak['kind']}])\n",
        "| op | train tok/s | train GFLOP/s | train util | decode tok/s "
        "| decode GFLOP/s | decode util | state bytes |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for name, e in sorted(r["entries"].items()):
        if "train_util" not in e:
            continue
        tf = e["train_fwd_tok_per_s"] * e["train_flops_per_token"] / 1e9
        df = e["decode_tok_per_s"] * e["decode_flops_per_token"] / 1e9
        out.append(
            f"| {name} | {e['train_fwd_tok_per_s']:.0f} | {tf:.2f} | "
            f"{100 * e['train_util']:.1f}% ({e['train_bound']}) | "
            f"{e['decode_tok_per_s']:.0f} | {df:.2f} | "
            f"{100 * e['decode_util']:.1f}% ({e['decode_bound']}) | "
            f"{e['state_bytes']} |"
        )
    out.append(
        "\n(utilization = achieved FLOP/s or GB/s over the binding "
        "roofline resource, against the chip's published peak. "
        "The gap to 100% on non-fused ops is the fused-kernel ROADMAP "
        "headroom.)"
    )
    return "\n".join(out)


def render_trend(history_path):
    """§Trend from a repro.obs.bench/v1 history: latest run vs the one
    before it, through the perfcheck significance rule."""
    if not history_path or not os.path.exists(history_path):
        return None
    from repro.obs.perf import read_bench
    from repro.obs.perfcheck import compare_runs

    runs = read_bench(history_path)
    if len(runs) < 2:
        return None
    prev, last = runs[-2], runs[-1]
    cmp = compare_runs(prev, last)
    out = [
        "\n### §Trend — latest bench run vs previous "
        f"({prev['env'].get('git_sha')} -> {last['env'].get('git_sha')}, "
        f"{len(cmp['compared'])} shared rows)\n",
        "| row | previous | latest | ratio | trend |",
        "|---|---|---|---|---|",
    ]
    for c in sorted(cmp["compared"], key=lambda c: c["name"]):
        trend = ("**regressed**" if c["regressed"]
                 else "improved" if c["improved"] else "~")
        out.append(
            f"| {c['name']} | {c['old']:.4g} {c['unit']} | "
            f"{c['new']:.4g} {c['unit']} | x{c['ratio']:.2f} | {trend} |"
        )
    for name in cmp["only_new"]:
        out.append(f"| {name} | — | new | | |")
    out.append(
        "\n(trend = the perfcheck significance rule: a move must clear "
        "both the relative tolerance and the noise allowance from both "
        "runs' IQRs; `~` is within noise.)"
    )
    return "\n".join(out)


def render_distributed():
    """§Distributed table from results/distributed.json (benchmarks.run
    bench_distributed): per-device train tok/s, 1 -> 8 host devices."""
    path = os.path.join(RESULTS, "distributed.json")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        r = json.load(f)
    sh = r["shape"]
    out = [
        "\n### §Distributed — sharded train step scaling "
        f"({sh['arch']}, B={sh['B']} n={sh['n']}, host-device mesh)\n",
        "| devices | steps/s | tok/s | tok/s/device |",
        "|---|---|---|---|",
    ]
    for e in r["entries"]:
        out.append(
            f"| {e['devices']} | {e['steps_per_s']} | {e['tok_per_s']} | "
            f"{e['tok_per_s_per_device']} |"
        )
    ents = {e["devices"]: e for e in r["entries"]}
    if 1 in ents and 8 in ents:
        eff = ents[8]["tok_per_s"] / max(ents[1]["tok_per_s"], 1e-9) / 8
        out.append(
            f"\n8-device scaling efficiency: **{100 * eff:.0f}%** (host "
            "devices share one CPU, so this tracks sharding/collective "
            "overhead, not real speedup — compare on TPU)"
        )
    return "\n".join(out)


def render(rows):
    out = []
    out.append("### §Dry-run — compile results (every arch x shape x mesh)\n")
    out.append(
        "| cell | mesh | mixer | mb | compile (s) | peak GiB/dev | "
        "AG / AR / A2A / CP (count) |"
    )
    out.append("|---|---|---|---|---|---|---|")
    for r in rows:
        cc = r["collectives"]["counts"]
        mesh = "x".join(str(v) for v in r["mesh"].values())
        out.append(
            f"| {r['arch']} x {r['shape']} | {mesh} | {r['mixer']} | "
            f"{r.get('microbatches', 1)} | {r['compile_s']} | "
            f"{r['memory']['peak_bytes']/2**30:.2f} | "
            f"{cc['all-gather']} / {cc['all-reduce']} / {cc['all-to-all']} / "
            f"{cc['collective-permute']} |"
        )

    out.append("\n### §Roofline — per-device terms (single-pod 16x16)\n")
    out.append(
        "| cell | compute (s) | memory (s) | collective (s) | bottleneck | "
        "MODEL_FLOPS/HLO_FLOPs | next lever |"
    )
    out.append("|---|---|---|---|---|---|---|")
    for r in rows:
        if "pod" in r["mesh"]:
            continue  # roofline table is single-pod per the assignment
        rf = r["roofline"]
        try:
            mf, total, active = model_flops(
                r["arch"], r["shape"], r["mixer"], r["devices"]
            )
            ratio = f"{mf / max(r['cost']['flops'], 1):.2f}"
        except Exception:
            ratio = "n/a"
        out.append(
            f"| {r['arch']} x {r['shape']} | {rf['compute_s']:.2f} | "
            f"{rf['memory_s']:.2f} | {rf['collective_s']:.2f} | "
            f"{rf['bottleneck'].replace('_s','')} | {ratio} | "
            f"{_SUGGEST[rf['bottleneck']][:60]}... |"
        )
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--md", default=None)
    ap.add_argument("--history", default=os.path.join(RESULTS,
                                                      "history.jsonl"),
                    help="repro.obs.bench/v1 history for the §Trend "
                         "section (default results/history.jsonl)")
    args = ap.parse_args()
    rows = load_results()
    text = render(rows)
    for section in (
        render_train_step(),
        render_serving(),
        render_spec(),
        render_ops(),
        render_utilization(),
        render_trend(args.history),
        render_distributed(),
    ):
        if section:
            text = text + "\n" + section
    print(text)
    if args.md:
        with open(args.md, "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main()

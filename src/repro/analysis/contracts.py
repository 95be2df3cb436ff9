"""Lowered-HLO trace contracts: what the compiled program must look like.

The AST linter (``repro.analysis.checks``) verifies the *source*; this
module verifies the *artifact*.  The four hot entry points — train step,
prefill, decode block, speculative round — are lowered and compiled on
CPU for a small config (**never executed**: every argument is a
``ShapeDtypeStruct``) and the optimized HLO is checked, via the
loop-aware parser in :mod:`repro.analysis.hlo_analysis`, against the
contracts the paper's efficiency claims rest on:

* **no-f64** — no op computes in or produces ``f64``: a silent float64
  upcast doubles state bytes and halves the roofline;
* **donation** — every buffer the entry point declares donated is
  actually aliased by XLA (``input_output_alias``): a dropped donation
  means a second copy of params/opt-state/decode-state lives through
  the step;
* **no-host-transfers** — no infeed/outfeed/send/recv or host-callback
  custom-calls inside the step: the engine's one-sync-per-block
  discipline (RPR004) is meaningless if the compiled program phones
  home mid-step;
* **bounded-collectives** — at most ``max_collectives`` collective ops
  (0 for the single-device contract config);
* **stable-HLO** (recompilation hazard) — prompt lengths that pad to
  the same chunk bucket must produce byte-identical normalized HLO:
  if shape-identical inputs ever lower differently, every admission
  risks a recompile.

CLI: ``python -m repro.analysis.contracts [--arch hla-1b] [--json]``.
Exit 1 on any violated contract.  The tier-1 pytest wiring lives in
``tests/test_contracts.py``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from .hlo_analysis import analyze, parse_hlo

# --------------------------------------------------------------------------
# HLO-level predicates
# --------------------------------------------------------------------------

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\((\d+),\s*\{[\d,\s]*\},\s*(?:may|must)-alias\)"
)

# custom-call targets that move data to/from the host mid-program
_HOST_CALL_MARKERS = ("callback", "xla_python", "host")
_TRANSFER_KINDS = ("infeed", "outfeed", "send", "recv",
                   "send-done", "recv-done")


def f64_ops(hlo_text: str) -> List[str]:
    """Names of ops whose output or operands are f64."""
    comps, _ = parse_hlo(hlo_text)
    out = []
    for comp in comps.values():
        for op in comp.ops:
            if "f64[" in op.out_shapes or "f64[" in op.rhs:
                out.append(f"{comp.name}/{op.name} = {op.kind}")
    return out


def host_transfer_ops(hlo_text: str) -> List[str]:
    """Names of host-transfer ops (infeed/outfeed/send/recv/callbacks)."""
    comps, _ = parse_hlo(hlo_text)
    out = []
    for comp in comps.values():
        for op in comp.ops:
            if op.kind in _TRANSFER_KINDS:
                out.append(f"{comp.name}/{op.name} = {op.kind}")
            elif op.kind == "custom-call" and any(
                m in op.rhs for m in _HOST_CALL_MARKERS
            ):
                out.append(f"{comp.name}/{op.name} = {op.rhs[:80]}")
    return out


def donated_aliases(hlo_text: str) -> Dict[int, str]:
    """``input_output_alias`` of the compiled module:
    parameter number -> output tuple index (as text)."""
    m = re.search(r"input_output_alias=\{(.*?)\}(?:,\s*[a-z_]+=|\s*$)",
                  hlo_text)
    if not m:
        return {}
    return {
        int(param): out_idx
        for out_idx, param, in (
            e[:2] for e in _ALIAS_ENTRY_RE.findall("{" + m.group(1) + "}")
        )
    }


# source-location debug info in compiled HLO text: the FileNames /
# FunctionNames / FileLocations / StackFrames tables (header lines and their
# numbered rows) and each instruction's ``metadata={...}``.  It records
# where the caller traced from, not what the program computes.
_DEBUG_TABLE_RE = re.compile(
    r"^(FileNames|FunctionNames|FileLocations|StackFrames)$|^\d+ "
)
_METADATA_RE = re.compile(r",? ?metadata=\{[^{}]*\}")


def hlo_fingerprint(hlo_text: str) -> str:
    """sha256 of the HLO with comments and source-location debug info
    stripped — two lowerings are "the same program" iff their
    fingerprints match."""
    lines = [
        _METADATA_RE.sub("", ln.rstrip()) for ln in hlo_text.splitlines()
        if ln.strip() and not ln.strip().startswith("//")
        and not _DEBUG_TABLE_RE.match(ln)
    ]
    return hashlib.sha256("\n".join(lines).encode()).hexdigest()


# --------------------------------------------------------------------------
# contract evaluation
# --------------------------------------------------------------------------


@dataclasses.dataclass
class ContractReport:
    """One entry point's verdict.  ``violations`` empty means the
    compiled artifact honors every contract."""

    name: str
    violations: List[str]
    n_aliased: int
    collective_total: int
    fingerprint: str

    @property
    def ok(self) -> bool:
        return not self.violations

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["ok"] = self.ok
        return d


def check_hlo(
    name: str,
    hlo_text: str,
    *,
    expected_donations: int = 0,
    max_collectives: int = 0,
) -> ContractReport:
    """Evaluate every per-module contract on one compiled HLO text."""
    violations: List[str] = []
    bad_f64 = f64_ops(hlo_text)
    if bad_f64:
        violations.append(
            f"f64 ops in compiled program ({len(bad_f64)}): "
            + "; ".join(bad_f64[:5])
        )
    transfers = host_transfer_ops(hlo_text)
    if transfers:
        violations.append(
            f"host transfers inside the step ({len(transfers)}): "
            + "; ".join(transfers[:5])
        )
    aliases = donated_aliases(hlo_text)
    if len(aliases) != expected_donations:
        violations.append(
            f"donation contract: {expected_donations} buffer(s) declared "
            f"donated but {len(aliases)} aliased by XLA — a dropped "
            "donation keeps a dead copy live through the step"
        )
    stats = analyze(hlo_text)
    total_coll = sum(stats["collective_counts"].values())
    if total_coll > max_collectives:
        violations.append(
            f"collective count {total_coll} exceeds bound "
            f"{max_collectives}: {stats['collective_counts']}"
        )
    return ContractReport(
        name=name,
        violations=violations,
        n_aliased=len(aliases),
        collective_total=total_coll,
        fingerprint=hlo_fingerprint(hlo_text),
    )


def lower_compiled_text(fn, args, *, donate_argnums=()) -> str:
    """Compile ``fn`` on abstract args (no execution) -> optimized HLO.

    ``lowered.as_text()`` would be StableHLO MLIR, which parse_hlo cannot
    read — the contracts run on the *compiled* module, which is also the
    only place ``input_output_alias`` exists.
    """
    jitted = jax.jit(fn, donate_argnums=donate_argnums)
    return jitted.lower(*args).compile().as_text()


def pad_to_bucket(n: int, chunk: int) -> int:
    """The serving admission bucket: lengths are padded up to a chunk
    multiple, so only the bucket — never the raw length — may key a
    compilation."""
    return max(chunk, -(-n // chunk) * chunk)


# --------------------------------------------------------------------------
# the four hot entry points, as abstract-arg factories
# --------------------------------------------------------------------------


def default_config():
    """Small CPU-lowerable config: reduced hla-1b with a small chunk so
    the padded-length set stays cheap to compile."""
    from ..configs import get_config

    cfg = get_config("hla-1b", reduced=True)
    return cfg.replace(hla=dataclasses.replace(cfg.hla, chunk=16))


def _abstract_params(cfg):
    from ..distributed import steps as steps_mod
    from ..models.param import abstract_params

    return abstract_params(steps_mod.model_specs(cfg))


def _abstract_opt_state(cfg, params_abs):
    from ..optim import adamw

    md = jnp.dtype(getattr(cfg, "moment_dtype", "float32"))
    mom = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, md), params_abs
    )
    return adamw.OptState(
        step=jax.ShapeDtypeStruct((), jnp.int32),
        mu=mom,
        nu=jax.tree.map(lambda a: a, mom),
    )


def _abstract_states(cfg, slots: int, max_len: int):
    from ..models import lm

    return jax.eval_shape(lambda: lm.lm_init_states(cfg, slots, max_len))


def _n_leaves(tree) -> int:
    return len(jax.tree.leaves(tree))


def _key_struct():
    return jax.eval_shape(lambda: jax.random.PRNGKey(0))


def train_step_hlo(cfg, *, batch: int = 2, seq: int = 32
                   ) -> Tuple[str, int]:
    """Train step with (params, opt_state) donated.

    Returns (compiled HLO, number of donated leaves)."""
    from ..distributed import steps as steps_mod
    from ..optim import adamw

    step = steps_mod.make_train_step(cfg, adamw.OptConfig())
    params = _abstract_params(cfg)
    opt_state = _abstract_opt_state(cfg, params)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((batch, seq), jnp.int32),
    }
    hlo = lower_compiled_text(
        step, (params, opt_state, batch_abs), donate_argnums=(0, 1)
    )
    return hlo, _n_leaves(params) + _n_leaves(opt_state)


def prefill_hlo(cfg, *, batch: int = 2, prompt_len: int = 32) -> str:
    """Admission prefill.  Declares NO donations (the prompt batch and
    params are both reused), so the contract asserts an empty alias map."""
    from ..distributed import steps as steps_mod

    step = steps_mod.make_prefill_step(cfg)
    params = _abstract_params(cfg)
    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((batch, prompt_len), jnp.int32),
    }
    return lower_compiled_text(step, (params, batch_abs))


def make_decode_block(cfg, scfg, n_steps: int):
    """The contract mirror of ``Engine._decode_block``: a lax.scan of
    single-token ``lm_apply`` decode steps with on-device sampling.
    Kept structurally minimal — the contract is about what XLA does to
    a scan-of-decode-steps, not about engine bookkeeping."""
    from ..models import lm
    from ..serving.sampling import sample

    def decode_block(params, states, tokens, positions, active, key):
        def body(carry, _):
            states, tok, pos, key = carry
            logits, states, _ = lm.lm_apply(
                params, tok, cfg, states=states, positions=pos,
                mode="decode",
            )
            key, sub = jax.random.split(key)
            nxt = sample(logits[:, -1], sub, scfg)
            tok = jnp.where(active[:, None], nxt[:, None], tok)
            pos = pos + active[:, None].astype(pos.dtype)
            return (states, tok, pos, key), nxt

        (states, tok, pos, _), toks = jax.lax.scan(
            body, (states, tokens, positions, key), length=n_steps
        )
        return states, tok, pos, toks

    return decode_block


def decode_block_hlo(cfg, *, slots: int = 2, block: int = 4,
                     max_len: int = 64) -> Tuple[str, int]:
    """Decode block with (states, tokens, positions) donated — the
    in-place state update the O(1)-state claim depends on."""
    from ..serving.sampling import SamplingConfig

    fn = make_decode_block(cfg, SamplingConfig(), block)
    states = _abstract_states(cfg, slots, max_len)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    hlo = lower_compiled_text(
        fn,
        (_abstract_params(cfg), states, tokens, positions, active,
         _key_struct()),
        donate_argnums=(1, 2, 3),
    )
    return hlo, _n_leaves(states) + 2


def spec_round_hlo(cfg, *, slots: int = 2, k: int = 4,
                   max_len: int = 64) -> Tuple[str, int]:
    """Speculative round (verify + commit) with decode state donated."""
    from ..serving.sampling import SamplingConfig
    from ..serving.spec.verify import make_spec_round

    fn = make_spec_round(cfg, SamplingConfig())
    states = _abstract_states(cfg, slots, max_len)
    tokens = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    positions = jax.ShapeDtypeStruct((slots, 1), jnp.int32)
    active = jax.ShapeDtypeStruct((slots,), jnp.bool_)
    drafts = jax.ShapeDtypeStruct((slots, k), jnp.int32)
    hlo = lower_compiled_text(
        fn,
        (_abstract_params(cfg), states, tokens, positions, active,
         drafts, _key_struct()),
        donate_argnums=(1, 2, 3),
    )
    return hlo, _n_leaves(states) + 2


# --------------------------------------------------------------------------
# the full contract run
# --------------------------------------------------------------------------


def check_entry_points(
    cfg=None,
    *,
    max_collectives: int = 0,
    prompt_lengths: Sequence[int] = (5, 11, 16),
) -> List[ContractReport]:
    """Lower all four entry points and evaluate every contract.

    ``prompt_lengths`` drives the recompilation-hazard check: all
    lengths padding to the same chunk bucket must fingerprint
    identically (the default set pads to one 16-bucket)."""
    if cfg is None:
        cfg = default_config()
    reports: List[ContractReport] = []

    hlo, n_don = train_step_hlo(cfg)
    reports.append(check_hlo(
        "train_step", hlo, expected_donations=n_don,
        max_collectives=max_collectives,
    ))

    hlo = prefill_hlo(cfg, prompt_len=pad_to_bucket(
        prompt_lengths[0], cfg.hla.chunk
    ))
    prefill_report = check_hlo(
        "prefill", hlo, expected_donations=0,
        max_collectives=max_collectives,
    )

    # recompilation hazard: same bucket -> byte-identical program
    by_bucket: Dict[int, Dict[int, str]] = {}
    for n in prompt_lengths:
        bucket = pad_to_bucket(n, cfg.hla.chunk)
        fp = hlo_fingerprint(prefill_hlo(cfg, prompt_len=bucket))
        by_bucket.setdefault(bucket, {})[n] = fp
    for bucket, fps in sorted(by_bucket.items()):
        if len(set(fps.values())) > 1:
            prefill_report.violations.append(
                f"recompilation hazard: prompt lengths {sorted(fps)} all "
                f"pad to bucket {bucket} but lower to "
                f"{len(set(fps.values()))} distinct programs"
            )
    reports.append(prefill_report)

    hlo, n_don = decode_block_hlo(cfg)
    reports.append(check_hlo(
        "decode_block", hlo, expected_donations=n_don,
        max_collectives=max_collectives,
    ))

    hlo, n_don = spec_round_hlo(cfg)
    reports.append(check_hlo(
        "spec_round", hlo, expected_donations=n_don,
        max_collectives=max_collectives,
    ))
    return reports


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import json as _json

    p = argparse.ArgumentParser(
        prog="python -m repro.analysis.contracts",
        description="Lower-only HLO trace contracts for the four hot "
                    "entry points (CPU, no execution).",
    )
    p.add_argument("--arch", default=None,
                   help="config name (default: reduced hla-1b)")
    p.add_argument("--max-collectives", type=int, default=0)
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)

    cfg = None
    if args.arch:
        from ..configs import get_config

        cfg = get_config(args.arch, reduced=True)
        cfg = cfg.replace(hla=dataclasses.replace(cfg.hla, chunk=16))
    reports = check_entry_points(cfg, max_collectives=args.max_collectives)
    if args.json:
        print(_json.dumps(
            {"schema": "repro.contracts/v1",
             "reports": [r.to_dict() for r in reports]}, indent=2,
        ))
    else:
        for r in reports:
            status = "ok" if r.ok else "VIOLATED"
            print(f"{r.name:14s} {status}  aliased={r.n_aliased} "
                  f"collectives={r.collective_total} "
                  f"fp={r.fingerprint[:12]}")
            for v in r.violations:
                print(f"    - {v}")
    return 0 if all(r.ok for r in reports) else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

"""Lowered-HLO trace contracts (repro.analysis.contracts).

Two halves:

* toy-program tests that PLANT each violation (an f64 upcast, a dropped
  donation, a host callback) and assert the contract catches it — the
  detector itself is under test;
* the real thing: all four hot entry points (train step, prefill,
  decode block, spec round) lowered on CPU for the small contract
  config, asserting no-f64 + donation + no-host-transfers +
  zero-collectives + stable-HLO-across-the-padded-length-set.

Everything here is lower-only: no entry point is ever executed.  The
conftest enables x64 for the fp64 test oracles, so the real entry
points lower under ``jax.enable_x64(False)`` — exactly the
default runtime configuration the contracts describe.
"""

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.contracts import (
    check_entry_points,
    check_hlo,
    donated_aliases,
    f64_ops,
    hlo_fingerprint,
    host_transfer_ops,
    lower_compiled_text,
    pad_to_bucket,
    prefill_hlo,
    default_config,
)

# --------------------------------------------------------------------------
# planted violations: the detectors must catch what they claim to
# --------------------------------------------------------------------------


def test_planted_f64_is_caught():
    def bad(x):
        # a silent upcast: the exact bug the no-f64 contract exists for
        return (x.astype(jnp.float64) * 2.0).sum()

    hlo = lower_compiled_text(
        bad, (jax.ShapeDtypeStruct((8,), jnp.float32),)
    )
    assert f64_ops(hlo)
    report = check_hlo("planted_f64", hlo)
    assert not report.ok
    assert any("f64" in v for v in report.violations)


def test_planted_dropped_donation_is_caught():
    def shrink(s):
        # output shape != donated input shape: XLA cannot alias it
        return s[:4] * 1.0

    with pytest.warns(UserWarning, match="donated buffers"):
        hlo = lower_compiled_text(
            shrink, (jax.ShapeDtypeStruct((8,), jnp.float32),),
            donate_argnums=(0,),
        )
    assert donated_aliases(hlo) == {}
    report = check_hlo("planted_drop", hlo, expected_donations=1)
    assert not report.ok
    assert any("donation" in v for v in report.violations)


def test_honored_donation_passes():
    def step(s, x):
        return s + x, (x * x).sum()

    hlo = lower_compiled_text(
        step,
        (jax.ShapeDtypeStruct((8, 4), jnp.float32),
         jax.ShapeDtypeStruct((8, 4), jnp.float32)),
        donate_argnums=(0,),
    )
    assert len(donated_aliases(hlo)) == 1
    assert check_hlo("ok_donation", hlo, expected_donations=1).ok


def test_host_transfer_detection_on_synthetic_hlo():
    # detector-level check on a handcrafted module: outfeed + a host
    # callback custom-call are both transfers, a gemm custom-call is not
    hlo = """\
HloModule synthetic, entry_computation_layout={(f32[4]{0})->f32[4]{0}}

ENTRY %main (p0: f32[4]) -> f32[4] {
  %p0 = f32[4]{0} parameter(0)
  %cc = f32[4]{0} custom-call(%p0), custom_call_target="xla_python_cpu_callback"
  %of = token[] outfeed(%p0)
  ROOT %r = f32[4]{0} custom-call(%cc), custom_call_target="__onednn$matmul"
}
"""
    found = host_transfer_ops(hlo)
    assert len(found) == 2
    assert not check_hlo("synthetic", hlo).ok


def test_alias_parsing_multiple_entries():
    hlo = ("HloModule m, input_output_alias={ {0}: (0, {}, may-alias), "
           "{1}: (2, {}, must-alias) }, "
           "entry_computation_layout={()->()}\n")
    assert donated_aliases(hlo) == {0: "0", 2: "1"}


def test_fingerprint_ignores_comments_only():
    a = "ENTRY %m {\n  %x = f32[4] parameter(0)\n}"
    b = "// a comment\nENTRY %m {\n  %x = f32[4] parameter(0)\n}"
    c = "ENTRY %m {\n  %x = f32[8] parameter(0)\n}"
    assert hlo_fingerprint(a) == hlo_fingerprint(b)
    assert hlo_fingerprint(a) != hlo_fingerprint(c)


def test_pad_to_bucket():
    assert pad_to_bucket(1, 16) == 16
    assert pad_to_bucket(16, 16) == 16
    assert pad_to_bucket(17, 16) == 32


# --------------------------------------------------------------------------
# the four hot entry points (lower-only, small config)
# --------------------------------------------------------------------------


@pytest.fixture(scope="module")
def entry_reports():
    with jax.enable_x64(False):
        return {r.name: r for r in check_entry_points()}


def test_all_four_entry_points_covered(entry_reports):
    assert sorted(entry_reports) == [
        "decode_block", "prefill", "spec_round", "train_step",
    ]


@pytest.mark.parametrize(
    "name", ["train_step", "prefill", "decode_block", "spec_round"]
)
def test_entry_point_contracts_hold(entry_reports, name):
    r = entry_reports[name]
    assert r.ok, f"{name} violated: {r.violations}"
    assert r.collective_total == 0  # single-device contract config


def test_donations_actually_alias(entry_reports):
    # train step donates params+opt_state; decode/spec donate the decode
    # state (+ tokens/positions); prefill donates nothing
    assert entry_reports["train_step"].n_aliased > 0
    assert entry_reports["decode_block"].n_aliased > 0
    assert entry_reports["spec_round"].n_aliased > 0
    assert entry_reports["prefill"].n_aliased == 0


def test_same_bucket_lowers_identically():
    # the recompilation-hazard detector's core claim, asserted directly:
    # two prefills at the same padded length are byte-identical programs
    cfg = default_config()
    with jax.enable_x64(False):
        n = pad_to_bucket(5, cfg.hla.chunk)
        fp1 = hlo_fingerprint(prefill_hlo(cfg, prompt_len=n))
        fp2 = hlo_fingerprint(prefill_hlo(cfg, prompt_len=n))
        fp_other = hlo_fingerprint(
            prefill_hlo(cfg, prompt_len=2 * cfg.hla.chunk)
        )
    assert fp1 == fp2
    assert fp1 != fp_other  # different bucket really is a new program

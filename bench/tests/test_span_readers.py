"""Readers of the engine's host spans, on hand-built trace summaries, and
the operation labels of kernels named by ``pallas_call(name=...)``."""

import os

import pytest

from bench import readers, run, trace_reduce as tr

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _read(family, summ):
    return run.load_reader(os.path.join(METRICS, family + ".py"))(
        {"trace": summ})


def _summ(host_spans, idle_gaps, busy_window_ns=2e9):
    return tr.TraceSummary(
        window_ns=busy_window_ns, busy_window_ns=busy_window_ns,
        busy_ns=0.5 * busy_window_ns, n_devices=1, kernels=[],
        collective_ns=0.0, collective_exposed_ns=0.0, top_ops=[],
        idle_gaps=idle_gaps, host_spans=host_spans, longest_gaps=[])


ENGINE = {
    "engine.tick": [100e6, 300e6],
    "engine.admit": [60e6, 90e6, 50e6],
    "engine.cache_lookup": [10e6, 20e6],
    "engine.cache_insert": [30e6],
    "engine.prefill": [40e6, 60e6, 40e6],
}
GAPS = [("engine.cache_insert", 0.2), ("no host span", 0.01),
        ("engine.cache_lookup", 0.1), ("engine.prefill", 0.05)]


def test_cache_host_ms_is_cache_time_per_admission():
    assert _read("cache_host_ms", _summ(ENGINE, GAPS)) == pytest.approx(
        (10 + 20 + 30) / 3)


def test_cache_idle_share_counts_both_cache_spans():
    assert _read("cache_idle_share", _summ(ENGINE, GAPS)) == pytest.approx(
        100 * 0.3 / 2)


def test_admit_tick_share_is_admission_time_over_tick_time():
    assert _read("admit_tick_share", _summ(ENGINE, GAPS)) == pytest.approx(
        100 * 200 / 400)


def test_unattributed_idle_share_reads_no_host_span():
    assert _read("unattributed_idle_share", _summ(ENGINE, GAPS)) == \
        pytest.approx(100 * 0.01 / 2)


def test_unattributed_idle_share_absent_from_a_short_list_is_zero():
    gaps = [g for g in GAPS if g[0] != "no host span"]
    assert _read("unattributed_idle_share", _summ(ENGINE, gaps)) == 0.0


def test_unattributed_idle_share_absent_from_a_full_list_is_unknown():
    gaps = [(f"engine.s{i}", 0.1 - 0.001 * i) for i in range(10)]
    assert _read("unattributed_idle_share", _summ(ENGINE, gaps)) is None


@pytest.mark.parametrize("family", ["cache_host_ms", "cache_idle_share",
                                    "admit_tick_share"])
def test_a_program_without_the_spans_reads_nothing(family):
    """A program whose engine spans are only ``engine.prefill`` and
    ``engine.decode_block``: the readers find nothing and raise nothing."""
    old = {"engine.prefill": [40e6], "engine.decode_block": [170e6]}
    gaps = [("no host span", 1.2), ("engine.prefill", 0.9)]
    assert _read(family, _summ(old, gaps)) is None
    assert run.load_reader(os.path.join(METRICS, family + ".py"))(
        {"trace": None}) is None


# A decode-step kernel's operation as the TPU trace names it, with the
# name ``pallas_call`` gave it in place of the name stack's (``checkpoint``).
DECODE_OP = (
    "%{name} = (bf16[512,1,128]{{2,1,0:T(2,128)(2,1)}}, "
    "f32[512,128,128]{{2,1,0:T(8,128)}}, f32[512,128,128]{{2,1,0:T(8,128)}}, "
    "f32[512,1,128]{{2,1,0:T(1,128)}}, f32[512,128,128]{{2,1,0:T(8,128)}}, "
    "/*index=5*/f32[512,1,128]{{2,1,0:T(1,128)}}) custom-call("
    "f32[512,1,1]{{2,1,0:T(1,128)}} %p0, bf16[512,1,128]{{2,1,0}} %p1, "
    "bf16[512,1,128]{{2,1,0}} %p2, bf16[512,1,128]{{2,1,0}} %p3, "
    "f32[512,128,128]{{2,1,0}} %p4, f32[512,128,128]{{2,1,0}} %p5, "
    "f32[512,1,128]{{2,1,0}} %p6, f32[512,128,128]{{2,1,0}} %p7, "
    "f32[512,1,128]{{2,1,0}} %p8), custom_call_target=\"tpu_custom_call\", "
    "frontend_attributes={{kernel_metadata={{}}}}")


def test_a_named_kernel_keeps_its_label_shapes_and_match():
    old, new = (DECODE_OP.format(name=n)
                for n in ("checkpoint.4", "hla2_decode_step.1"))
    assert tr.op_label(new) == \
        "%hla2_decode_step.1 tpu_custom_call bf16[512,1,128] x6"
    assert tr.op_label(old).split(" ", 1)[1] == tr.op_label(new).split(
        " ", 1)[1]
    assert tr.output_shapes(new) == tr.output_shapes(old)
    assert tr.operand_shapes(new) == tr.operand_shapes(old)
    assert len(tr.operand_shapes(new)) == 9
    assert tr.KERNEL in new
    assert readers.is_decode_step(tr.output_shapes(new))
    assert not readers.is_hla2_prefill(tr.output_shapes(new))

"""Readers of the engine's host spans, on hand-built trace summaries, the
operation labels of kernels named by ``pallas_call(name=...)``, and the
decode step's count per row."""

import os

import pytest

from bench import costs, readers, run, trace_reduce as tr

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


def _decode_call(rows, dk, dv, stack=None):
    """HLO text of a decode-step call over ``rows`` heads at key width
    ``dk`` and value width ``dv``.  With ``stack``, the call updates one
    layer of a state stack of ``stack`` rows in place: its state operands
    and outputs are the whole stack, and the layer index comes first."""
    n = stack or rows
    states = [f"f32[{n},{dk},{dk}]", f"f32[{n},{dk},{dv}]", f"f32[{n},1,{dk}]",
              f"f32[{n},{dk},{dv}]", f"f32[{n},1,{dk}]"]
    ins = ([f"s32[1]{{0}} %layer"] if stack else []) + [
        f"f32[{rows},1,1]{{2,1,0}} %gamma", f"bf16[{rows},1,{dk}]{{2,1,0}} %q",
        f"bf16[{rows},1,{dk}]{{2,1,0}} %k", f"bf16[{rows},1,{dv}]{{2,1,0}} %v",
    ] + [f"{s}{{2,1,0}} %s{i}" for i, s in enumerate(states)]
    outs = [f"bf16[{rows},1,{dv}]{{2,1,0}}"] + [s + "{2,1,0}" for s in states]
    return (f"%hla2_decode_step.4 = ({', '.join(outs)}) custom-call("
            f"{', '.join(ins)}), custom_call_target=\"tpu_custom_call\"")


def _decode_share(text, seconds):
    summ = _summ({}, [])
    summ.kernels = [tr.Event(text, 0.0, seconds * 1e9)]
    return run.load_reader(os.path.join(METRICS, "decode_step_roofline.py"))(
        {"trace": summ, "device_kind": "TPU v5 lite", "cfg": {}})


@pytest.mark.parametrize("rows,dk,dv", [(512, 128, 128), (240, 96, 192)])
@pytest.mark.parametrize("stack", [None, 24], ids=["per-layer", "in-stack"])
def test_decode_step_counts_bytes_and_operations_per_row(rows, dk, dv, stack):
    """A decode step's count is its rows' work: a call that updates one
    layer of a stack of states in place reads the bytes, operations and
    roofline share of a per-layer call over the same rows and time, and
    those bytes are the per-layer call's operands plus outputs."""
    per_layer = _decode_call(rows, dk, dv)
    text = _decode_call(rows, dk, dv, stack and stack * rows)
    outs, ins = tr.output_shapes(text), tr.operand_shapes(text)
    assert readers.is_decode_step(outs)
    assert len(ins) == (10 if stack else 9)
    old = tr.nbytes(tr.output_shapes(per_layer)) + tr.nbytes(
        tr.operand_shapes(per_layer))
    assert readers.decode_step_bytes(outs, ins) == old
    if (rows, dk, dv) == (512, 128, 128):
        assert old == 202_901_504
    assert readers.decode_flops(outs, {}) == costs.decode_step_flops(
        rows, dk, dv)
    seconds = 306e-6
    assert _decode_share(text, seconds) == _decode_share(per_layer, seconds) \
        == pytest.approx(100 * old / 819e9 / seconds)


def test_the_real_decode_call_reads_its_operands_and_outputs():
    """The trace's own text of a per-layer call at 512 rows: the count per
    row is its operands read once and its outputs written once."""
    text = DECODE_OP.format(name="hla2_decode_step.4")
    outs, ins = tr.output_shapes(text), tr.operand_shapes(text)
    assert readers.decode_step_bytes(outs, ins) == \
        readers.operand_bytes(outs, ins) == 202_901_504

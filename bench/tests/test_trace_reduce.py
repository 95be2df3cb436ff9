"""Trace reduction on a small trace recorded on one TPU v5 lite: two
rounds of an HLA2 prefill kernel and a matmul inside host spans
``engine.decode_block``, with a 2 ms sleep (``bench.idle``) between."""

import os

import pytest

from bench import readers, trace_reduce as tr

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "small.xplane.pb")


@pytest.fixture(scope="module")
def summ():
    return tr.reduce_trace(TRACE, window="engine.decode_block",
                           attribute=["bench."])


def test_busy_idle_and_window(summ):
    assert summ.n_devices == 1
    assert 0 < summ.busy_ns < summ.window_ns
    assert 0.99 < summ.idle_share < 1.0  # a few microseconds of work
    assert summ.collective_ns == 0.0 == summ.collective_exposed_ns


def test_kernels_and_their_shapes(summ):
    assert len(summ.kernels) >= 1
    k = summ.kernels[0]
    outs = tr.output_shapes(k.name)
    assert outs[0] == ("bf16", (2, 256, 128)) and len(outs) == 6
    assert readers.is_hla2_prefill(outs)
    ins = tr.operand_shapes(k.name)
    assert ins[0] == ("f32", (2, 1, 1)) and len(ins) == 4
    assert tr.nbytes(ins) == 2 * 4 + 3 * 2 * 256 * 128 * 2


def test_gaps_are_attributed_to_the_covering_host_span(summ):
    gaps = dict(summ.idle_gaps)
    assert set(gaps) <= {"bench.idle", "no host span"}
    assert summ.host_spans["bench.idle"]
    assert summ.busy_window_ns <= summ.window_ns
    for offset, seconds, who in summ.longest_gaps:
        assert 0 <= offset and 0 < seconds and who in gaps
    labels = [name for name, _ in summ.top_ops]
    assert any("tpu_custom_call" in name for name in labels)


def test_kernel_roofline_reads_the_trace(summ):
    obs = {"trace": summ, "device_kind": "TPU v5 lite",
           "cfg": {"chunk": 128}}
    share = readers.kernel_roofline(obs, readers.is_hla2_prefill,
                                    readers.hla2_fwd_flops)
    assert 0 < share < 100
    assert readers.kernel_roofline(obs, readers.is_decode_step,
                                   readers.decode_flops) is None
    assert readers.idle_share({"trace": summ}) == pytest.approx(
        100 * summ.idle_share)


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert tr.clip([(0, 3), (5, 6)], 1, 5.5) == [(1, 3), (5, 5.5)]
    assert tr.measure([(0, 3), (5, 6)]) == 4
    # collective (0, 10) with compute over (2, 4) and (6, 7): 7 exposed
    assert tr.subtract([(0, 10)], [(2, 4), (6, 7)]) == 7
    assert tr.subtract([(0, 1), (3, 4)], [(0, 4)]) == 0


def test_collectives_and_containers_are_told_apart():
    assert tr.COLLECTIVE.search("%ar = f32[4] all-reduce-start(f32[4] %x)")
    assert not tr.COLLECTIVE.search("%f = f32[4] fusion(f32[4] %x)")
    assert tr.CONTAINER.search("%while.6 = (s32[]) while((s32[]) %t)")
    assert not tr.CONTAINER.search("%k = f32[2] custom-call(f32[2] %x)")

"""The yardstick's counts against hand counts, and the table of peaks."""

import json
import os

import pytest

from bench import costs, peaks, readers

HERE = os.path.dirname(os.path.abspath(__file__))


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("cpu")
    with pytest.raises(KeyError):
        peaks.peak("")


def test_v5e_peaks():
    for kind in ("TPU v5 lite", "TPU v5e"):
        p = peaks.peak(kind)
        assert p == {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def test_hla2_chunk_by_hand():
    # w=4, d=3, dv=2: 4 products w*w*d, one w^3, two w*d*d, two d*d*dv,
    # three w*d*dv, three w*w*dv, 2 operations per multiply-add
    by_hand = 2 * (4 * 48 + 64 + 2 * 36 + 2 * 18 + 3 * 24 + 3 * 32)
    assert costs.hla2_chunk_flops(4, 3, 2) == by_hand == 1064


def test_hla2_prefill_and_bwd_shapes():
    # 2 rows of 12 tokens in chunks of 4: six chunks
    assert costs.hla2_fwd_flops(2, 12, 4, 3, 2) == 6 * 1064
    assert costs.hla2_bwd_flops(2, 12, 4, 3, 2) == 12 * 1064


def test_decode_step_by_hand():
    # 3 rows, d=4, dv=2: 4*16 + 10*8 = 144 per row
    assert costs.decode_step_flops(3, 4, 2) == 432


def test_model_counts_of_hla_1b_8l():
    with open(os.path.join(HERE, "..", "configs",
                           "sheared-llama-1.3b-hla2-8l.json")) as f:
        c = json.load(f)
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5504
    assert costs.matmul_params(c) == 8 * per_layer + 2048 * 32000
    mixer = 16 * 15 * 2 * 128 ** 3 / 128  # per token per layer
    assert costs.train_flops_per_token(c) == pytest.approx(
        6 * costs.matmul_params(c) + 3 * 8 * mixer)
    assert costs.state_bytes(c) == 8 * 3_162_112


def test_least_time_takes_the_larger_bound():
    c = {"n_layers": 1, "d_model": 4, "n_heads": 1, "head_dim": 4,
         "d_ff": 4, "vocab": 4, "chunk": 4}
    pk = {"flops_per_s": 1.0, "bytes_per_s": 1e30}
    t = costs.serve_least_seconds(c, pk, [8], [2])
    assert t == pytest.approx(8 * costs.prefill_flops_per_token(c)
                              + 2 * costs.decode_flops_per_token(c))


def test_kernel_classes_by_output_shapes():
    fwd = [("bf16", (32, 4096, 128)), ("f32", (32, 128, 128)),
           ("f32", (32, 32, 128, 128))]
    bwd = [("bf16", (32, 4096, 128))] * 3 + [("f32", (32, 1, 1))]
    pre = [("bf16", (16, 640, 128)), ("f32", (16, 128, 128)),
           ("f32", (16, 128, 128)), ("f32", (16, 1, 128)),
           ("f32", (16, 128, 128)), ("f32", (16, 1, 128))]
    dec = [("bf16", (512, 1, 128))] + pre[1:]
    table = {"fwd": readers.is_hla2_train_fwd, "bwd": readers.is_hla2_bwd,
             "pre": readers.is_hla2_prefill, "dec": readers.is_decode_step}
    for name, outs in (("fwd", fwd), ("bwd", bwd), ("pre", pre),
                       ("dec", dec)):
        hits = [k for k, f in table.items() if f(outs)]
        assert hits == [name]


# rows, tokens, key and value widths of the hybrid's linear layers
R, N, DK, DV = 30, 512, 96, 192
STATES = [("f32", (R, DK, DK)), ("f32", (R, DK, DV)), ("f32", (R, 1, DK)),
          ("f32", (R, DK, DV)), ("f32", (R, 1, DK))]


@pytest.mark.parametrize("outs,match,read,want", [
    pytest.param([("bf16", (R, N, DV))] + STATES, readers.is_hla2_prefill,
                 readers.hla2_fwd_flops, costs.hla2_fwd_flops, id="prefill"),
    pytest.param([("bf16", (R, N, DV))] + STATES + [("f32", (R, 4, DK, DK))],
                 readers.is_hla2_train_fwd, readers.hla2_fwd_flops,
                 costs.hla2_fwd_flops, id="train-fwd"),
    pytest.param([("bf16", (R, N, DK))] * 2 + [("bf16", (R, N, DV)),
                                               ("f32", (R, 1, 1))],
                 readers.is_hla2_bwd, readers.hla2_bwd_flops,
                 costs.hla2_bwd_flops, id="bwd"),
])
def test_chunk_kernels_count_key_and_value_widths(outs, match, read, want):
    """At key width 96 and value width 192 each chunk kernel counts its
    operations at both widths, read from its output shapes."""
    assert match(outs)
    assert read(outs, {"chunk": 128}) == want(R, N, 128, DK, DV)

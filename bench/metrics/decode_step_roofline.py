"""Roofline share of the fused decode-step kernel over its traced calls
(bounded by the bytes of the states it reads and writes, counted per row
of the call)."""

from bench import readers


def read(obs):
    return readers.kernel_roofline(obs, readers.is_decode_step,
                                   readers.decode_flops,
                                   readers.decode_step_bytes)

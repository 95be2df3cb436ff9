"""Roofline share of the HLA2 chunk kernel's backward, over its traced
calls (twice the forward's operations)."""

from bench import readers


def read(obs):
    return readers.kernel_roofline(obs, readers.is_hla2_bwd,
                                   readers.hla2_bwd_flops)

"""Roofline share of the HLA2 chunk kernel as prefill calls it (resumed
from a cached state), over its traced calls."""

from bench import readers


def read(obs):
    return readers.kernel_roofline(obs, readers.is_hla2_prefill,
                                   readers.hla2_fwd_flops)

"""Roofline share of the HLA2 chunk kernel's training forward (the
variant that saves per-chunk states), over its traced calls."""

from bench import readers


def read(obs):
    return readers.kernel_roofline(obs, readers.is_hla2_train_fwd,
                                   readers.hla2_fwd_flops)

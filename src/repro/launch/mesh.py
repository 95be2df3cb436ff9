"""Mesh construction.  Functions only — importing this module never touches
jax device state (required by the dry-run contract)."""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """The target deployment mesh: 16x16 chips per pod, 2 pods multi-pod.

    DP over ("pod", "data"), TP over "model".  Requires 256 / 512 devices
    (real chips, or host placeholders via
    XLA_FLAGS=--xla_force_host_platform_device_count=...).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def _auto_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: the model shards through logical
    ``constrain`` calls that GSPMD propagates, which Explicit axes (the
    JAX >= 0.7 default) reject at the first unannotated gather."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(shape)
    )


def make_mesh(shape=None, axes=None):
    """General mesh helper (tests / small runs).

    Defaults: all available devices on a ("data", "model") mesh with the
    model axis as large as possible up to 4 (elastic-friendly: recomputed
    from whatever devices exist at launch).
    """
    n = len(jax.devices())
    if shape is None:
        model = 1
        for cand in (4, 2, 1):
            if n % cand == 0:
                model = cand
                break
        shape = (n // model, model)
        axes = ("data", "model")
    return _auto_mesh(shape, axes)


def mesh_summary(mesh) -> str:
    return f"mesh{dict(zip(mesh.axis_names, mesh.devices.shape))}"


# XLA flags recommended for the real-TPU launch (documented here; the
# launcher exports them).  Collective/compute overlap knobs:
TPU_XLA_FLAGS = " ".join(
    [
        "--xla_enable_async_collective_permute=true",
        "--xla_tpu_enable_async_collective_fusion=true",
        "--xla_tpu_enable_async_collective_fusion_fuse_all_gather=true",
        "--xla_tpu_overlap_compute_collective_tc=true",
        "--xla_tpu_data_parallel_opt_different_sized_ops=true",
    ]
)

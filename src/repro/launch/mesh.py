"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state — required because the dry-run
must set ``XLA_FLAGS=--xla_force_host_platform_device_count=512`` (and
``JAX_PLATFORMS=cpu``) *before* jax initializes, while smoke tests and
benchmarks must see 1 device.

Meshes (TPU v5e pods, 256 chips each):

  * single-pod: (16, 16) = (data, model)          — 256 chips
  * multi-pod:  (2, 16, 16) = (pod, data, model)  — 512 chips

Axis roles (dist/sharding.py): batch over (pod, data); TP/EP over model;
FSDP weight sharding over data.  Growing to 1000+ nodes = growing ``pod``
(pure DP, only gradient all-reduce crosses pods) and/or ``data`` — a shape
change here, no model or rules change.
"""

from __future__ import annotations

from repro.dist import make_mesh

__all__ = ["make_production_mesh", "HW"]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


class HW:
    """TPU v5e roofline constants (per chip)."""

    PEAK_FLOPS_BF16 = 197e12        # FLOP/s
    HBM_BW = 819e9                  # bytes/s
    ICI_BW = 50e9                   # bytes/s per link
    HBM_BYTES = 16 * 1024**3        # 16 GiB HBM per chip

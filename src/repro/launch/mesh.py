"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before the first jax call).

* single pod: (16, 16) = 256 chips, axes ("data", "model")
* multi-pod:  (2, 16, 16) = 512 chips, axes ("pod", "data", "model")

The "pod" axis is pure DP by default (batch shards over ("pod", "data"));
the compressed-gradient path (optim.compress) and pipeline configs target it
explicitly because inter-pod links are the slow tier.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``.

    JAX's own default is Explicit axes, under which a gather, scatter or
    contraction over a sharded operand must name its output sharding.  The
    serving, training and app code here leaves that to the compiler, so
    every mesh in the repo is built through this helper."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_debug_mesh(n_data: int = 4, n_model: int = 2):
    """Small mesh for multi-device CPU tests."""
    return make_mesh((n_data, n_model), ("data", "model"))


# TPU v5e hardware model (roofline constants, per chip)
PEAK_FLOPS = 197e12          # bf16 FLOP/s
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s per link

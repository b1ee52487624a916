"""Pallas TPU kernels for the compute hot-spots.

Each kernel ships three layers: ``<name>.py`` (pl.pallas_call + BlockSpec),
``ops.py`` (jitted public wrapper, interpret=True on CPU), ``ref.py``
(pure-jnp oracle used by the allclose tests).
"""
from repro.kernels import ops, ref

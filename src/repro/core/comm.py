"""Pluggable communication object — the JAX analogue of the paper's
``send``/``recv``/``all_gather`` *function arguments*.

The paper passes MPI primitives INTO its generic functions so the transport is
swappable (pypar vs mpi4py vs ...).  Inside a single JAX SPMD program the
transport is a set of named-axis collectives; we preserve the paper's design by
bundling axis-bound collective closures into a :class:`Comm` value that generic
functions take as an argument.  A :class:`SerialComm` implements the same
interface for single-process execution, so user code is transport-agnostic,
exactly as in the paper.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Comm:
    """Axis-bound collectives, usable inside ``shard_map``/``pmap`` bodies.

    ``axis`` may be a single axis name or a tuple of names (collectives then
    operate over the product of those mesh axes).
    """

    axis: Any  # str | tuple[str, ...]

    # -- topology ----------------------------------------------------------
    def rank(self) -> jax.Array:
        """Paper's ``my_rank``."""
        return jax.lax.axis_index(self.axis)

    def size(self) -> int:
        """Paper's ``num_procs`` (static)."""
        if isinstance(self.axis, (tuple, list)):
            import math
            return int(math.prod(jax.lax.axis_size(a) for a in self.axis))
        return int(jax.lax.axis_size(self.axis))

    # -- collectives --------------------------------------------------------
    def all_gather(self, x, *, axis: int = 0, tiled: bool = False):
        """``axis`` selects where shards land (tiled: concat dim; untiled:
        the inserted stack dim) — e.g. ``axis=-1, tiled=True`` reassembles a
        vocab-sharded logits row, the serving TP head's single gather."""
        return jax.lax.all_gather(x, self.axis, axis=axis, tiled=tiled)

    def all_reduce_sum(self, x):
        return jax.lax.psum(x, self.axis)

    def all_reduce_max(self, x):
        return jax.lax.pmax(x, self.axis)

    def all_reduce_min(self, x):
        return jax.lax.pmin(x, self.axis)

    def all_to_all(self, x, *, split_axis: int, concat_axis: int, tiled: bool = True):
        """Paper's ``redistribute_work`` exchange as one collective: rank r
        keeps chunk r of ``split_axis`` and receives everyone else's,
        stacked along ``concat_axis``.  This is the MoE expert-parallel
        dispatch/combine primitive (``moe_apply_expert_parallel``): the
        (E, C, d) capacity buffer splits over experts going out and over
        source ranks coming back.  SerialComm's twin is the identity, so
        the same block runs unchanged on one device."""
        return jax.lax.all_to_all(x, self.axis, split_axis=split_axis,
                                  concat_axis=concat_axis, tiled=tiled)

    def shift(self, x, offset: int = 1):
        """Ring point-to-point: every rank sends to ``rank+offset`` (mod n).

        This is the SPMD replacement for the paper's ``send``/``recv`` pair —
        point-to-point transfers must be expressed as a permutation so the
        compiler can schedule them on the ICI torus.
        """
        n = self.size()
        perm = [(i, (i + offset) % n) for i in range(n)]
        return jax.lax.ppermute(x, self.axis, perm)

    def permute(self, x, perm: Sequence[tuple[int, int]]):
        return jax.lax.ppermute(x, self.axis, perm)

    def broadcast_from(self, x, root: int = 0):
        """Paper's ``pypar.broadcast``: value from ``root`` to all ranks."""
        picked = jnp.where(self.rank() == root, x, jnp.zeros_like(x))
        return jax.lax.psum(picked, self.axis)

    def pvary(self, x):
        """Mark a replicated value as device-varying (vma bookkeeping)."""
        return jax.lax.pcast(x, self.axis, to="varying")


class SerialComm:
    """Single-process Comm with identical interface (paper's serial path)."""

    axis = None

    def rank(self):
        return jnp.asarray(0)

    def size(self):
        return 1

    def all_gather(self, x, *, axis: int = 0, tiled: bool = False):
        return x if tiled else jnp.expand_dims(x, axis)

    def all_reduce_sum(self, x):
        return x

    def all_reduce_max(self, x):
        return x

    def all_reduce_min(self, x):
        return x

    def all_to_all(self, x, *, split_axis: int, concat_axis: int, tiled: bool = True):
        return x

    def shift(self, x, offset: int = 1):
        return x

    def permute(self, x, perm):
        return x

    def broadcast_from(self, x, root: int = 0):
        return x

    def pvary(self, x):
        return x


def make_comm(axis) -> Comm | SerialComm:
    """Factory: ``axis=None`` gives the serial transport."""
    return SerialComm() if axis is None else Comm(axis)

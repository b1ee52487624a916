"""Minimal functional param-definition system.

Models declare parameters as trees of :class:`Param` (shape + logical
PartitionSpec + initializer).  From one declaration we derive:

* concrete initialization (``init_params``) — jitted, with on-device sharding;
* abstract ``ShapeDtypeStruct`` trees with shardings for the dry-run
  (``abstract_params``) — no allocation ever happens for the 480B configs;
* the sharding tree (``sharding_tree``) used as ``in_shardings`` for
  ``train_step``/``serve_step``;
* the dtypes a server holds the weights in (``hold_params``): a leaf whose
  every read casts it to the compute dtype is held in that dtype.

This mirrors the paper's philosophy: the *declaration* is user code, the
*distribution* (partitioning, placement) is generic machinery.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.mesh.axes import AxisRules, logical_to_sharding


@dataclasses.dataclass(frozen=True)
class Param:
    """Declaration of one parameter tensor."""

    shape: tuple
    spec: P                       # logical axes, same length as shape
    init: str = "normal"          # normal | zeros | ones | embed | small
    scale: float | None = None    # stddev override
    dtype: Any = None             # override model dtype
    # every read of the leaf casts it to the compute dtype first (the
    # model's ``dtype``), so a server may hold it in that dtype
    read_as_compute: bool = False

    def stddev(self) -> float:
        if self.scale is not None:
            return self.scale
        fan_in = self.shape[-2] if len(self.shape) >= 2 else self.shape[-1]
        return 1.0 / math.sqrt(max(fan_in, 1))


def is_param(x) -> bool:
    return isinstance(x, Param)


def _tree_map(fn, defs):
    return jax.tree_util.tree_map(fn, defs, is_leaf=is_param)


def init_params(defs, key, dtype=jnp.float32):
    """Materialize parameters (host/device per surrounding jit)."""
    leaves, treedef = jax.tree_util.tree_flatten(defs, is_leaf=is_param)
    keys = jax.random.split(key, len(leaves))

    def make(p: Param, k):
        dt = p.dtype or dtype
        if p.init == "zeros":
            return jnp.zeros(p.shape, dt)
        if p.init == "ones":
            return jnp.ones(p.shape, dt)
        std = p.stddev() if p.init != "embed" else 1.0
        if p.init == "small":
            std = 0.02
        return (jax.random.normal(k, p.shape, jnp.float32) * std).astype(dt)

    return jax.tree_util.tree_unflatten(
        treedef, [make(p, k) for p, k in zip(leaves, keys)])


def abstract_params(defs, mesh: Mesh, rules: AxisRules, dtype=jnp.float32):
    """ShapeDtypeStruct tree with shardings — dry-run stand-in, no allocation."""
    def make(p: Param):
        dt = p.dtype or dtype
        return jax.ShapeDtypeStruct(
            p.shape, dt, sharding=logical_to_sharding(p.spec, mesh, rules))

    return _tree_map(make, defs)


def sharding_tree(defs, mesh: Mesh, rules: AxisRules):
    return _tree_map(lambda p: logical_to_sharding(p.spec, mesh, rules), defs)


def spec_tree(defs):
    return _tree_map(lambda p: p.spec, defs)


def param_count(defs) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_param)
    return int(sum(int(np.prod(p.shape)) for p in leaves))


def param_bytes(defs, dtype=jnp.float32) -> int:
    leaves = jax.tree_util.tree_leaves(defs, is_leaf=is_param)
    return int(sum(int(np.prod(p.shape)) * jnp.dtype(p.dtype or dtype).itemsize
                   for p in leaves))


def tree_bytes(tree) -> int:
    """Bytes of the arrays (or shapes) of ``tree``."""
    return sum(math.prod(a.shape) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(tree))


def hold_params(defs, params, dtype):
    """``(held, bytes_cast)``: ``params`` as a server holds them.  A float
    leaf declared ``read_as_compute`` and stored wider than the compute
    ``dtype`` is held in ``dtype``: every read rounds it to that anyway, so
    the programs compute the same values.  Every other leaf (norm scales,
    routers, anything read in float32) is held as stored.  The casts run in
    one jitted call (a tree of shapes stays shapes, on the same shardings);
    the caller's arrays are not donated.  ``bytes_cast`` is the bytes of
    the leaves held in ``dtype``."""
    dtype = jnp.dtype(dtype)
    leaves, treedef = jax.tree_util.tree_flatten(params)
    cast = [i for i, (p, a) in enumerate(zip(treedef.flatten_up_to(defs),
                                             leaves))
            if p.read_as_compute and jnp.issubdtype(a.dtype, jnp.floating)
            and jnp.dtype(a.dtype).itemsize > dtype.itemsize]
    if not cast:
        return params, 0
    if all(isinstance(leaves[i], jax.ShapeDtypeStruct) for i in cast):
        new = [leaves[i].update(dtype=dtype) for i in cast]
    else:
        new = jax.jit(lambda xs: [x.astype(dtype) for x in xs])(
            [leaves[i] for i in cast])
    for i, a in zip(cast, new):
        leaves[i] = a
    return jax.tree_util.tree_unflatten(treedef, leaves), tree_bytes(new)

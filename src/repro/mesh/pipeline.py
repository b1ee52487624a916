"""Pipeline parallelism, function-centric: the user supplies ``stage_fn``
(one pipeline stage's computation — the paper's ``subdomain_solve`` role);
this module supplies the generic schedule and the stage-boundary transfer
(the paper's ``communicate``: a neighbour ``ppermute``, exactly the additive
Schwarz ghost-exchange pattern applied to the layer dimension).

GPipe schedule over a mesh axis ``axis`` with S stages and M microbatches:
the classic loop runs T = M + S - 1 ticks; at tick t, stage s processes
microbatch t - s.  Implemented SPMD-style inside ``shard_map``: every stage
executes every tick (TPUs are lock-stepped anyway); activations advance one
stage per tick via ``ppermute``; outputs are collected from the last stage.
Bubble fraction = (S-1)/T, reported by :func:`bubble_fraction`.

This is deliberately the *forward* pipeline primitive (inference / activation
pipelining across pods); it composes with the rest of the stack as a user
function and is exercised by tests + the multi-pod dry-run flag rather than
being welded into every model.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import Comm


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)


def pipeline_apply(stage_fn: Callable, params_stacked, x_micro, mesh,
                   *, axis: str = "pod"):
    """Run a GPipe forward pipeline over ``axis``.

    Args:
      stage_fn: (stage_params, h) -> h — one stage's computation (a user
        function; e.g. a block of transformer layers).
      params_stacked: pytree whose leaves have a leading (n_stages,) axis,
        sharded over ``axis`` (each device row holds its stage's params).
      x_micro: (n_micro, micro_batch, ...) microbatched input (replicated
        over ``axis``).
      mesh: the device mesh containing ``axis``.

    Returns (n_micro, micro_batch, ...) outputs of the LAST stage.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_micro.shape[0]
    ticks = n_micro + n_stages - 1

    p_specs = jax.tree_util.tree_map(lambda _: P(axis), params_stacked)

    def body(params_local, x_all):
        comm = Comm(axis)
        stage = jax.lax.axis_index(axis)
        sp = jax.tree_util.tree_map(lambda a: a[0], params_local)
        micro_shape = x_all.shape[1:]

        def tick(carry, t):
            h_in, outs = carry
            # stage 0 injects microbatch t (if still in range)
            mb = jnp.take(x_all, jnp.clip(t, 0, n_micro - 1), axis=0)
            h = jnp.where(stage == 0, mb, h_in)
            h = stage_fn(sp, h)
            # last stage emits microbatch t - (n_stages - 1)
            out_idx = t - (n_stages - 1)
            valid = jnp.logical_and(out_idx >= 0, stage == n_stages - 1)
            outs = jax.lax.cond(
                out_idx >= 0,
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(
                    jnp.where(valid, h, o[jnp.maximum(out_idx, 0)])),
                lambda o: o, outs)
            # advance the pipe: stage s -> s+1 (ring; wraparound ignored)
            h_next = comm.shift(h, offset=1)
            return (h_next, outs), None

        h0 = jnp.zeros(micro_shape, x_all.dtype)
        outs0 = jnp.zeros((n_micro,) + micro_shape, x_all.dtype)
        (_, outs), _ = jax.lax.scan(tick, (h0, outs0), jnp.arange(ticks))
        # every device holds `outs`, but only the last stage's is real:
        # broadcast it (replicated output spec needs agreement)
        outs = comm.broadcast_from(outs, root=n_stages - 1)
        return outs

    return jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(p_specs, P()),
        out_specs=P(),
        check_vma=False))(params_stacked, x_micro)


def reference_apply(stage_fn: Callable, params_stacked, x_micro):
    """Oracle: run the stages sequentially on one device."""
    n_stages = jax.tree_util.tree_leaves(params_stacked)[0].shape[0]

    def one(h):
        for s in range(n_stages):
            sp = jax.tree_util.tree_map(lambda a: a[s], params_stacked)
            h = stage_fn(sp, h)
        return h

    return jax.vmap(one)(x_micro)

"""Diffusion Monte Carlo (paper §4.2 / Appendix B) — the dynamic-population
application with load balancing.

Physics: N non-interacting bosons in a 3D harmonic trap,
H = -(1/2)∇² + (1/2) r² (ħ=m=ω=1).  Ground state energy E0 = 3/2 per
particle — the assertion target of the tests/benchmark.

Walkers diffuse with step N(0, sqrt(tau)) (D = 1/2) and branch with

    G_B = exp(-((V(R) + V(R'))/2 - E_T) tau),   marker = floor(G_B + u)

TPU adaptation of the paper's ``class Walkers``: the
population lives in a fixed-capacity array with a live ``count``; delete/clone
(the paper's ``delete``/``append``) are realized as a prefix-sum *compaction*
— the static-shape equivalent of list surgery.  E_T population control is the
paper's ``finalize_timestep``.

Two drivers:
* :func:`run_serial` — the paper's ``time_integration`` with a Walkers class.
* :func:`make_parallel_step` — SPMD step for ``shard_map``: each shard owns a
  sub-population; :func:`repro.core.load_balance.dynamic_load_balancing`
  (count-driven) re-balances shards exactly like the paper's
  ``redistribute_work`` moved walkers between MPI ranks.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

from repro.core.comm import Comm, SerialComm, make_comm
from repro.core.load_balance import dynamic_load_balancing
from repro.core.runtime import Executor, make_executor
from repro.core.time_integration import time_integration


def potential(pos):
    """V(r) = r^2 / 2 per walker.  pos: (cap, 3)."""
    return 0.5 * jnp.sum(pos * pos, axis=-1)


# ---------------------------------------------------------------------------
# Pure-array population step (shared by serial class and SPMD step)
# ---------------------------------------------------------------------------

def walker_step(key, pos, count, e_trial, *, tau: float, max_clone: int = 2):
    """One DMC step on a fixed-capacity population.

    pos: (cap, 3); count: live prefix length; e_trial: current E_T.
    Returns (new_pos, new_count, obs) with obs = dict of estimators.
    """
    cap = pos.shape[0]
    k_move, k_branch = jax.random.split(key)
    alive = jnp.arange(cap) < count

    # -- diffusion ----------------------------------------------------------
    xi = jax.random.normal(k_move, pos.shape) * jnp.sqrt(tau)
    new_pos = pos + xi
    v_old = potential(pos)
    v_new = potential(new_pos)

    # -- branching ----------------------------------------------------------
    gb = jnp.exp(-((v_old + v_new) / 2.0 - e_trial) * tau)
    u = jax.random.uniform(k_branch, (cap,))
    marker = jnp.floor(gb + u).astype(jnp.int32)
    marker = jnp.clip(marker, 0, max_clone)
    marker = jnp.where(alive, marker, 0)

    # -- compaction (delete + clone in one scatter) --------------------------
    # new slot s takes the walker r(s) with offsets[r] <= s < offsets[r+1]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32),
                               jnp.cumsum(marker)])            # (cap+1,)
    new_count = jnp.minimum(offsets[-1], cap)
    s = jnp.arange(cap, dtype=jnp.int32)
    r = jnp.clip(jnp.searchsorted(offsets, s, side="right") - 1, 0, cap - 1)
    valid = s < new_count
    out_pos = jnp.where(valid[:, None], new_pos[r], 0.0)

    # -- observables ---------------------------------------------------------
    w = jnp.where(alive, 1.0, 0.0)
    pot_mean = jnp.sum(v_new * w) / jnp.maximum(count, 1)
    obs = {"pot": pot_mean, "count_before": count, "count_after": new_count}
    return out_pos, new_count.astype(jnp.int32), obs


def adjust_e_trial(e_trial, old_count, new_count, target, *, tau: float,
                   kappa: float = 0.1):
    """Population control (paper's ``finalize_timestep``): growth estimator
    plus a weak pull towards the target population."""
    growth = -jnp.log(jnp.maximum(new_count, 1).astype(jnp.float32)
                      / jnp.maximum(old_count, 1)) / tau
    pull = kappa * jnp.log(target / jnp.maximum(new_count, 1)
                           .astype(jnp.float32))
    return e_trial + tau * growth + pull


# ---------------------------------------------------------------------------
# Paper-faithful serial driver (class Walkers + time_integration)
# ---------------------------------------------------------------------------

class Walkers:
    """The paper's Walkers contract: __len__, move, get_marker, append/delete
    (fused into the compaction), sample_observables, finalize_timestep."""

    def __init__(self, n: int, capacity: int, *, tau: float = 0.01, seed=0):
        self.capacity = capacity
        self.tau = tau
        self.target = n
        key = jax.random.PRNGKey(seed)
        self.key, k0 = jax.random.split(key)
        pos = jax.random.normal(k0, (capacity, 3))
        self.pos = jnp.where((jnp.arange(capacity) < n)[:, None], pos, 0.0)
        self.count = jnp.asarray(n, jnp.int32)
        self.e_trial = jnp.asarray(1.5, jnp.float32)
        self._last_obs = None

    def __len__(self):
        return int(self.count)

    def move(self):
        self.key, k = jax.random.split(self.key)
        self.pos, self.count, self._last_obs = walker_step(
            k, self.pos, self.count, self.e_trial, tau=self.tau)

    def sample_observables(self):
        return {"e_trial": self.e_trial, **self._last_obs}

    def finalize_timestep(self, old_size, new_size):
        self.e_trial = adjust_e_trial(self.e_trial, old_size, new_size,
                                      self.target, tau=self.tau)


def run_serial(n_walkers: int = 500, timesteps: int = 400, *,
               capacity: int | None = None, tau: float = 0.01, seed: int = 0):
    """Paper §3.2 serial loop, verbatim structure."""
    capacity = capacity or 4 * n_walkers

    def initialize():
        return Walkers(n_walkers, capacity, tau=tau, seed=seed), timesteps

    def do_timestep(walkers):
        walkers.move()
        return walkers.sample_observables()

    def finalize(output):
        e = jnp.stack([o["e_trial"] for o in output])
        counts = jnp.stack([o["count_after"] for o in output])
        return {"e_trial": e, "counts": counts,
                "e0_estimate": e[len(e) // 2:].mean()}

    return time_integration(initialize, do_timestep, finalize)


def run_replicas(n_replicas: int = 4, executor: Executor | str = "thread",
                 n_walkers: int = 300, timesteps: int = 300, *,
                 tau: float = 0.02, seed: int = 0, **executor_kwargs):
    """Independent-replica DMC through the function-centric runtime.

    Each replica is one full serial DMC run with its own seed — a
    heavyweight *host* task (a separately-jitted program), exactly the
    paper's original task-farm scope.  The executor must therefore be a
    host tier (``serial`` or ``thread``); the thread farm overlaps replicas
    because the device computation releases the GIL.  ``finalize`` averages
    the per-replica energies and reports their spread (the standard
    independent-population error bar).
    """
    executor = make_executor(executor, **executor_kwargs)

    def initialize():
        return [((), {"n_walkers": n_walkers, "timesteps": timesteps,
                      "tau": tau, "seed": seed + i})
                for i in range(n_replicas)]

    def finalize(outputs):
        e0s = jnp.stack([o["e0_estimate"] for o in outputs])
        return {"e0_estimate": e0s.mean(), "e0_std": e0s.std(),
                "replicas": outputs}

    return executor.run(initialize, run_serial, finalize)


# ---------------------------------------------------------------------------
# SPMD step (shard_map body) with dynamic load balancing
# ---------------------------------------------------------------------------

def make_parallel_step(*, tau: float = 0.01, target: int,
                       threshold_factor: float = 1.1, axis: str = "data"):
    """Returns ``step(carry) -> (carry, obs)`` to run INSIDE shard_map.

    carry = (key, pos, count, e_trial); each shard owns its slice.  After the
    local move/branch, counts are rebalanced across the axis when skew exceeds
    ``threshold_factor`` — the paper's dynamic_load_balancing on the torus.
    """
    def step(carry):
        key, pos, count, e_trial = carry
        comm = make_comm(axis)
        key, k = jax.random.split(key)
        k = jax.random.fold_in(k, comm.rank())
        pos, count, obs = walker_step(k, pos, count, e_trial, tau=tau)

        pos, count, counts_all, rebalanced = dynamic_load_balancing(
            pos, count, comm, threshold_factor=threshold_factor)

        old_total = comm.all_reduce_sum(obs["count_before"])
        new_total = counts_all.sum()
        e_trial = adjust_e_trial(e_trial, old_total, new_total, target,
                                 tau=tau)
        pot_global = comm.all_reduce_sum(
            obs["pot"] * obs["count_before"]) / jnp.maximum(old_total, 1)
        obs = {"e_trial": e_trial, "count_after": new_total,
               "pot": pot_global, "rebalanced": rebalanced,
               "local_count": count}
        return (key, pos, count, e_trial), obs

    return step


def run_parallel(mesh, n_walkers: int = 512, timesteps: int = 200, *,
                 capacity_factor: int = 4, tau: float = 0.01, seed: int = 0,
                 axis: str = "data"):
    """Full SPMD DMC: one jitted scan over timesteps, population sharded over
    ``axis``, load-balanced every step."""
    from jax.sharding import PartitionSpec as P

    n_shards = mesh.shape[axis]
    cap_local = capacity_factor * n_walkers // n_shards
    step = make_parallel_step(tau=tau, target=n_walkers, axis=axis)

    def body(carry, _):
        return step(carry)

    def run(key):
        def per_shard(key):
            rank = jax.lax.axis_index(axis)
            k0 = jax.random.fold_in(key, rank)
            pos = jax.random.normal(k0, (cap_local, 3))
            n_local = n_walkers // n_shards
            pos = jnp.where((jnp.arange(cap_local) < n_local)[:, None],
                            pos, 0.0)
            carry = (key, pos, jnp.asarray(n_local, jnp.int32),
                     jnp.asarray(1.5, jnp.float32))
            carry, obs = jax.lax.scan(body, carry, None, length=timesteps)
            obs["local_count"] = obs["local_count"][:, None]    # (T, 1)
            return obs

        return jax.shard_map(
            per_shard, mesh=mesh, in_specs=P(),
            out_specs={"e_trial": P(), "count_after": P(), "pot": P(),
                       "rebalanced": P(), "local_count": P(None, axis)},
            check_vma=False,
        )(key)

    obs = jax.jit(run)(jax.random.PRNGKey(seed))
    e = obs["e_trial"]
    return {"e_trial": e, "counts": obs["count_after"],
            "local_counts": obs["local_count"],
            "rebalances": obs["rebalanced"].sum(),
            "e0_estimate": e[e.shape[0] // 2:].mean()}

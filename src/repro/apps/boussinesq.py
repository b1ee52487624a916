"""Boussinesq water waves via additive Schwarz (paper §4.3 / Appendix C).

Model (constant depth H=1, so the ∇H terms of (C.1) vanish):

    continuity (explicit):  (eta^l - eta^{l-1})/dt + ∇·((1 + a·eta)∇phi) = 0
    bernoulli  (implicit):  (phi^l - phi^{l-1})/dt + (a/2)|∇phi|² + eta^l
                            - (e/3) ∇² (phi^l - phi^{l-1})/dt = 0

Each time step therefore needs one *implicit Helmholtz solve*
``(I - c ∇²) dphi = rhs`` with ``c = e/3`` — this is the paper's KONTIT/BERIT
role, and exactly where additive Schwarz enters: the **same serial Jacobi
kernel** (:func:`jacobi_sweeps` — the "25-year-old Fortran code" stand-in,
written once with no knowledge of parallelism) is reused per subdomain, while
the generic :func:`repro.core.schwarz.additive_schwarz_iterations` supplies
the outer iteration, halo ``communicate``, and the paper's convergence test.

Domain decomposition: 1-D row blocks over a mesh axis; every local field
carries one ghost row on each side.  Physical BCs are no-flux (mirror);
the x-direction is handled inside the stencil with edge padding.

Validation: the Schwarz-parallel solution must match the single-domain serial
solve (same kernel, global Jacobi) to stencil tolerance, and mass
(sum of eta) must be conserved under no-flux BCs.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.comm import Comm, make_comm
from repro.core.runtime import (Executor, MeshExecutor, SerialExecutor,
                                make_executor)
from repro.core.schwarz import (additive_schwarz_iterations, halo_exchange,
                                simple_convergence_test)


@dataclasses.dataclass(frozen=True)
class BoussinesqParams:
    nx: int = 128
    ny: int = 128
    dx: float = 0.1
    dt: float = 0.02
    alpha: float = 0.0          # nonlinearity
    eps: float = 0.3            # dispersion
    jacobi_sweeps: int = 6      # per Schwarz iteration
    schwarz_max_iter: int = 200
    schwarz_threshold: float = 1e-10

    @property
    def c(self) -> float:
        return self.eps / 3.0


# ---------------------------------------------------------------------------
# "Legacy serial kernel": pure stencils on a ghost-padded block.
# Knows nothing about meshes or communication (the paper's F77 role).
# ---------------------------------------------------------------------------

def _pad_x(f):
    """Mirror-pad the x (last) axis: no-flux east/west walls."""
    return jnp.pad(f, ((0, 0), (1, 1)), mode="edge")


def laplacian(f, dx):
    """5-point Laplacian of the interior of a y-ghost-padded block.

    f: (ny_loc + 2, nx) -> (ny_loc, nx)."""
    fx = _pad_x(f)
    return (f[:-2, :] + f[2:, :] + fx[1:-1, :-2] + fx[1:-1, 2:]
            - 4.0 * f[1:-1, :]) / (dx * dx)


def grad_sq(f, dx):
    """|∇f|² of the interior (central differences)."""
    fx = _pad_x(f)
    gy = (f[2:, :] - f[:-2, :]) / (2 * dx)
    gx = (fx[1:-1, 2:] - fx[1:-1, :-2]) / (2 * dx)
    return gx * gx + gy * gy


def div_k_grad(k, f, dx):
    """∇·(k ∇f) of the interior, k on cell centres (ghost-padded like f)."""
    kx, fx = _pad_x(k), _pad_x(f)
    ke = 0.5 * (kx[1:-1, 1:-1] + kx[1:-1, 2:])
    kw = 0.5 * (kx[1:-1, 1:-1] + kx[1:-1, :-2])
    kn = 0.5 * (k[1:-1, :] + k[2:, :])
    ks = 0.5 * (k[1:-1, :] + k[:-2, :])
    return (ke * (fx[1:-1, 2:] - fx[1:-1, 1:-1])
            - kw * (fx[1:-1, 1:-1] - fx[1:-1, :-2])
            + kn * (f[2:, :] - f[1:-1, :])
            - ks * (f[1:-1, :] - f[:-2, :])) / (dx * dx)


def jacobi_sweeps(dphi, rhs, c, dx, n_sweeps: int):
    """n Jacobi sweeps for (I - c∇²) dphi = rhs on a ghost-padded block.

    Ghost rows are held fixed (they are the Schwarz artificial BCs)."""
    diag = 1.0 + 4.0 * c / (dx * dx)

    def sweep(dphi, _):
        fx = _pad_x(dphi)
        nb = (dphi[:-2, :] + dphi[2:, :]
              + fx[1:-1, :-2] + fx[1:-1, 2:]) / (dx * dx)
        interior = (rhs + c * nb) / diag
        return dphi.at[1:-1, :].set(interior), None

    dphi, _ = jax.lax.scan(sweep, dphi, None, length=n_sweeps)
    return dphi


# ---------------------------------------------------------------------------
# BCs and the per-time-step update (shared serial/parallel)
# ---------------------------------------------------------------------------

def apply_physical_bc(f, comm: Comm | None):
    """Mirror into the ghost rows at the *global* north/south walls.

    On interior subdomain edges the ghosts come from neighbours; shard 0's
    south ghost and shard n-1's north ghost are physical walls."""
    if comm is None:
        return f.at[0, :].set(f[1, :]).at[-1, :].set(f[-2, :])
    rank = comm.rank()
    n = comm.size()
    f = jnp.where(rank == 0, f.at[0, :].set(f[1, :]), f)
    f = jnp.where(rank == n - 1, f.at[-1, :].set(f[-2, :]), f)
    return f


def _communicate(f, comm):
    """Refresh ghost rows from neighbours (then physical BCs overwrite the
    outer walls)."""
    if comm is None:
        return f
    left, right = halo_exchange(f[1:-1], comm, halo=1, axis=0)
    return f.at[0, :].set(left[-1, :]).at[-1, :].set(right[0, :])


def timestep(eta, phi, p: BoussinesqParams, comm: Comm | None):
    """One Boussinesq step on ghost-padded local blocks (serial: comm=None
    and the 'local block' is the global domain).

    Returns (eta, phi, schwarz_iters)."""
    refresh = (lambda f: apply_physical_bc(_communicate(f, comm), comm))

    # -- continuity: explicit eta update ------------------------------------
    phi = refresh(phi)
    eta = refresh(eta)
    depth = 1.0 + p.alpha * eta
    eta_new_int = eta[1:-1, :] - p.dt * div_k_grad(depth, phi, p.dx)
    eta = refresh(eta.at[1:-1, :].set(eta_new_int))

    # -- bernoulli: implicit Helmholtz solve for dphi -------------------------
    rhs = -p.dt * (eta[1:-1, :] + 0.5 * p.alpha * grad_sq(phi, p.dx))
    dphi0 = jnp.zeros_like(phi)

    if comm is None:
        # serial: plain Jacobi to convergence with the SAME kernel
        def cond(carry):
            dphi, prev, it = carry
            diff = jnp.sum((dphi - prev) ** 2)
            den = jnp.maximum(jnp.sum(dphi ** 2), 1e-30)
            return jnp.logical_and(it < p.schwarz_max_iter,
                                   jnp.logical_or(it < 2,
                                                  diff / den > p.schwarz_threshold))

        def body(carry):
            dphi, _, it = carry
            prev = dphi
            dphi = apply_physical_bc(dphi, None)
            dphi = jacobi_sweeps(dphi, rhs, p.c, p.dx, p.jacobi_sweeps)
            return dphi, prev, it + 1

        dphi, _, iters = jax.lax.while_loop(
            cond, body, (dphi0, dphi0, jnp.asarray(0, jnp.int32)))
    else:
        dphi, iters, _ = additive_schwarz_iterations(
            subdomain_solve=lambda d: jacobi_sweeps(d, rhs, p.c, p.dx,
                                                    p.jacobi_sweeps),
            communicate=lambda d: _communicate(d, comm),
            set_bc=lambda d: apply_physical_bc(d, comm),
            max_iter=p.schwarz_max_iter,
            threshold=p.schwarz_threshold,
            solution=dphi0, comm=comm)

    phi = phi + dphi
    return eta, phi, iters


# ---------------------------------------------------------------------------
# Drivers
# ---------------------------------------------------------------------------

def initial_condition(p: BoussinesqParams, *, k_mode: int = 1):
    """Standing wave: eta = A cos(k x), phi = 0 (global, no ghosts)."""
    x = (jnp.arange(p.nx) + 0.5) * p.dx
    Lx = p.nx * p.dx
    eta = 0.05 * jnp.cos(k_mode * jnp.pi * x / Lx)
    return jnp.tile(eta, (p.ny, 1)), jnp.zeros((p.ny, p.nx))


def _with_ghosts(f):
    return jnp.pad(f, ((1, 1), (0, 0)))


def run_serial(p: BoussinesqParams, steps: int, *, k_mode: int = 1):
    eta, phi = initial_condition(p, k_mode=k_mode)
    eta, phi = _with_ghosts(eta), _with_ghosts(phi)

    def body(carry, _):
        eta, phi = carry
        eta, phi, iters = timestep(eta, phi, p, None)
        probe = eta[1 + p.ny // 4, p.nx // 4]
        return (eta, phi), {"mass": eta[1:-1].sum(), "probe": probe,
                            "iters": iters}

    (eta, phi), hist = jax.lax.scan(body, (eta, phi), None, length=steps)
    return eta[1:-1], phi[1:-1], hist


def run(p: BoussinesqParams, steps: int, *, k_mode: int = 1,
        executor: Executor | str = "serial", **executor_kwargs):
    """Executor-selecting driver: a :class:`MeshExecutor` runs the
    row-decomposed Schwarz solve over its mesh axis; a
    :class:`SerialExecutor` runs the single-domain serial solve (same kernel
    either way — Schwarz is domain decomposition, so only a mesh changes the
    layout).  Other executors are rejected rather than silently degraded.
    """
    executor = make_executor(executor, **executor_kwargs)
    if isinstance(executor, MeshExecutor):
        return run_parallel(executor.mesh, p, steps, k_mode=k_mode,
                            axis=executor.axis)
    if not isinstance(executor, SerialExecutor):
        raise TypeError(
            f"boussinesq.run supports 'serial' or 'mesh' executors, not "
            f"{type(executor).__name__}: the Schwarz solve is domain "
            f"decomposition, so only a mesh changes the layout")
    return run_serial(p, steps, k_mode=k_mode)


def run_parallel(mesh, p: BoussinesqParams, steps: int, *, k_mode: int = 1,
                 axis: str = "data"):
    """Row-decomposed Schwarz run; one jitted scan over time."""
    n = mesh.shape[axis]
    assert p.ny % n == 0, (p.ny, n)
    eta0, phi0 = initial_condition(p, k_mode=k_mode)

    def per_shard(eta_l, phi_l):
        comm = Comm(axis)
        eta = jnp.pad(eta_l, ((1, 1), (0, 0)))
        phi = jnp.pad(phi_l, ((1, 1), (0, 0)))

        def body(carry, _):
            eta, phi = carry
            eta, phi, iters = timestep(eta, phi, p, comm)
            mass = comm.all_reduce_sum(eta[1:-1].sum())
            return (eta, phi), {"mass": mass, "iters": iters}

        (eta, phi), hist = jax.lax.scan(body, (eta, phi), None, length=steps)
        return eta[1:-1], phi[1:-1], hist

    run = jax.shard_map(
        per_shard, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None),
                   {"mass": P(), "iters": P()}),
        check_vma=False)
    eta, phi, hist = jax.jit(run)(eta0, phi0)
    return eta, phi, hist

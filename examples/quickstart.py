"""Quickstart: the paper's §2 parabola example through all four executors of
the function-centric runtime (every tier drives the SAME three functions).

    PYTHONPATH=src python examples/quickstart.py

1. ``solve_problem`` / ``SerialExecutor``     — the paper's serial loop.
2. ``vmap_solve_problem`` / ``VmapExecutor``  — vectorized on one device.
3. ``parallel_solve_problem`` / ``MeshExecutor`` — SPMD over a device mesh
   (here 1 CPU device; on a pod, the production mesh — code unchanged).
4. ``ThreadFarmExecutor``                     — concurrent host-level farm
   (work stealing + straggler re-dispatch) for separately-jitted programs.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.core import solve_problem, vmap_solve_problem, parallel_solve_problem
from repro.core.runtime import ThreadFarmExecutor
from repro.launch.mesh import make_mesh

M, N, L = 32, 50, 10.0


# --- the user's three functions (the paper's Parabola class) ----------------

class Parabola:
    def initialize(self):
        x = np.linspace(0, L, N)
        vals = np.linspace(-1, 1, M)
        self.input_args = [((x,), {"a": a, "b": b, "c": 5.0})
                           for a in vals for b in vals]
        return self.input_args

    def func(self, x, a=0.0, b=0.0, c=1.0):
        return a * x ** 2 + b * x + c

    def finalize(self, output):
        return [(args[1]["a"], args[1]["b"])
                for args, out in zip(self.input_args, output)
                if np.min(out) < 0]


print("== tier 1: paper-faithful serial solve_problem ==")
p = Parabola()
ab = solve_problem(p.initialize, p.func, p.finalize)
print(f"   {len(ab)} of {M*M} (a,b) combinations give f < 0 somewhere")


# --- tier 2/3: the same problem as stacked-array tasks ----------------------

x = jnp.linspace(0, L, N)
vals = jnp.linspace(-1, 1, M)
aa, bb = jnp.meshgrid(vals, vals, indexing="ij")


def initialize():
    return {"a": aa.ravel(), "b": bb.ravel()}


def func(task):
    return task["a"] * x ** 2 + task["b"] * x + 5.0


def finalize(out):
    neg = (out.min(axis=-1) < 0)
    return int(neg.sum())


print("== tier 2: vmapped on one device ==")
n_neg = vmap_solve_problem(initialize, func, finalize)
print(f"   {n_neg} negative combinations (matches: {n_neg == len(ab)})")

print("== tier 3: SPMD task farm over the available mesh ==")
mesh = make_mesh((jax.device_count(),), ("data",))
n_neg = parallel_solve_problem(initialize, func, finalize, mesh)
print(f"   {n_neg} negative combinations on a {jax.device_count()}-device mesh")
assert n_neg == len(ab)

print("== tier 4: concurrent host-level thread farm ==")
farm = ThreadFarmExecutor(num_workers=8, deadline_factor=3.0)
n_neg = farm.run(initialize, func, finalize)
print(f"   {n_neg} negative combinations on the thread farm")
assert n_neg == len(ab)
print("quickstart OK")

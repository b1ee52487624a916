"""Paper §4.2: diffusion Monte Carlo for a 3D harmonic trap, serial AND
SPMD-parallel with dynamic load balancing (run with more fake devices to see
the rebalancer work):

    PYTHONPATH=src python examples/dmc_walkers.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/dmc_walkers.py
"""
import jax
import numpy as np

from repro.apps import dmc
from repro.launch.mesh import make_mesh

print("== serial DMC (paper's time_integration + Walkers class) ==")
out = dmc.run_serial(n_walkers=400, timesteps=500, tau=0.02)
print(f"   E0 estimate: {float(out['e0_estimate']):.4f}  (exact: 1.5)")
print(f"   final population: {int(out['counts'][-1])}")

n_dev = jax.device_count()
print(f"== SPMD DMC over {n_dev} device(s), load-balanced every step ==")
mesh = make_mesh((n_dev,), ("data",))
out = dmc.run_parallel(mesh, n_walkers=128 * n_dev, timesteps=400, tau=0.02)
lc = np.asarray(out["local_counts"])[-1]
print(f"   E0 estimate: {float(out['e0_estimate']):.4f}")
print(f"   load-balancer fired {int(out['rebalances'])} times")
print(f"   final per-shard walker counts: {lc} (skew "
      f"{lc.max() / max(lc.min(), 1):.2f})")

"""Paper §4.3: Boussinesq ocean waves via additive Schwarz.

The same Jacobi "legacy kernel" runs (a) on the global domain and (b) per
subdomain under the generic Schwarz layer; the solutions must agree.

    PYTHONPATH=src python examples/boussinesq_waves.py
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/boussinesq_waves.py
"""
import jax
import numpy as np

from repro.apps import boussinesq as bq
from repro.launch.mesh import make_mesh

p = bq.BoussinesqParams(nx=64, ny=64, dt=0.02, eps=0.3, alpha=0.05)
steps = 60

print(f"== serial solve ({p.nx}x{p.ny}, {steps} steps) ==")
eta_s, phi_s, hist_s = bq.run_serial(p, steps=steps)
print(f"   mass drift: {abs(float(hist_s['mass'][-1] - hist_s['mass'][0])):.2e}")

n_dev = jax.device_count()
print(f"== additive Schwarz over {n_dev} subdomain(s) ==")
mesh = make_mesh((n_dev,), ("data",))
eta_p, phi_p, hist_p = bq.run_parallel(mesh, p, steps=steps)
err = np.abs(np.asarray(eta_s) - np.asarray(eta_p)).max()
print(f"   max |eta_serial - eta_schwarz| = {err:.2e}")
print(f"   mean Schwarz iterations/step: "
      f"{float(np.asarray(hist_p['iters']).mean()):.1f}")
assert err < 1e-4
print("serial and Schwarz-parallel solutions agree")

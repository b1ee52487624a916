"""Paper Table 2: DMC scaled-size scaling with dynamic load balancing.

The paper's test keeps 200 walkers per processor and reports near-constant
wall time as processors grow (85-88% efficiency).  On one host we reproduce
the *structure*: the SPMD step is run over 1/2/4/8 fake devices in
subprocesses, walkers per shard held constant, and we report wall time +
rebalance counts.  Constant time across device counts = the paper's scaled
scalability; the load balancer's fire count shows the population dynamics."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap
import time


def _run_spmd_dmc(n_dev: int, walkers_per_shard: int = 128,
                  steps: int = 200) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import time, jax
        from repro.apps import dmc
        from repro.launch.mesh import make_mesh
        mesh = make_mesh(({n_dev},), ("data",))
        # warmup compile
        dmc.run_parallel(mesh, n_walkers={walkers_per_shard * n_dev},
                         timesteps=2, tau=0.02)
        t0 = time.perf_counter()
        out = dmc.run_parallel(mesh, n_walkers={walkers_per_shard * n_dev},
                               timesteps={steps}, tau=0.02)
        dt = time.perf_counter() - t0
        print("RESULT", dt, float(out["e0_estimate"]), int(out["rebalances"]))
    """)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=420,
                         # virtual CPU devices by design: never contend
                         # for an accelerator the parent may hold
                         env=dict(os.environ, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=os.path.join(root, "src")))
    assert out.returncode == 0, out.stderr[-2000:]
    line = [l for l in out.stdout.splitlines() if l.startswith("RESULT")][0]
    _, dt, e0, reb = line.split()
    return {"time_s": float(dt), "e0": float(e0), "rebalances": int(reb)}


def run(csv_rows: list):
    base = None
    for n in (1, 2, 4, 8):
        r = _run_spmd_dmc(n)
        base = base or r["time_s"]
        eff = base / r["time_s"]
        csv_rows.append(
            f"dmc_{n}dev,{r['time_s']*1e6:.0f},"
            f"walkers={128*n};e0={r['e0']:.3f};rebalances={r['rebalances']};"
            f"scaled_eff={eff:.2f}")

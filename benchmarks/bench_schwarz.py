"""Paper Table 3: Boussinesq/additive-Schwarz speedup.

The paper fixes a 1000x1000 mesh, 40 steps, and reports speedup vs CPUs
(91-103%).  Same structure here: fixed global grid, 40 steps, subdomain count
swept over subprocess device counts; correctness pinned by serial-vs-Schwarz
agreement (max |eta_s - eta_p|)."""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap


def _run(n_dev: int, steps: int = 40, ny: int = 64) -> dict:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_dev}"
        import time, jax, numpy as np
        from repro.apps import boussinesq as bq
        from repro.launch.mesh import make_mesh
        p = bq.BoussinesqParams(nx=64, ny={ny}, dt=0.02, eps=0.3, alpha=0.05)
        mesh = make_mesh(({n_dev},), ("data",))
        bq.run_parallel(mesh, p, steps=2)        # warmup
        t0 = time.perf_counter()
        eta_p, phi_p, hist = bq.run_parallel(mesh, p, steps={steps})
        dt = time.perf_counter() - t0
        eta_s, _, _ = bq.run_serial(p, steps={steps})
        err = float(np.abs(np.asarray(eta_s) - np.asarray(eta_p)).max())
        iters = float(np.asarray(hist["iters"]).mean())
        print("RESULT", dt, err, iters)
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
    _, dt, err, iters = line.split()
    return {"time_s": float(dt), "err": float(err), "iters": float(iters)}


def run(csv_rows: list):
    base = None
    for n in (1, 2, 4, 8):
        r = _run(n)
        base = base or r["time_s"]
        csv_rows.append(
            f"schwarz_{n}sub,{r['time_s']*1e6:.0f},"
            f"speedup={base/r['time_s']:.2f};max_err={r['err']:.2e};"
            f"schwarz_iters={r['iters']:.0f}")

"""Executor-runtime benchmarks.

Two claims measured:

1. **Farm throughput** — the :class:`ThreadFarmExecutor` must beat the serial
   farm by >= 3x on 8 workers for task sets that release the GIL (device
   compute / I/O), since that is the whole point of making ``host_task_farm``
   genuinely concurrent.
2. **Cross-tier parity** — all four executors return identical results on the
   quickstart parabola problem (the acceptance criterion of the runtime
   refactor).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.runtime import (MeshExecutor, SerialExecutor,
                                ThreadFarmExecutor, VmapExecutor)
from repro.launch.mesh import make_mesh


def _farm_speedup(csv_rows, name, thunks, workers=8):
    t0 = time.perf_counter()
    serial = [t() for t in thunks]
    t_serial = time.perf_counter() - t0

    farm = ThreadFarmExecutor(num_workers=workers)
    t0 = time.perf_counter()
    threaded, stats = farm.map_callables(thunks)
    t_farm = time.perf_counter() - t0

    assert serial == threaded or np.allclose(
        np.asarray(serial, dtype=float), np.asarray(threaded, dtype=float)), name
    speedup = t_serial / max(t_farm, 1e-9)
    csv_rows.append(
        f"runtime_farm_{name},{t_farm*1e6:.0f},"
        f"serial_s={t_serial:.4f};farm_s={t_farm:.4f};"
        f"workers={workers};speedup={speedup:.2f}x;"
        f"steals={stats['steals']};rebalances={stats['rebalances']}")
    return speedup


def run(csv_rows: list):
    # -- 1a. I/O-bound task set (pure GIL release) ---------------------------
    def io_task(i):
        return lambda: (time.sleep(0.02), i)[1]

    _farm_speedup(csv_rows, "io_bound", [io_task(i) for i in range(32)])

    # -- 1b. device-bound task set (jitted programs, shapes differ per task
    # bucket — the serve engine's prefill pattern) ---------------------------
    fns = {}
    for bucket in (256, 384, 512, 640):
        f = jax.jit(lambda x: jnp.linalg.matrix_power(x @ x.T, 4).sum())
        f(jnp.eye(bucket)).block_until_ready()          # compile up front
        fns[bucket] = f

    def dev_task(i):
        bucket = (256, 384, 512, 640)[i % 4]
        x = jnp.eye(bucket) * (1.0 + 1e-6 * i)
        return lambda: float(fns[bucket](x).block_until_ready())

    _farm_speedup(csv_rows, "device_bound", [dev_task(i) for i in range(32)])

    # -- 2. four-executor parity on the quickstart problem -------------------
    M, N, L = 16, 24, 10.0
    x = jnp.linspace(0, L, N)
    vals = jnp.linspace(-1, 1, M)
    aa, bb = jnp.meshgrid(vals, vals, indexing="ij")

    def initialize():
        return {"a": aa.ravel(), "b": bb.ravel()}

    def func(task):
        return task["a"] * x ** 2 + task["b"] * x + 5.0

    def finalize(out):
        return np.asarray(out)

    mesh = make_mesh((jax.device_count(),), ("data",))
    execs = {"serial": SerialExecutor(), "vmap": VmapExecutor(),
             "mesh": MeshExecutor(mesh),
             "thread": ThreadFarmExecutor(num_workers=8)}
    outs, times = {}, {}
    for name, ex in execs.items():
        t0 = time.perf_counter()
        outs[name] = ex.run(initialize, func, finalize)
        times[name] = time.perf_counter() - t0
    ref = outs["serial"]
    ok = all(np.allclose(outs[n], ref, rtol=1e-5, atol=1e-6) for n in outs)
    csv_rows.append(
        "runtime_parity," + f"{times['vmap']*1e6:.0f}," +
        ";".join(f"{n}_s={t:.4f}" for n, t in times.items()) +
        f";identical={ok}")
    assert ok, "executor tiers disagree on the quickstart problem"

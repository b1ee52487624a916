"""Executor-runtime tests: cross-tier parity on the quickstart problem plus
deterministic concurrency/work-stealing/straggler coverage for the farm."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

from repro.core.runtime import (MeshExecutor, SerialExecutor,
                                ThreadFarmExecutor, VmapExecutor,
                                make_executor, straggler_deadline)


# ---------------------------------------------------------------------------
# The quickstart parabola in stacked form (shared by the parity tests)
# ---------------------------------------------------------------------------

M, N, L = 16, 24, 10.0
_x = jnp.linspace(0, L, N)


def _initialize():
    vals = jnp.linspace(-1, 1, M)
    aa, bb = jnp.meshgrid(vals, vals, indexing="ij")
    return {"a": aa.ravel(), "b": bb.ravel()}


def _func(task):
    return task["a"] * _x ** 2 + task["b"] * _x + 5.0


def _finalize(out):
    return np.asarray(out)


def _all_executors():
    execs = [SerialExecutor(), VmapExecutor(),
             MeshExecutor(make_mesh((jax.device_count(),), ("data",))),
             ThreadFarmExecutor(num_workers=4)]
    return execs


def test_all_executors_identical_results():
    """The acceptance-criterion parity check: four executors, one answer."""
    ref = _all_executors()[0].run(_initialize, _func, _finalize)
    assert ref.shape == (M * M, N)
    for ex in _all_executors()[1:]:
        got = ex.run(_initialize, _func, _finalize)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=type(ex).__name__)


def test_mesh_executor_passes_valid_mask():
    """Two-argument finalize gets padded outputs + the valid-task mask."""
    mesh = make_mesh((jax.device_count(),), ("data",))
    n_tasks = 3 * jax.device_count() + 1 if jax.device_count() > 1 else 3

    def initialize():
        return {"a": jnp.arange(float(n_tasks))}

    seen = {}

    def finalize(out, mask):
        seen["out"], seen["mask"] = out, mask
        return out[mask].sum()

    got = MeshExecutor(mesh).run(initialize, lambda t: 2.0 * t["a"], finalize)
    assert seen["mask"].sum() == n_tasks
    assert seen["out"].shape[0] % jax.device_count() == 0
    assert float(got) == pytest.approx(2.0 * sum(range(n_tasks)))


def test_finalize_arity_defaulted_params_stay_one_arg():
    """A defaulted second parameter (or *args) must NOT receive the mask —
    pre-runtime finalizers like np.mean(a, axis=...) keep the 1-arg call."""
    def init():
        return {"a": jnp.arange(4.0)}

    got = VmapExecutor().run(init, lambda t: t["a"] * 2, np.mean)
    assert float(got) == pytest.approx(3.0)

    seen = {}

    def fin_defaulted(out, verbose=False):
        seen["verbose"] = verbose
        return out

    SerialExecutor().run(init, lambda t: t["a"], fin_defaulted)
    assert seen["verbose"] is False

    def fin_varargs(*outs):
        return outs

    outs = SerialExecutor().run(init, lambda t: t["a"], fin_varargs)
    assert len(outs) == 1                  # mask not smuggled into *args


def test_serial_executor_paper_host_form():
    """List-of-(args, kwargs) tasks keep the paper's verbatim semantics."""
    def initialize():
        return [((i,), {"k": 10}) for i in range(5)]

    out = SerialExecutor().run(initialize, lambda i, k=1: i * k, sum)
    assert out == sum(i * 10 for i in range(5))


def test_executors_accept_generator_host_tasks():
    """initialize() may return any iterable of (args, kwargs) pairs — the
    paper's loop just iterates it."""
    def initialize():
        return (((i,), {}) for i in range(4))

    out = SerialExecutor().run(initialize, lambda i: i * 3, list)
    assert out == [0, 3, 6, 9]
    out = ThreadFarmExecutor(num_workers=2).run(
        initialize, lambda i: i * 3, list)
    assert out == [0, 3, 6, 9]


def test_make_executor_specs():
    assert isinstance(make_executor("serial"), SerialExecutor)
    assert isinstance(make_executor("vmap"), VmapExecutor)
    assert isinstance(make_executor("thread"), ThreadFarmExecutor)
    mesh = make_mesh((jax.device_count(),), ("data",))
    assert isinstance(make_executor("mesh", mesh=mesh), MeshExecutor)
    ex = SerialExecutor()
    assert make_executor(ex) is ex
    with pytest.raises(ValueError):
        make_executor("mesh")
    with pytest.raises(ValueError):
        make_executor("bogus")


# ---------------------------------------------------------------------------
# ThreadFarmExecutor: concurrency, stealing, rebalance, stragglers
# ---------------------------------------------------------------------------

def test_farm_overlaps_gil_releasing_tasks():
    """8 sleep-bound tasks on 8 workers must take ~1 task-time, not ~8."""
    farm = ThreadFarmExecutor(num_workers=8)
    t0 = time.perf_counter()
    results, stats = farm.map_callables(
        [lambda i=i: (time.sleep(0.05), i)[1] for i in range(8)])
    wall = time.perf_counter() - t0
    assert results == list(range(8))
    assert wall < 0.25                     # serial would be >= 0.4s
    assert stats["num_workers"] == 8


def test_farm_timings_indexed_by_task():
    """stats['timings'][i] is task i's runtime (the pre-runtime contract),
    regardless of completion order."""
    delays = [0.0, 0.06, 0.0, 0.03]
    farm = ThreadFarmExecutor(num_workers=4)
    _, stats = farm.map_callables(
        [lambda i=i: time.sleep(delays[i]) for i in range(4)])
    t = stats["timings"]
    assert len(t) == 4 and all(x is not None for x in t)
    assert t[1] > 0.05 and t[3] > 0.02 and t[0] < 0.02 and t[2] < 0.02


def test_farm_results_order_independent_of_execution_order():
    """Work stealing may run tasks in any order; results stay index-ordered."""
    rng = np.random.default_rng(0)
    delays = rng.uniform(0.0, 0.004, size=64)
    farm = ThreadFarmExecutor(num_workers=8)
    results, stats = farm.map_callables(
        [lambda i=i: (time.sleep(delays[i]), i)[1] for i in range(64)])
    assert results == list(range(64))
    assert sum(stats["worker_tasks"]) == 64


def test_farm_work_stealing_engages():
    """All slow work piled on one worker's initial queue gets stolen."""
    # 2 workers, 8 tasks -> worker 0 seeds tasks 0-3, worker 1 tasks 4-7.
    # Make worker-0's share slow so worker 1 finishes and steals.
    farm = ThreadFarmExecutor(num_workers=2, rebalance=False)
    results, stats = farm.map_callables(
        [lambda i=i: (time.sleep(0.03 if i < 4 else 0.0), i)[1]
         for i in range(8)])
    assert results == list(range(8))
    assert stats["steals"] >= 1
    # both workers did real work
    assert min(stats["worker_tasks"]) >= 1


def test_farm_straggler_redispatch_first_completion_wins():
    calls = []
    lock = threading.Lock()

    def flaky():
        with lock:
            calls.append(time.perf_counter())
            first = len(calls) == 1
        if first:
            time.sleep(0.3)               # first attempt straggles
            return "late"
        return "fast"                     # backup attempt returns instantly

    tasks = [lambda: "ok"] * 6 + [flaky]
    farm = ThreadFarmExecutor(num_workers=4, deadline_factor=2.0,
                              min_straggler_s=0.02)
    results, stats = farm.map_callables(tasks)
    assert results[:6] == ["ok"] * 6
    assert results[6] == "fast"           # backup finished first and won
    assert stats["stragglers"] == [6]
    assert len(calls) == 2                # re-issued exactly once


def test_farm_timing_rebalance_triggers():
    """With one slow worker and queued work, the farm must rebalance queues
    using the measured per-worker speed."""
    slow_worker_seen = threading.Event()

    def make(i):
        def task():
            # tasks 0..9 seed worker 0's queue (2 workers, 20 tasks);
            # make them slow so rebalancing moves its backlog to worker 1
            if i < 10:
                slow_worker_seen.set()
                time.sleep(0.01)
            return i
        return task

    farm = ThreadFarmExecutor(num_workers=2, steal=False, rebalance=True)
    results, stats = farm.map_callables([make(i) for i in range(20)])
    assert results == list(range(20))
    assert slow_worker_seen.is_set()
    assert stats["rebalances"] >= 1


def test_farm_single_worker_straggler_inline_redo():
    """With one worker no idle peer exists, so the farm must keep the old
    serial semantics: re-run a deadline-breaching task post-hoc."""
    calls = {"n": 0}

    def slow():
        calls["n"] += 1
        time.sleep(0.05 if calls["n"] == 1 else 0.0)
        return 42

    tasks = [lambda: 1] * 6 + [slow]
    farm = ThreadFarmExecutor(num_workers=1, deadline_factor=3.0)
    results, stats = farm.map_callables(tasks)
    assert results == [1] * 6 + [42]
    assert stats["stragglers"] == [6]
    assert calls["n"] == 2


def test_farm_single_worker_failed_redo_keeps_original_result():
    """A redo that raises must never clobber the slow-but-successful
    original."""
    calls = {"n": 0}

    def slow_then_broken():
        calls["n"] += 1
        if calls["n"] == 1:
            time.sleep(0.05)
            return 42
        raise RuntimeError("redo exploded")

    tasks = [lambda: 1] * 6 + [slow_then_broken]
    farm = ThreadFarmExecutor(num_workers=1, deadline_factor=3.0)
    results, stats = farm.map_callables(tasks)
    assert results[6] == 42                # original result preserved
    assert stats["stragglers"] == [6]
    assert calls["n"] == 2


def test_farm_failing_backup_does_not_discard_running_original():
    """A fast-failing backup attempt must wait for the in-flight original;
    the original's success settles the task."""
    calls = {"n": 0}
    lock = threading.Lock()

    def slow_original_broken_backup():
        with lock:
            calls["n"] += 1
            first = calls["n"] == 1
        if first:
            time.sleep(0.3)                # slow but healthy
            return "late"
        raise RuntimeError("backup hit non-idempotent state")

    tasks = [lambda: "ok"] * 5 + [slow_original_broken_backup]
    farm = ThreadFarmExecutor(num_workers=4, deadline_factor=2.0,
                              min_straggler_s=0.02)
    results, stats = farm.map_callables(tasks)     # must not raise
    assert results[5] == "late"
    assert stats["stragglers"] == [5]
    assert calls["n"] == 2


def test_farm_nested_call_from_task_runs_serially():
    """A task calling back into its own farm instance (e.g. a task on a
    long-lived engine farm) must nest serially, not deadlock."""
    farm = ThreadFarmExecutor(num_workers=2, deadline_factor=3.0)

    def outer():
        inner, stats = farm.map_callables([lambda: 10, lambda: 20])
        assert stats["num_workers"] == 1       # serial nested fallback
        return sum(inner)

    results, _ = farm.map_callables([outer, lambda: 1])
    assert results == [30, 1]


def test_nested_host_task_farm_same_config():
    from repro.core import host_task_farm

    def outer():
        r, _ = host_task_farm([lambda: 5, lambda: 6], deadline_factor=3.0)
        return sum(r)

    results, _ = host_task_farm([outer] * 3, deadline_factor=3.0)
    assert results == [11, 11, 11]


def test_no_copy_finalize_when_unpadded():
    """Serial/Vmap never pad, so 1-arg finalize must get the outputs
    untouched (no per-leaf device copy)."""
    seen = {}

    def finalize(out):
        seen["out"] = out
        return out

    SerialExecutor().run(lambda: {"a": jnp.arange(4.0)},
                         lambda t: t["a"], finalize)
    # stacked once by the executor, then passed through without re-slicing
    assert seen["out"].shape == (4,)
    got = VmapExecutor().run(lambda: {"a": jnp.arange(4.0)},
                             lambda t: t["a"] * 2, finalize)
    assert got is seen["out"]


def test_farm_base_exception_does_not_deadlock():
    """A task calling sys.exit() must settle the task and re-raise at the
    join — not kill the worker loop and hang the farm forever."""
    import sys
    farm = ThreadFarmExecutor(num_workers=2)
    with pytest.raises(SystemExit):
        farm.map_callables([lambda: 1, lambda: sys.exit(1), lambda: 2])
    # the instance is not poisoned: _call_lock was released
    results, _ = farm.map_callables([lambda: 3])
    assert results == [3]


def test_boussinesq_rejects_non_mesh_parallel_executor():
    from repro.apps import boussinesq as bq
    p = bq.BoussinesqParams(nx=16, ny=16)
    with pytest.raises(TypeError, match="serial.*or.*mesh"):
        bq.run(p, 2, executor="vmap")


def test_farm_backup_completion_unblocks_hung_original():
    """The whole point of backup tasks: a truly stuck original attempt must
    not gate map_callables once its backup has settled the task."""
    release = threading.Event()
    calls = []
    lock = threading.Lock()

    def hung_once():
        with lock:
            calls.append(1)
            first = len(calls) == 1
        if first:
            release.wait(10.0)            # simulates deadlocked I/O
            return "late"
        return "fast"

    farm = ThreadFarmExecutor(num_workers=4, deadline_factor=2.0,
                              min_straggler_s=0.02)
    t0 = time.perf_counter()
    results, stats = farm.map_callables([lambda: "ok"] * 5 + [hung_once])
    wall = time.perf_counter() - t0
    release.set()                         # free the stuck worker thread
    assert results[5] == "fast"
    assert stats["stragglers"] == [5]
    assert wall < 5.0                     # returned long before the 10s hang


def test_vmap_executor_accepts_tuple_pytree_tasks():
    """Stacked tasks as a tuple pytree (valid before the refactor) must not
    be mistaken for the paper's (args, kwargs) host form."""
    from repro.core import vmap_solve_problem

    def initialize():
        return (jnp.arange(4.0), jnp.arange(4.0) * 10)

    got = vmap_solve_problem(initialize, lambda t: t[0] + t[1],
                             lambda o: np.asarray(o))
    np.testing.assert_allclose(got, [0.0, 11.0, 22.0, 33.0])
    got = SerialExecutor().run(initialize, lambda t: t[0] + t[1],
                               lambda o: np.asarray(o))
    np.testing.assert_allclose(got, [0.0, 11.0, 22.0, 33.0])


def test_farm_reuses_pool_across_calls():
    farm = ThreadFarmExecutor(num_workers=4)
    farm.map_callables([lambda: 1] * 8)
    pool = farm._pool
    farm.map_callables([lambda: 2] * 8)
    assert farm._pool is pool              # no per-call pool teardown


def test_farm_propagates_task_errors():
    farm = ThreadFarmExecutor(num_workers=2)
    with pytest.raises(RuntimeError, match="boom"):
        farm.map_callables([lambda: 1,
                            lambda: (_ for _ in ()).throw(RuntimeError("boom"))])


def test_farm_worker_crash_frees_idle_workers():
    """An internal worker-loop bug must surface AND not strand the other
    workers in an untimed wait holding pool slots."""
    farm = ThreadFarmExecutor(num_workers=4)
    boom = RuntimeError("internal farm bug")

    def broken_rebalance(st):
        raise boom

    farm._maybe_rebalance = broken_rebalance
    with pytest.raises(RuntimeError, match="internal farm bug"):
        farm.map_callables([lambda: 1] * 8)
    del farm._maybe_rebalance              # restore the real method
    results, _ = farm.map_callables([lambda: 2] * 8)
    assert results == [2] * 8              # pool slots were not leaked


def test_farm_fails_fast_on_task_error():
    """A failing task must stop queued tasks from starting (the serial farm
    raised immediately), not run the whole batch first."""
    executed = []
    lock = threading.Lock()

    def make(i):
        def task():
            if i == 0:
                raise ValueError("early failure")
            time.sleep(0.01)
            with lock:
                executed.append(i)
            return i
        return task

    farm = ThreadFarmExecutor(num_workers=2)
    with pytest.raises(ValueError, match="early failure"):
        farm.map_callables([make(i) for i in range(40)])
    time.sleep(0.1)                        # let in-flight tasks finish
    assert len(executed) < 10              # queues were drained, not run


def test_host_task_farm_concurrent_same_config_independent():
    """Two threads on the same config must not serialize whole runs."""
    from repro.core import host_task_farm
    done_b = []

    def run_a():
        host_task_farm([lambda: time.sleep(0.1)] * 4, num_workers=2,
                       deadline_factor=None)

    def run_b():
        host_task_farm([lambda: 0] * 4, num_workers=2, deadline_factor=None)
        done_b.append(time.perf_counter())

    t0 = time.perf_counter()
    a = threading.Thread(target=run_a)
    a.start()
    time.sleep(0.02)                       # let A take the cached farm
    b = threading.Thread(target=run_b)
    b.start()
    b.join()
    assert done_b[0] - t0 < 0.15           # B did not wait out A's ~0.2s run
    a.join()


def test_farm_empty_and_single():
    farm = ThreadFarmExecutor(num_workers=4)
    results, stats = farm.map_callables([])
    assert results == [] and stats["num_workers"] == 0
    results, _ = farm.map_callables([lambda: 7])
    assert results == [7]


def test_farm_stacked_pytree_mode_matches_serial():
    farm = ThreadFarmExecutor(num_workers=4)
    got = farm.run(_initialize, _func, _finalize)
    ref = SerialExecutor().run(_initialize, _func, _finalize)
    np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Applications select executors instead of hand-wiring tiers
# ---------------------------------------------------------------------------

def test_mcmc_executor_selection_matches():
    from repro.apps import mcmc
    y, _ = mcmc.make_synthetic_votes(jax.random.PRNGKey(2), 12, 24)
    ref = mcmc.solve(mcmc.IdealPointProblem(y, n_chains=2, n_iter=30,
                                            burn=10, seed=3), "serial")
    for spec in ("vmap", "thread"):
        got = mcmc.solve(mcmc.IdealPointProblem(y, n_chains=2, n_iter=30,
                                                burn=10, seed=3), spec)
        np.testing.assert_allclose(np.asarray(got["x_mean"]),
                                   np.asarray(ref["x_mean"]),
                                   rtol=1e-4, atol=1e-4, err_msg=spec)


def test_dmc_replica_farm():
    from repro.apps import dmc
    out = dmc.run_replicas(n_replicas=2, executor="thread", num_workers=2,
                           n_walkers=80, timesteps=120, tau=0.02, seed=0)
    assert abs(float(out["e0_estimate"]) - 1.5) < 0.4
    assert len(out["replicas"]) == 2
    # thread farm must agree with the serial executor on the same seeds
    ref = dmc.run_replicas(n_replicas=2, executor="serial",
                           n_walkers=80, timesteps=120, tau=0.02, seed=0)
    np.testing.assert_allclose(float(out["e0_estimate"]),
                               float(ref["e0_estimate"]), rtol=1e-5)


def test_boussinesq_executor_dispatch():
    from repro.apps import boussinesq as bq
    p = bq.BoussinesqParams(nx=24, ny=24, dt=0.02)
    _, _, hist = bq.run(p, 5, executor="serial")
    mesh = make_mesh((jax.device_count(),), ("data",))
    _, _, hist_m = bq.run(p, 5, executor="mesh", mesh=mesh)
    # mass of the standing wave is ~0, so compare absolutely (the Schwarz
    # iterates differ from global Jacobi only at stencil tolerance)
    np.testing.assert_allclose(np.asarray(hist_m["mass"]),
                               np.asarray(hist["mass"]), atol=1e-4)


def test_fault_redispatch_stragglers_entry_point():
    from repro.train.fault import redispatch_stragglers
    results, stats = redispatch_stragglers([lambda i=i: i for i in range(5)],
                                           deadline_factor=5.0)
    assert results == list(range(5))
    assert stats["stragglers"] == []


def test_straggler_deadline_rule():
    assert straggler_deadline([1.0, 1.0, 1.0], 3.0) == 3.0
    assert straggler_deadline([1e-6] * 5, 3.0, floor=0.01) == 0.01
    # median of even-length list: upper middle (same rule as host_task_farm)
    assert straggler_deadline([1.0, 2.0], 2.0) == 4.0
    assert straggler_deadline([], 3.0, floor=0.5) == 0.5  # no history yet


def test_make_executor_rejects_options_with_instance():
    with pytest.raises(ValueError, match="configure the instance"):
        make_executor(ThreadFarmExecutor(), num_workers=8)
    mesh = make_mesh((jax.device_count(),), ("data",))
    with pytest.raises(ValueError, match="configure the instance"):
        make_executor(SerialExecutor(), mesh=mesh)

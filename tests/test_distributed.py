"""Multi-device SPMD tests.

Each test runs in a SUBPROCESS with ``--xla_force_host_platform_device_count``
because the main pytest process must keep 1 device (smoke-test requirement).
"""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_spmd(body: str, n_devices: int = 8, timeout: int = 420) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n_devices}"
        import warnings; warnings.filterwarnings("ignore")
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_parallel_solve_problem_task_farm():
    run_spmd("""
        from repro.core import parallel_solve_problem
        mesh = make_mesh((8,), ("data",))
        m = 12  # 144 tasks over 8 shards (not divisible: pad+mask path)
        def initialize():
            a = jnp.linspace(-1, 1, m); b = jnp.linspace(-1, 1, m)
            aa, bb = jnp.meshgrid(a, b, indexing="ij")
            return {"a": aa.ravel(), "b": bb.ravel()}
        x = jnp.linspace(0, 10.0, 16)
        def func(t):
            return t["a"] * x**2 + t["b"] * x + 5
        got = parallel_solve_problem(initialize, func, lambda o: o, mesh)
        tasks = initialize()
        want = jax.vmap(func)(tasks)
        np.testing.assert_allclose(got, np.asarray(want), rtol=1e-5, atol=1e-5)
        print("task farm OK")
    """)


def test_redistribute_work_across_shards():
    run_spmd("""
        from repro.core.comm import Comm
        from repro.core.load_balance import redistribute_work
        mesh = make_mesh((8,), ("data",))
        cap = 16
        def per_shard(x):
            comm = Comm("data")
            rank = comm.rank()
            count = jnp.where(rank == 0, 9, jnp.where(rank == 1, 5, 0))
            data = jnp.where((jnp.arange(cap) < count)[:, None],
                             x + 100.0 * rank, 0.0)
            new_data, new_count = redistribute_work(data, count, comm)
            return new_data, new_count.reshape(1)
        x = jnp.tile(jnp.arange(cap, dtype=jnp.float32)[:, None], (8, 1))
        f = jax.jit(jax.shard_map(per_shard, mesh=mesh,
                    in_specs=P("data", None),
                    out_specs=(P("data", None), P("data")), check_vma=False))
        data, counts = f(x)
        counts = np.asarray(counts)
        assert counts.sum() == 14, counts           # conservation
        assert counts.max() - counts.min() <= 1     # balance
        # global rank-major order preserved: first shard's items come first
        flat = np.asarray(data).reshape(8, cap, 1)
        live = [flat[r, :counts[r], 0] for r in range(8)]
        merged = np.concatenate(live)
        want = np.concatenate([np.arange(9), 100.0 + np.arange(5)])
        np.testing.assert_allclose(merged, want)
        print("redistribute OK")
    """)


def test_dmc_parallel_with_load_balancing():
    run_spmd("""
        from repro.apps import dmc
        mesh = make_mesh((8,), ("data",))
        out = dmc.run_parallel(mesh, n_walkers=512, timesteps=400, tau=0.02)
        e0 = float(out["e0_estimate"])
        assert abs(e0 - 1.5) < 0.2, e0
        assert int(out["rebalances"]) > 0           # LB actually fired
        lc = np.asarray(out["local_counts"])[-1]
        assert lc.max() - lc.min() <= max(3, 0.2 * lc.mean()), lc
        print("parallel DMC OK", e0)
    """)


def test_boussinesq_schwarz_matches_serial():
    run_spmd("""
        from repro.apps import boussinesq as bq
        p = bq.BoussinesqParams(nx=48, ny=48, dt=0.02, eps=0.3, alpha=0.05)
        eta_s, phi_s, hist_s = bq.run_serial(p, steps=40)
        mesh = make_mesh((8,), ("data",))
        eta_p, phi_p, hist_p = bq.run_parallel(mesh, p, steps=40)
        err = np.abs(np.asarray(eta_s) - np.asarray(eta_p)).max()
        assert err < 1e-5, err
        print("schwarz OK", err)
    """)


def test_sharded_train_step_matches_single_device():
    run_spmd("""
        from repro.configs import smoke_config
        from repro.models.api import build_model
        from repro.optim import AdamWConfig
        from repro.optim.adamw import adamw_init
        from repro.train import make_train_step
        from repro.train.state import state_shardings
        from repro.mesh.axes import rules_for_mesh
        from repro.data import SyntheticTask

        cfg = smoke_config("qwen3-1.7b").replace(remat="none", tp=2)
        model = build_model(cfg)
        task = SyntheticTask(cfg, batch=8, seq_len=32)
        batch = task.batch_at(0)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamWConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)

        # single device
        s1 = {"params": params, "opt": adamw_init(params, opt)}
        step1 = make_train_step(model, opt, donate=False)
        o1, m1 = step1(s1, batch)

        # 4x2 mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        rules = rules_for_mesh(mesh)
        sh = state_shardings(model, mesh, rules)
        s2 = jax.device_put({"params": params, "opt": adamw_init(params, opt)}, sh)
        step2 = make_train_step(model, opt, mesh, rules, donate=False)
        o2, m2 = step2(s2, batch)

        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        w1 = np.asarray(jax.tree_util.tree_leaves(o1["params"])[0])
        w2 = np.asarray(jax.device_get(jax.tree_util.tree_leaves(o2["params"])[0]))
        np.testing.assert_allclose(w1, w2, rtol=2e-4, atol=2e-5)
        print("sharded step OK", float(m1["loss"]), float(m2["loss"]))
    """)


def test_compressed_pod_dp_matches_uncompressed():
    run_spmd("""
        from repro.configs import smoke_config
        from repro.models.api import build_model
        from repro.optim import AdamWConfig
        from repro.train.pod_dp import make_pod_dp_step
        from repro.mesh.axes import rules_for_mesh
        from repro.data import SyntheticTask

        cfg = smoke_config("qwen3-1.7b").replace(remat="none", tp=2)
        model = build_model(cfg)
        task = SyntheticTask(cfg, batch=8, seq_len=32)
        opt = AdamWConfig(peak_lr=3e-3, warmup_steps=0, decay_steps=20)
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = rules_for_mesh(mesh)

        def run(compress):
            step = make_pod_dp_step(model, opt, mesh, rules, compress=compress)
            state = step.init_state(jax.random.PRNGKey(0))
            losses = []
            for i in range(8):
                state, out = step(state, task.batch_at(i))
                losses.append(out["loss"])
            return losses, out, state

        lc, outc, sc = run(True)
        lu, outu, su = run(False)
        assert lc[-1] < lc[0], lc                       # training works
        # int8+EF tracks uncompressed DP closely
        assert abs(lc[-1] - lu[-1]) < 0.05, (lc[-1], lu[-1])
        # wire savings: ~4x less than fp32
        assert outc["wire_bytes"] < 0.3 * outc["fp32_bytes"]
        # pods stay in lockstep (same params on both pods)
        import numpy as np
        w0 = np.asarray(jax.device_get(
            jax.tree_util.tree_leaves(sc["pods"][0]["params"])[0]))
        w1 = np.asarray(jax.device_get(
            jax.tree_util.tree_leaves(sc["pods"][1]["params"])[0]))
        np.testing.assert_allclose(w0, w1, rtol=1e-6, atol=1e-7)
        print("pod-DP OK", lc[0], lc[-1], lu[-1])
    """)


def test_elastic_reshard_resume_across_mesh_sizes():
    run_spmd("""
        import tempfile
        from repro.configs import smoke_config
        from repro.models.api import build_model
        from repro.optim import AdamWConfig
        from repro.optim.adamw import adamw_init
        from repro.train import (make_train_step, save_checkpoint,
                                 restore_checkpoint)
        from repro.train.state import state_shardings
        from repro.mesh.axes import rules_for_mesh
        from repro.data import SyntheticTask

        cfg = smoke_config("qwen3-1.7b").replace(remat="none", tp=2)
        model = build_model(cfg)
        task = SyntheticTask(cfg, batch=8, seq_len=32)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamWConfig(peak_lr=1e-2, warmup_steps=0, decay_steps=10)

        mesh1 = make_mesh((4, 2), ("data", "model"))
        rules1 = rules_for_mesh(mesh1)
        sh1 = state_shardings(model, mesh1, rules1)
        state = jax.device_put({"params": params,
                                "opt": adamw_init(params, opt)}, sh1)
        step1 = make_train_step(model, opt, mesh1, rules1, donate=False)
        state, _ = step1(state, task.batch_at(0))

        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, state)
            # "cluster shrank": resume on 2x2
            mesh2 = make_mesh((2, 2), ("data", "model"))
            rules2 = rules_for_mesh(mesh2)
            sh2 = state_shardings(model, mesh2, rules2)
            state2, step_no = restore_checkpoint(d, state, shardings=sh2)
            assert step_no == 1
            stepf = make_train_step(model, opt, mesh2, rules2, donate=False)
            state2, out = stepf(state2, task.batch_at(1))
            assert np.isfinite(float(out["loss"]))
        print("elastic reshard OK")
    """)


def test_moe_ep_all_to_all_matches_serial():
    run_spmd("""
        from repro.configs import smoke_config
        from repro.models.api import build_model
        from repro.mesh.axes import rules_for_mesh
        from repro.data import SyntheticTask

        cfg = smoke_config("qwen3-moe-235b-a22b").replace(
            remat="none", tp=2, capacity_factor=8.0)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        task = SyntheticTask(cfg, batch=8, seq_len=32)
        batch = task.batch_at(0)
        l1, m1 = jax.jit(lambda p, b: model.loss(p, b, None))(params, batch)

        mesh = make_mesh((4, 2), ("data", "model"))
        rules = rules_for_mesh(mesh)
        from repro.models.module import sharding_tree
        psh = sharding_tree(model.param_defs(), mesh, rules)
        params2 = jax.device_put(params, psh)
        l2, m2 = jax.jit(lambda p, b: model.loss(p, b, rules))(params2, batch)
        assert abs(float(l1) - float(l2)) < 2e-3, (float(l1), float(l2))
        print("moe EP OK", float(l1), float(l2))
    """)

"""CPU runs of whole benchmark cells at a tiny width (bench/run.py).

These skip the command's look for a TPU and drive the rest of a run: the
engine built from the seed, warm-up, the open- and closed-loop load
drivers, the window, and the reference check that decides ``correct``.
The fault tests break the timed path underneath and see ``correct`` come
out false.
"""
import json
import pathlib
import shutil
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spec  # noqa: E402

TINY = dict(num_hidden_layers=2, hidden_size=64, intermediate_size=128,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512)
ENGINE = {"max_slots": 4, "max_len": 256, "prefill_chunk": 32}
MIXES = {
    "tiny-open": {"loop": "open", "rate_per_s": 40.0, "requests": 64,
                  "block": 8, "size_seed": 1,
                  "sessions": {"count": 2, "zipf_s": 1.0,
                               "prefix_len": [32, 64]},
                  "prompt_len": {"median": 24, "sigma": 0.5, "min": 8,
                                 "max": 64},
                  "output_len": {"median": 8, "sigma": 0.5, "min": 4,
                                 "max": 16},
                  "engine": ENGINE, "warm_sessions": True},
    "tiny-closed": {"loop": "closed", "clients": 3, "requests": 64,
                    "block": 3, "size_seed": 2,
                    "prompt_len": {"median": 16, "sigma": 0.5, "min": 8,
                                   "max": 48},
                    "output_len": {"median": 12, "sigma": 0.5, "min": 4,
                                   "max": 24},
                    "engine": ENGINE},
}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose only cells are tiny ones, added as files."""
    root = tmp_path_factory.mktemp("co")
    shutil.copytree(ROOT / "bench", root / "bench")
    base = json.loads((ROOT / "BENCHMARK.json").read_text())
    for name in ("qwen3", "qwen2"):
        src = {"qwen3": "qwen3-1.7b", "qwen2": "qwen2-7b-tp4"}[name]
        c = json.loads((ROOT / "bench" / "configs" / f"{src}.json").read_text())
        c.update(TINY, name=f"tiny-{name}")
        c["deployment"] = {"chips": 1, "tp": 1,
                           "engine": {"page_size": 16, "num_pages": 96}}
        (root / "bench" / "configs" / f"tiny-{name}.json").write_text(
            json.dumps(c))
    for mix, m in MIXES.items():
        (root / "bench" / "traffic" / f"{mix}.json").write_text(json.dumps(m))
    cells = [("tiny-qwen3", "tiny-open"), ("tiny-qwen2", "tiny-closed")]
    base["configs"] = [{"name": f"tiny-{n}", "source": "test",
                        "file": f"bench/configs/tiny-{n}.json", "reduced": [],
                        "why": "test"} for n in ("qwen3", "qwen2")]
    base["workloads"] = []
    for cfg, mix in cells:
        name = f"{cfg}.{mix}"
        base["workloads"].append({"name": name, "config": cfg,
                                  "traffic": mix, "chips": 1, "why": "test"})
        (root / "bench" / "checks" / f"{name}.json").write_text(
            json.dumps({"max_logit_gap": 0.05}))
    for m in base["end_to_end"] + base["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(base))
    return root


def _run(root, name, seed=2**31 + 3, **kw):
    cell = spec.load_cell(root, name)
    return run.run_cell(cell, seed, 1.5, False, **kw)


@pytest.mark.parametrize("name", ["tiny-qwen3.tiny-open",
                                  "tiny-qwen2.tiny-closed"])
def test_load_driver_run_is_correct(root, name):
    res = _run(root, name)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    m = res["metrics"]
    for k in ("setup_s", "output_tok_s"):
        assert m[k]["value"] > 0, k
    assert res["device"]["platform"] == "cpu" and res["device"]["count"] == 1
    assert list(res)[-1] == "check"
    assert res["check"]["max_logit_gap"]["value"] <= 0.05
    assert res["check"]["checked_tokens"]["value"] >= 1


def _token_altered(build):
    def system(c, knobs, seed, **kw):
        eng = build(c, knobs, seed, **kw)
        orig, vocab = eng.sampler, int(c["vocab_size"])
        eng.sampler = lambda key, logits: (orig(key, logits) + 1) % vocab
        return eng
    return system


def _state_unchanged(build):
    """Each decode step hands back the KV storage it was given: the step's
    writes are lost."""
    import jax
    import jax.numpy as jnp

    def system(c, knobs, seed, **kw):
        eng = build(c, knobs, seed, **kw)
        orig = eng._decode_paged

        def stale(p, st, *a):
            keep = jax.tree_util.tree_map(jnp.copy, st)
            _, logits, tel = orig(p, st, *a)
            return keep, logits, tel
        eng._decode_paged = stale
        return eng
    return system


def _half_batch(build):
    """Each decode step computes the first half of the slots only: the rest
    take the first half's logits."""
    def system(c, knobs, seed, **kw):
        eng = build(c, knobs, seed, **kw)
        orig = eng._decode_paged

        def half(p, st, *a):
            st, logits, tel = orig(p, st, *a)
            h = logits.shape[0] // 2
            return st, logits.at[h:2 * h].set(logits[:h]), tel
        eng._decode_paged = half
        return eng
    return system


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_fault_in_the_timed_path_is_not_correct(root, fault):
    from bench.models import qwen_program
    res = _run(root, "tiny-qwen2.tiny-closed",
               system=fault(qwen_program.build_engine))
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > 0.05


@pytest.fixture(scope="module")
def control_root(tmp_path_factory):
    """A checkout with one cell: the decode cell's configuration at a width
    a CPU test can hold (the full vocabulary kept), its mix with fewer
    clients, and the decode cell's own checks file."""
    root = tmp_path_factory.mktemp("ctl")
    shutil.copytree(ROOT / "bench", root / "bench")
    base = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in base["workloads"]
                if w["name"] == "qwen3-1.7b.decode")
    conf = next(c for c in base["configs"] if c["name"] == cell["config"])
    c = json.loads((ROOT / conf["file"]).read_text())
    c.update(num_hidden_layers=4, hidden_size=128, intermediate_size=384,
             num_attention_heads=4, num_key_value_heads=2, head_dim=32)
    c["deployment"] = {"chips": 1, "tp": 1,
                       "engine": {"page_size": 16, "num_pages": 128}}
    (root / "bench" / "configs" / "ctl.json").write_text(json.dumps(c))
    m = json.loads((ROOT / "bench" / "traffic" /
                    f"{cell['traffic']}.json").read_text())
    m.update(clients=3, block=3, engine={"max_slots": 4, "max_len": 256},
             prompt_len={"median": 48, "sigma": 0.5, "min": 16, "max": 96},
             output_len={"median": 48, "sigma": 0.5, "min": 24, "max": 96})
    (root / "bench" / "traffic" / "ctlmix.json").write_text(json.dumps(m))
    shutil.copy(ROOT / "bench" / "checks" / f"{cell['name']}.json",
                root / "bench" / "checks" / "ctl.ctlmix.json")
    base["configs"] = [dict(conf, name="ctl", file="bench/configs/ctl.json")]
    base["workloads"] = [dict(cell, name="ctl.ctlmix", config="ctl",
                              traffic="ctlmix")]
    for mt in base["end_to_end"] + base["per_layer"]:
        mt.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(base))
    return root


@pytest.mark.parametrize("seed", [11, 12])
def test_control_fails_where_the_program_passes(control_root, seed):
    """The lower-precision control (float8 e4m3 operands), judged by the
    run's own comparison against the decode cell's own limit, comes out
    not correct where the program on the same tokens comes out correct."""
    cell = spec.load_cell(control_root, "ctl.ctlmix")
    limit = json.loads((ROOT / "bench" / "checks" /
                        "qwen3-1.7b.decode.json").read_text())
    res = run.run_cell(cell, seed, 4.0, False, control=True)
    assert res["check"]["checked_tokens"]["value"] > 0
    assert res["correct"], res["check"]
    ctl = res["control"]
    assert ctl["limit"] == limit["max_logit_gap"]
    assert ctl["correct"] is False, ctl
    assert ctl["max_logit_gap"] > ctl["limit"]


def test_four_chip_cell_runs_on_virtual_devices(tmp_path):
    """A tp=4 cell on four virtual CPU devices: the engine's mesh path and
    the reference's weights split over the four devices."""
    import os
    import subprocess
    code = f"""
import json, pathlib, shutil, sys
sys.path[:0] = [{str(ROOT)!r}, {str(ROOT / 'src')!r}]
from bench import run, spec
root = pathlib.Path({str(tmp_path)!r})
shutil.copytree({str(ROOT / 'bench')!r}, root / 'bench')
base = json.loads(pathlib.Path({str(ROOT / 'BENCHMARK.json')!r}).read_text())
c = json.loads((root / 'bench/configs/qwen2-7b-tp4.json').read_text())
c.update(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
         num_attention_heads=8, num_key_value_heads=4, vocab_size=1024)
c['deployment']['engine']['num_pages'] = 128
(root / 'bench/configs/t4.json').write_text(json.dumps(c))
m = json.loads((root / 'bench/traffic/decode.json').read_text())
m.update(clients=6, block=6, engine={{'max_slots': 8, 'max_len': 256}},
         prompt_len={{'median': 24, 'sigma': 0.5, 'min': 8, 'max': 64}},
         output_len={{'median': 8, 'sigma': 0.5, 'min': 4, 'max': 16}})
(root / 'bench/traffic/t4mix.json').write_text(json.dumps(m))
base['configs'] = [dict(base['configs'][0], name='t4', file='bench/configs/t4.json')]
base['workloads'] = [dict(name='t4.t4mix', config='t4', traffic='t4mix', chips=4, why='test')]
(root / 'BENCHMARK.json').write_text(json.dumps(base))
(root / 'bench/checks/t4.t4mix.json').write_text(json.dumps({{'max_logit_gap': 0.05}}))
res = run.run_cell(spec.load_cell(root, 't4.t4mix'), 77, 1.5, False)
print(json.dumps(res))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["correct"], res["check"]
    assert res["device"]["count"] == 4
    assert res["metrics"]["setup_s"]["value"] > 0
    assert res["check"]["checked_tokens"]["value"] >= 1

"""The Qwen3-MoE share configuration at a tiny width on the CPU: the
engine's logits against the plain reference (``bench/models/qwen_moe.py``),
prefill and then decode through the cache, and whole runs of a cell built
from the configuration file (``bench/run.py``), whose ``correct`` a planted
fault turns false.
"""
import json
import pathlib
import shutil
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import run, spec  # noqa: E402
from bench.models import qwen_moe as R  # noqa: E402
from bench.models import qwen_moe_program as SUT  # noqa: E402

CELL = "qwen3-moe-235b-a22b-ep16.decode-c128"
TINY = dict(num_hidden_layers=2, hidden_size=64, moe_intermediate_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=16,
            vocab_size=512, num_experts=4)
MIX = {"loop": "closed", "clients": 3, "requests": 64, "block": 3,
       "size_seed": 2,
       "prompt_len": {"median": 24, "sigma": 0.5, "min": 8, "max": 64},
       "output_len": {"median": 12, "sigma": 0.5, "min": 4, "max": 24},
       "engine": {"max_slots": 4, "max_len": 256, "prefill_chunk": 32}}
LIMIT = 0.05


def _tiny_config(dtype="bfloat16", first_expert=0):
    spec_ = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in spec_["workloads"] if w["name"] == CELL)
    conf = next(c for c in spec_["configs"] if c["name"] == cell["config"])
    c = json.loads((ROOT / conf["file"]).read_text())
    c.update(TINY, name="tiny-moe", torch_dtype=dtype)
    c["deployment"] = dict(c["deployment"], num_experts=16,
                           first_expert=first_expert,
                           engine={"page_size": 16, "num_pages": 96})
    return conf, c


def test_engine_logits_match_the_reference_through_the_cache():
    """Prefill (two chunks) and then decode through the paged cache: every
    row of logits the engine samples from equals the reference's full
    forward pass at that position."""
    _, c = _tiny_config("float32", first_expert=4)
    eng = SUT.build_engine(c, {"max_slots": 4, "max_len": 128,
                               "page_size": 16, "num_pages": 64,
                               "prefill_chunk": 32}, seed=2**33 + 5)
    assert eng.model.cfg.n_experts == 16
    assert eng.model.cfg.held_experts == 4
    rows = {}

    def recording(rid):
        def sample(key, logits):
            rows.setdefault(rid, []).append(np.asarray(logits)[:c["vocab_size"]])
            return jnp.argmax(logits[:c["vocab_size"]])
        return sample

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, c["vocab_size"], n) for n in (45, 9, 70)]
    for i, p in enumerate(prompts):
        eng.submit(p.tolist(), max_new_tokens=10, sampler=recording(i))
    done = sorted(eng.run_until_drained(), key=lambda r: r.rid)
    eng.close()
    assert eng.stats["moe_dropped_tokens"] == 0
    assert eng.stats["moe_remote_assignments"] > 0
    w = jax.jit(lambda k: R.make_weights(c, k))(R.seed_key(2**33 + 5))
    for r in done:
        seq = np.concatenate([r.prompt, r.output[:-1]]).astype(np.int32)
        h = R.hidden_states(c, w, jnp.asarray(seq))
        want = np.asarray(h @ w["head"].astype(jnp.float32))
        got = np.stack(rows[r.rid])
        assert got.shape == (len(r.output), c["vocab_size"])
        np.testing.assert_allclose(got, want[len(r.prompt) - 1:],
                                   rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout whose one cell is the share configuration at a tiny
    width, under a small closed-loop mix."""
    root = tmp_path_factory.mktemp("moe")
    shutil.copytree(ROOT / "bench", root / "bench")
    base = json.loads((ROOT / "BENCHMARK.json").read_text())
    conf, c = _tiny_config()
    (root / "bench" / "configs" / "tiny-moe.json").write_text(json.dumps(c))
    (root / "bench" / "traffic" / "tiny-mix.json").write_text(json.dumps(MIX))
    (root / "bench" / "checks" / "tiny-moe.tiny-mix.json").write_text(
        json.dumps({"max_logit_gap": LIMIT}))
    base["configs"] = [dict(conf, name="tiny-moe",
                            file="bench/configs/tiny-moe.json")]
    base["workloads"] = [{"name": "tiny-moe.tiny-mix", "config": "tiny-moe",
                          "traffic": "tiny-mix", "chips": 1, "why": "test"}]
    for m in base["end_to_end"] + base["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(base))
    return root


def _run(root, seed=2**31 + 3, **kw):
    cell = spec.load_cell(root, "tiny-moe.tiny-mix")
    return run.run_cell(cell, seed, 1.5, False, **kw)


def test_moe_cell_run_is_correct(root):
    engines = []

    def system(c, knobs, seed, **kw):
        engines.append(SUT.build_engine(c, knobs, seed, **kw))
        return engines[-1]
    res = _run(root, system=system)
    assert res["correct"], res["check"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["check"]["checked_tokens"]["value"] >= 1
    assert res["metrics"]["output_tok_s"]["value"] > 0
    s = engines[0].stats
    assert s["moe_dropped_tokens"] == 0
    assert s["moe_local_assignments"] + s["moe_remote_assignments"] == \
        s["moe_tokens_routed"] > 0


def _another_share(c, knobs, seed, **kw):
    """The engine serves the weights as if they were experts 4-7: the
    share the reference holds is not the one computed."""
    dep = dict(c["deployment"], first_expert=4)
    return SUT.build_engine(dict(c, deployment=dep), knobs, seed, **kw)


def test_moe_cell_with_another_share_is_not_correct(root):
    res = _run(root, system=_another_share)
    assert not res["correct"]
    assert res["check"]["max_logit_gap"]["value"] > LIMIT


def test_mfu_moe_reads_the_window(root):
    """The reader counts the held experts' assignments from the engine's
    counter and reads None without a trace or without the counter."""
    from bench.driver import Window
    read = spec.reader(root, "mfu.moe")
    cell = spec.load_cell(root, "tiny-moe.tiny-mix")
    win = Window(0.0, 1.0, [], [], [], [], {"moe_local_assignments": 10},
                 {"moe_local_assignments": 110}, [])
    run_ = {"cell": cell, "window": win, "chips": 1,
            "peaks": {"bf16_flops": 1e9}, "trace": {"busy_s": 0.5}}
    want = 100 * 100 * 6 * 64 * 32 / (0.5 * 1e9)
    assert read(run_) == pytest.approx(want)
    assert read(dict(run_, trace=None)) is None
    bare = Window(0.0, 1.0, [], [], [], [], {}, {}, [])
    assert read(dict(run_, window=bare)) is None

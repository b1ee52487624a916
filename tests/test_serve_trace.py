"""The serving engine in a profiler trace: ``serve.*`` host spans inside
each tick, and every engine program under its own ``jit_<name>``.

The engine is ticked under ``jax.profiler.trace`` on the CPU; the trace's
host plane holds the spans (with their stats) and, for each op the CPU ran,
the ``hlo_module`` it belongs to.  The four-device case runs in a
subprocess (the main pytest process keeps one device).
"""
import glob
import inspect
import json
import os
import textwrap

import jax
import pytest

from repro.configs import smoke_config
from repro.models.api import build_model
from repro.serve import ServeEngine
from tests.test_distributed import run_spmd

PAGED_SPANS = {"serve.tick", "serve.schedule", "serve.prefill", "serve.pages",
               "serve.upload", "serve.step", "serve.sample", "serve.commit"}
PROMPTS = ([5, 17, 33, 2, 9] * 7, [100, 200, 300], [7] * 20)


def read_trace(log_dir):
    """(``serve.*`` spans as (name, start, end, stats), the names of the
    HLO modules whose ops ran)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    spans, modules = [], set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
                if "hlo_module" in stats:
                    modules.add(stats["hlo_module"])
    return spans, modules


def _model(arch="qwen2-7b", **kw):
    cfg = smoke_config(arch).replace(remat="none", **kw)
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _traced(tmp_path, eng, max_new=6):
    with jax.profiler.trace(str(tmp_path)):
        for p in PROMPTS:
            eng.submit(p, max_new_tokens=max_new)
        done = eng.run_until_drained()
    eng.close()
    assert all(r.error is None for r in done)
    return (*read_trace(str(tmp_path)), done)


def _inside_a_tick(spans):
    ticks = [(s, e) for n, s, e, _ in spans if n == "serve.tick"]
    for n, s, e, _ in spans:
        if n != "serve.tick":
            assert any(ts <= s and e <= te for ts, te in ticks), n


@pytest.mark.parametrize("spec", [None, "ngram"])
def test_paged_tick_spans_and_program_names(tmp_path, spec):
    model, params = _model()
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=True,
                      page_size=8, prefill_chunk=16, spec_decode=spec)
    spans, modules, done = _traced(tmp_path, eng)
    names = {n for n, *_ in spans}
    assert PAGED_SPANS <= names
    _inside_a_tick(spans)
    # ticks are numbered in order, one span each
    nums = [st["tick"] for n, _, _, st in sorted(spans, key=lambda x: x[1])
            if n == "serve.tick"]
    assert nums == list(range(nums[0], nums[0] + len(nums)))
    # a request's prefill spans carry its id and cover its prompt in order
    for r in done:
        mine = sorted((st["start"], st["n"]) for n, _, _, st in spans
                      if n == "serve.prefill" and st["rid"] == r.rid)
        assert [s for s, _ in mine] == list(range(0, len(r.prompt), 16))
        assert sum(n for _, n in mine) == len(r.prompt)
    # each step span carries the pages its attention reads
    live = [st["live_pages"] for n, _, _, st in spans if n == "serve.step"]
    assert sum(live) == eng.stats["decode_live_pages"] > 0
    step = "jit_verify_paged" if spec else "jit_decode_paged"
    assert {step, "jit_prefill_chunk", "jit_lm_head"} <= modules
    assert not any("lambda" in m for m in modules), modules


def test_dense_tick_spans_and_program_names(tmp_path):
    model, params = _model()
    eng = ServeEngine(model, params, max_slots=2, max_len=128, paged=False)
    spans, modules, _ = _traced(tmp_path, eng)
    assert {"serve.tick", "serve.step", "serve.sample", "serve.commit"} <= \
        {n for n, *_ in spans}
    _inside_a_tick(spans)
    assert {"jit_decode", "jit_prefill", "jit_lm_head"} <= modules
    assert not any("lambda" in m for m in modules), modules


def test_tp_programs_carry_the_same_names(tmp_path):
    """The shard_map (tp=4) branches name their programs as the one-device
    engine does."""
    out = run_spmd(textwrap.dedent("""
        import glob, json, os
        from repro.configs import smoke_config
        from repro.models.api import build_model
        from repro.serve import ServeEngine
        """) + inspect.getsource(read_trace) + textwrap.dedent(f"""
        cfg = smoke_config("qwen2-7b").replace(remat="none", n_heads=8,
                                               n_kv_heads=4)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        eng = ServeEngine(model, params, max_slots=2, max_len=64, paged=True,
                          page_size=8, prefill_chunk=16,
                          mesh=make_mesh((4,), ("model",)))
        with jax.profiler.trace({str(tmp_path)!r}):
            eng.submit([5, 17, 33, 2, 9] * 4, max_new_tokens=4)
            done = eng.run_until_drained()
        assert all(r.error is None for r in done)
        spans, modules = read_trace({str(tmp_path)!r})
        assert "serve.step" in {{n for n, *_ in spans}}
        print(json.dumps(sorted(modules)))
        """), n_devices=4)
    modules = json.loads(out.strip().splitlines()[-1])
    assert {"jit_decode_paged", "jit_prefill_chunk", "jit_lm_head"} <= \
        set(modules)
    assert not any("lambda" in m for m in modules), modules

"""CPU tests of the reduction of the engine's own spans and program names
(bench/spans.py): hand-computed answers on a synthetic event list, a trace
written by the profiler here, and the recorded v5e traces."""
import gzip
import json
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import spans as SP  # noqa: E402
from bench import trace as TR  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"


def _synthetic():
    """Three ticks; the host clock reads the device's + 10 (+ 12 where the
    host woke later).  Device idle (device clock): [0,100) [300,320)
    [380,500) [700,800) [950,1000)."""
    dev = "/device:TPU:0"
    return {
        "devices": {dev: [["fusion.1", 100, 200], ["fusion.2", 320, 60],
                          ["fusion.1", 500, 200], ["fusion.1", 800, 150]]},
        "modules": {dev: [["jit_decode_paged(11)", 100, 200],
                          ["jit_prefill_chunk(12)", 320, 60],
                          ["jit_decode_paged(11)", 500, 200],
                          ["jit_decode_paged(11)", 800, 150]]},
        "host": [["bench.window", 0, 1000]],
        "spans": [
            ["serve.tick", 50, 270, {"tick": 1}],
            ["serve.schedule", 60, 20, {}],
            ["serve.step", 85, 15, {}],
            ["serve.sample", 100, 210, {}],
            ["serve.commit", 310, 8, {}],
            ["serve.tick", 330, 390, {"tick": 2}],
            ["serve.prefill", 335, 60, {"rid": 7, "start": 0, "n": 64}],
            ["serve.pages", 400, 20, {}],
            ["serve.upload", 420, 30, {}],
            ["serve.step", 450, 55, {}],
            ["serve.sample", 505, 207, {}],
            ["serve.commit", 712, 7, {}],
            ["serve.tick", 740, 225, {"tick": 3}],
            ["serve.step", 745, 60, {}],
            ["serve.sample", 805, 157, {}],
        ],
    }


def test_reduce_synthetic_by_hand():
    ev = _synthetic()
    sp = SP.reduce(ev, n_chips=1)
    assert sp["ticks"] == 3
    # sample ends 310, 712, 962 against step ends 300, 700, 950
    assert sp["clock_offset_ms"] == pytest.approx(10e-6)
    assert sp["module_s"] == pytest.approx(
        {"jit_decode_paged": 550e-9, "jit_prefill_chunk": 60e-9})
    assert sp["decode_step_ms"] == pytest.approx(200e-6)
    # idle moved by +10 onto the host clock; [390,510) splits across the
    # prefill, the tick itself, pages, upload, step and sample
    assert sp["idle_in_tick"] == pytest.approx({
        "serve.tick": 31e-9, "serve.schedule": 20e-9, "serve.step": 130e-9,
        "serve.sample": 24e-9, "serve.commit": 15e-9,
        "serve.prefill": 5e-9, "serve.pages": 20e-9, "serve.upload": 30e-9})
    # what lies outside the ticks is the rest of the window's idle time
    tr = TR.reduce(ev, n_chips=1)
    outside = 40 + 10 + 20 + 45
    assert sum(sp["idle_in_tick"].values()) == pytest.approx(
        tr["window_s"] - tr["busy_s"] - outside * 1e-9)
    got = SP.summary(sp, tr["busy_s"])
    assert got["decode_step_ms"] == pytest.approx(200e-6)
    assert got["prefill_share"] == pytest.approx(100 * 60 / 610)
    assert got["tick_idle_ms"] == pytest.approx(275e-9 / 3 * 1e3)


def test_reduce_clips_to_the_window_and_averages_chips():
    ev = _synthetic()
    ev["host"] = [["bench.window", 400, 600]]          # [400, 1000)
    ev["devices"]["/device:TPU:1"] = [["fusion.1", 500, 100]]
    ev["modules"]["/device:TPU:1"] = [["jit_decode_paged(11)", 500, 100]]
    sp = SP.reduce(ev, n_chips=2)
    # chip 0: decode runs 200 and 150 start inside, median 175; chip 1: 100
    assert sp["decode_step_ms"] == pytest.approx((175 + 100) / 2 * 1e-6)
    assert sp["module_s"] == pytest.approx({"jit_decode_paged": 225e-9})
    assert sp["ticks"] == 1                             # ticks starting in it
    with pytest.raises(RuntimeError):
        SP.reduce(ev, n_chips=3)


def test_clock_offset_skips_a_host_stall():
    """The second tick's host woke 300 after its step ended, nearer the
    third step than its own: that pair is not mutual and drops out."""
    assert SP._clock_offset([310, 1000, 1112], [300, 700, 1100]) == 10
    assert SP._clock_offset([310], []) is None


def test_innermost_nesting():
    spans = [("a", 0, 100), ("b", 0, 50), ("c", 10, 10), ("d", 50, 50)]
    assert SP._innermost(spans) == [("b", 0, 10), ("c", 10, 10),
                                    ("b", 20, 30), ("d", 50, 50)]


def test_program_without_spans_reads_nothing():
    """The recorded trace of a program with unnamed jits and no spans: the
    three numbers are None, nothing raises."""
    with open(DATA / "trace_v5e_small.json") as f:
        ev = json.load(f)
    ticks = [h for h in ev["host"] if h[0] == "bench.tick"]
    lo, hi = ticks[0][1], ticks[-1][1] + ticks[-1][2]
    ev["host"].append(["bench.window", lo, hi - lo])
    sp = SP.reduce(ev, n_chips=1)
    assert list(sp["module_s"]) == ["jit__lambda"]
    assert sp["ticks"] == 0 and sp["idle_in_tick"] == {}
    assert sp["clock_offset_ms"] is None
    busy = TR.reduce(ev, n_chips=1)["busy_s"]
    assert SP.summary(sp, busy) == {"decode_step_ms": None,
                                    "prefill_share": None,
                                    "tick_idle_ms": None}


def test_load_keeps_serve_spans_with_stats(tmp_path):
    import jax
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.tick", tick=4):
                with jax.profiler.TraceAnnotation("serve.prefill", rid=7,
                                                  start=16, n=9):
                    pass
            with jax.profiler.TraceAnnotation("other"):
                pass
    ev = SP.load(str(tmp_path))
    assert [h[0] for h in ev["host"]] == ["bench.window"]
    got = {n: st for n, _, _, st in ev["spans"]}
    assert got == {"serve.tick": {"tick": 4},
                   "serve.prefill": {"rid": 7, "start": 16, "n": 9}}


def _recorded_spans():
    """Three ticks of ``qwen3-1.7b.decode`` on a TPU v5e (the middle one
    runs two prefill chunks, the last one a prompt's last chunk, lm head
    and first token), in ``bench/trace.py``'s load format plus the
    ``serve.*`` spans; the window is the three ticks."""
    with gzip.open(DATA / "trace_v5e_decode_spans.json.gz", "rt") as f:
        return json.load(f)


def test_trace_reduce_of_named_programs_by_hand():
    """bench/trace.py's numbers on a trace of the named programs, held
    against the same hand computations as on the older recording."""
    ev = _recorded_spans()
    (lo, d), = [(s, d) for n, s, d in ev["host"] if n == "bench.window"]
    hi = lo + d
    tr = TR.reduce(ev, n_chips=1)
    assert tr["window_s"] == pytest.approx(d * 1e-9)
    busy, end = 0.0, -1.0
    for s, e in sorted((max(s, lo), min(s + d, hi))
                       for _, s, d in ev["devices"]["/device:TPU:0"]):
        if e <= s:
            continue
        if s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert tr["busy_s"] == pytest.approx(busy * 1e-9)
    assert sum(t for _, t in tr["idle_gaps"]) == \
        pytest.approx(tr["window_s"] - tr["busy_s"])
    top = [n for n, _ in tr["device_ops"]]
    assert all(n.startswith("jit_decode_paged(") for n in top[:3])
    assert not any("lambda" in n for n in top)
    assert tr["collective_s"] == 0.0


def test_spans_reduce_recorded_v5e():
    ev = _recorded_spans()
    sp = SP.reduce(ev, n_chips=1)
    tr = TR.reduce(ev, n_chips=1)
    assert sp["ticks"] == 3
    steps = sorted(d for n, _, d in ev["modules"]["/device:TPU:0"]
                   if n.startswith("jit_decode_paged("))
    assert sp["decode_step_ms"] == pytest.approx(steps[1] * 1e-6)
    assert {"jit_decode_paged", "jit_prefill_chunk", "jit_lm_head"} <= \
        set(sp["module_s"])
    # one request's chunks carry its id and tile its prompt
    chunks = sorted((st["start"], st["n"]) for n, _, _, st in ev["spans"]
                    if n == "serve.prefill" and st["rid"] == 36)
    assert chunks == [(0, 64), (64, 64), (128, 40)]
    # the host reads the device's clock plus about a millisecond
    off = sp["clock_offset_ms"]
    assert 0 < off < 5
    # idle inside ticks is the bench.tick idle, less what moving the idle
    # intervals by the offset carries past either end of the window
    got = sum(sp["idle_in_tick"].values())
    tick_idle = dict(tr["idle_gaps"])["bench.tick"]
    assert tick_idle - 2 * off * 1e-3 <= got <= \
        tr["window_s"] - tr["busy_s"]
    assert {"serve.upload", "serve.step", "serve.sample",
            "serve.prefill"} <= set(sp["idle_in_tick"])
    got = SP.summary(sp, tr["busy_s"])
    assert got["tick_idle_ms"] == pytest.approx(
        1e3 * sum(sp["idle_in_tick"].values()) / 3)
    assert 0 < got["prefill_share"] < 100


def test_cli_reads_an_operator_trace(monkeypatch, capsys):
    """An operator's trace has no ``bench.window``: the command line reads
    it from the first ``serve.tick`` to the last, the window the recording
    was cut to, and prints both reductions and the summary."""
    ev = _recorded_spans()
    want_tr = TR.reduce(ev, n_chips=1)
    want_sp = SP.reduce(ev, n_chips=1)
    ev["host"] = [h for h in ev["host"] if h[0] != "bench.window"]
    monkeypatch.setattr(SP, "load", lambda d: ev)
    assert SP.main(["some-trace-dir"]) == 0
    got = json.loads(capsys.readouterr().out)
    assert got["window_s"] == pytest.approx(want_tr["window_s"])
    assert got["busy_s"] == pytest.approx(want_tr["busy_s"])
    assert got["ticks"] == want_sp["ticks"] == 3
    assert got["decode_step_ms"] == pytest.approx(want_sp["decode_step_ms"])
    assert got["summary"] == pytest.approx(SP.summary(want_sp,
                                                      want_tr["busy_s"]))

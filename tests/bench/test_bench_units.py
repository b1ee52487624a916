"""CPU tests of the benchmark's own arithmetic and file lookup (bench/)."""
import json
import os
import pathlib
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from bench import driver as D  # noqa: E402
from bench import flops, peaks, spec, stats  # noqa: E402
from bench import trace as TR  # noqa: E402
from bench.loadgen import RequestSpec, Traffic  # noqa: E402

DATA = pathlib.Path(__file__).parent / "data"


def _config(name):
    with open(ROOT / "bench" / "configs" / f"{name}.json") as f:
        return json.load(f)


def _mix(name):
    with open(ROOT / "bench" / "traffic" / f"{name}.json") as f:
        return json.load(f)


# -- nearest rank and spread ---------------------------------------------------

@pytest.mark.parametrize("q", [50, 90, 95, 99])
def test_nearest_rank_matches_the_program_copy(q):
    from repro.serve.metrics import nearest_rank
    rng = np.random.default_rng(q)
    for n in (1, 2, 7, 10, 101):
        xs = list(rng.exponential(size=n))
        assert stats.nearest_rank(xs, q) == nearest_rank(xs, q)
    assert stats.nearest_rank([], q) is None


def test_nearest_rank_hand_values():
    xs = [5.0, 1.0, 4.0, 2.0, 3.0, 10.0, 9.0, 8.0, 7.0, 6.0]
    assert stats.nearest_rank(xs, 50) == 5.0
    assert stats.nearest_rank(xs, 90) == 9.0
    assert stats.nearest_rank(xs, 95) == 10.0


def test_spread_uses_statistics_quartiles():
    vals = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    # statistics.quantiles (exclusive): q1 = 10.75, median 12.5, q3 = 14.25
    assert stats.spread(vals) == pytest.approx(3.5 / 12.5)


# -- peaks -----------------------------------------------------------------------

def test_peaks_v5e():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16 * 2**30


@pytest.mark.parametrize("kind", ["TPU v4", "cpu", "TPU v5", ""])
def test_peaks_refuse_unknown_device_kind(kind):
    with pytest.raises(KeyError):
        peaks.peaks_for(kind)


# -- operation counts against hand counts ------------------------------------------

# per layer: d*(hq + 2*hkv)*hd + hq*hd*d + 3*d*f
HAND = {
    "qwen3-1.7b": dict(layer=2048 * 32 * 128 + 16 * 128 * 2048 + 3 * 2048 * 6144,
                       L=28, attn=4 * 16 * 128, head=2 * 2048 * 151936),
    "qwen2-7b-tp4": dict(layer=3584 * 36 * 128 + 28 * 128 * 3584
                         + 3 * 3584 * 18944,
                         L=28, attn=4 * 28 * 128, head=2 * 3584 * 152064),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_token_flops_hand_count(name):
    c, h = _config(name), HAND[name]
    assert flops.layer_matmul_params(c) == h["layer"]
    assert h["layer"] == {"qwen3-1.7b": 50_331_648,
                          "qwen2-7b-tp4": 233_046_016}[name]
    got = flops.token_flops(c, 1000, head=True)
    assert got == h["L"] * (2 * h["layer"] + h["attn"] * 1000) + h["head"]
    assert flops.token_flops(c, 1, head=False) == \
        h["L"] * (2 * h["layer"] + h["attn"])


@pytest.mark.parametrize("name", sorted(HAND))
def test_prefill_flops_is_the_sum_of_its_tokens(name):
    c = _config(name)
    start, end = 48, 300
    want = sum(flops.token_flops(c, p + 1, head=False)
               for p in range(start, end)) + HAND[name]["head"]
    assert flops.prefill_flops(c, start, end) == pytest.approx(want, rel=1e-12)
    assert flops.prefill_flops(c, 10, 10) == 0.0


# -- traffic -------------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["chat", "decode"])
def test_traffic_same_sizes_for_every_seed(mix):
    m = _mix(mix)
    a, b = Traffic(m, 7, 1000), Traffic(m, 2**31 + 11, 1000)
    block = m["block"]
    for lo in range(0, len(a), block):
        sa = sorted(a._specs[lo:lo + block])
        assert sa == sorted(b._specs[lo:lo + block])
    assert (a._specs != b._specs) == (block > 1)  # another order
    assert sorted(a.gaps) == pytest.approx(sorted(b.gaps))
    max_len = m["engine"]["max_len"]
    for i in range(len(a)):
        s = a.spec(i)
        assert s.prompt_len + s.output_len <= max_len - 2
        assert s.output_len >= 1 and s.tail_len >= 1


def test_traffic_tokens_follow_the_seed():
    m = _mix("chat")
    a, b, c = Traffic(m, 5, 151936), Traffic(m, 5, 151936), Traffic(m, 6, 151936)
    assert np.array_equal(a.prompt(3), b.prompt(3))
    assert not np.array_equal(a.session_prompt(0), c.session_prompt(0))
    s = a.spec(3)
    p = a.prompt(3)
    assert len(p) == s.prompt_len
    assert np.array_equal(p[:s.prefix_len], a.session_prompt(s.session))
    assert p.min() >= 0 and p.max() < 151936
    d = a.due_offsets(1200)
    assert np.all(np.diff(d) >= 0) and len(d) == 1200


# -- window accounting ----------------------------------------------------------------

class _Req:
    def __init__(self, n):
        self.output = list(range(n))


def _rec(due, times, done=None):
    spec_ = RequestSpec(0, -1, 0, 10, len(times) or 5)
    r = D.Record(spec_, -1, due, due, _Req(len(times)))
    r.token_times = list(times)
    r.done = done
    return r


def test_window_samples_censor_and_count():
    recs = [
        _rec(-1.0, [0.5, 1.0, 1.5]),         # due before the window
        _rec(1.0, [1.3, 1.6, 11.0]),         # last token after the close
        _rec(2.0, []),                       # no first token: censored
        _rec(9.0, [10.5]),                   # first token after the close
        _rec(10.5, []),                      # due after the close
    ]
    w = D.Window(t0=0.0, t1=10.0, records=recs, tick_live=[], tick_s=[],
                 tick_prefill=[], stats0={}, stats1={}, lateness=[])
    s = D.window_samples(w)
    assert sorted(s["ttft"]) == pytest.approx([0.3, 1.0, 8.0])
    # tokens in (0, 10]: 0.5, 1.0, 1.5, 1.3, 1.6
    assert s["tokens"] == 5
    # gaps whose later token is in the window
    assert sorted(s["itl"]) == pytest.approx([0.3, 0.5, 0.5])


@pytest.mark.parametrize("seed", [5, 2**33 + 7])
def test_check_sample_covers_slots_before_repeating(seed):
    from bench import check
    recs = []
    for i in range(40):                     # 8 slots, 5 finished requests each
        r = _rec(0.0, [1.0] * (4 + i % 7), done=2.0 + i * 0.1)
        r.spec = RequestSpec(i, -1, 0, 10, 4 + i % 7)
        r.slot = i % 8
        recs.append(r)
    late = _rec(0.0, [1.0] * 90, done=20.0)  # finished after the close
    late.slot = 0
    got = check.sample(recs + [late], 0.0, 10.0, seed, n=12)
    assert len(got) == 12 and late not in got
    assert max(recs, key=lambda r: len(r.req.output)) is got[0]
    assert sorted(r.slot for r in got[:8]) == list(range(8))
    assert check.sample(recs, 0.0, 10.0, seed, n=12) == got
    assert check.within(0.1, 0.15) and not check.within(0.2, 0.15)
    assert not check.within(None, 0.15)


# -- trace reduction on a trace recorded on a TPU v5e -----------------------------------

def _recorded():
    with open(DATA / "trace_v5e_small.json") as f:
        ev = json.load(f)
    ticks = [h for h in ev["host"] if h[0] == "bench.tick"]
    lo = ticks[0][1]
    hi = ticks[-1][1] + ticks[-1][2]
    ev["host"].append(["bench.window", lo, hi - lo])
    return ev, lo, hi


def test_trace_reduce_recorded_v5e():
    ev, lo, hi = _recorded()
    tr = TR.reduce(ev, n_chips=1)
    assert tr["window_s"] == pytest.approx((hi - lo) * 1e-9)
    # hand: union of the XLA ops clipped to the window
    ops = sorted((max(s, lo), min(s + d, hi))
                 for _, s, d in ev["devices"]["/device:TPU:0"])
    busy, end = 0.0, -1.0
    for s, e in ops:
        if e <= s:
            continue
        if s > end:
            busy += e - s
            end = e
        elif e > end:
            busy += e - end
            end = e
    assert tr["busy_s"] == pytest.approx(busy * 1e-9)
    assert 0 < tr["busy_s"] < tr["window_s"]
    assert sum(t for _, t in tr["idle_gaps"]) == \
        pytest.approx(tr["window_s"] - tr["busy_s"])
    names = dict(tr["idle_gaps"])
    assert set(names) <= {"bench.tick", "outside"}
    top = tr["device_ops"]
    assert top[0][0].endswith("/fusion") or top[0][0].endswith(
        "/convolution_tanh_fusion")
    assert all(a[1] >= b[1] for a, b in zip(top, top[1:]))
    assert tr["collective_s"] == 0.0


def test_trace_reduce_needs_one_window_and_enough_chips():
    ev, _, _ = _recorded()
    with pytest.raises(RuntimeError):
        TR.reduce(ev, n_chips=4)
    ev["host"] = [h for h in ev["host"] if h[0] != "bench.window"]
    with pytest.raises(RuntimeError):
        TR.reduce(ev, n_chips=1)


def test_trace_collectives_counted():
    ev = {"devices": {"/device:TPU:0": [["fusion.1", 0, 100],
                                        ["all-reduce.3", 100, 50],
                                        ["all-gather-start", 200, 25]],
                      "/device:TPU:1": [["fusion.1", 0, 80]]},
          "modules": {},
          "host": [["bench.window", 0, 400], ["bench.tick", 0, 300]]}
    tr = TR.reduce(ev, n_chips=2)
    assert tr["collective_s"] == pytest.approx(75e-9)
    assert tr["busy_s"] == pytest.approx((175 + 80) / 2 * 1e-9)
    assert dict(tr["idle_gaps"]) == pytest.approx(
        {"bench.tick": 125e-9, "outside": 100e-9})


# -- lookup by name; a cell added as files only ------------------------------------------

def _tmp_bench(tmp_path):
    """A checkout with the real metrics plus a dummy mix, configuration,
    limit file and per-layer metric that exist only as new files."""
    root = tmp_path / "co"
    shutil.copytree(ROOT / "bench", root / "bench")
    spec_ = json.loads((ROOT / "BENCHMARK.json").read_text())
    (root / "bench" / "traffic" / "dummy-mix.json").write_text(
        json.dumps(dict(_mix("decode"), clients=2)))
    (root / "bench" / "configs" / "dummy-config.json").write_text(
        json.dumps(_config("qwen3-1.7b")))
    (root / "bench" / "checks" / "dummy-config.dummy-mix.json").write_text(
        json.dumps({"max_logit_gap": 0.5}))
    (root / "bench" / "metrics" / "dummy_count.py").write_text(
        "def read(run):\n    return 42.0\n")
    spec_["configs"].append({"name": "dummy-config", "source": "x",
                             "file": "bench/configs/dummy-config.json",
                             "reduced": [], "why": "test"})
    spec_["workloads"].append({"name": "dummy-config.dummy-mix",
                               "config": "dummy-config",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    spec_["per_layer"].append({"name": "dummy_count", "unit": "n",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "setup_s",
                               "workloads": ["dummy-config.dummy-mix"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec_))
    return root


def test_cells_and_metrics_found_by_name(tmp_path):
    root = _tmp_bench(tmp_path)
    cell = spec.load_cell(root, "dummy-config.dummy-mix")
    assert cell.mix["clients"] == 2 and cell.chips == 1
    assert cell.config["hidden_size"] == 2048
    assert cell.limits == {"max_logit_gap": 0.5}
    e2e = [m["name"] for m in cell.end_to_end]
    assert e2e == ["setup_s"]               # the others list their cells
    assert [m["name"] for m in cell.per_layer] == ["dummy_count"]
    assert spec.reader(root, "dummy_count")({}) == 42.0
    # every metric the real benchmark lists has a reader file
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in real["end_to_end"] + real["per_layer"]:
        assert callable(spec.reader(ROOT, m["name"]))
    for w in real["workloads"]:
        c = spec.load_cell(ROOT, w["name"])
        assert c.limits["max_logit_gap"] > 0
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell")


def test_every_per_layer_metric_moves_a_reported_metric():
    real = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = [w["name"] for w in real["workloads"]]
    e2e = {m["name"]: m.get("workloads", cells) for m in real["end_to_end"]}
    for m in real["per_layer"]:
        for cell in m.get("workloads", cells):
            assert cell in e2e[m["moves"]], (m["name"], cell)


# -- the command refuses to run without a TPU ------------------------------------------------

def test_no_tpu_refusal_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "qwen3-1.7b.decode",
         "--seed", "2147483659", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    for line in p.stdout.splitlines():
        assert '"metrics"' not in line


def test_import_loads_no_tpu_library():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = ("import sys; sys.path[:0] = ['.', 'src']\n"
            "import bench.run, bench.trace, bench.driver, bench.check\n"
            "import bench.models.qwen, bench.models.qwen_program\n"
            "assert not [m for m in sys.modules if 'libtpu' in m], sys.modules\n")
    p = subprocess.run([sys.executable, "-c", code], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr

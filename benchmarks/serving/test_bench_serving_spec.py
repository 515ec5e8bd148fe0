"""Every configuration, cell and metric file loads by name and agrees with
BENCHMARK.json; a file added in another directory loads the same way;
the traffic generator gives every seed the same sizes."""
import json
import pathlib

import numpy as np
import pytest

import bench_serving_testkit as kit
import spec
import workload

HERE = pathlib.Path(__file__).resolve().parent
BENCH = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())


def _stems(sub, suffix):
    return sorted(p.name[:-len(suffix)] for p in (HERE / sub).glob(
        f"*{suffix}"))


@pytest.mark.parametrize("name", _stems("configs", ".json"))
def test_config_loads(name):
    c = spec.load_config(name)
    assert c["name"] == name
    assert set(c["reduced"]) <= set(c) - {"reduced"}


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_cell_loads(name):
    cell = spec.load_cell(name)
    spec.load_config(cell["config"])
    assert cell["requests"] % cell["strata"] == 0


@pytest.mark.parametrize("name", _stems("metrics", ".py"))
def test_metric_loads(name):
    mod = spec.load_metric(name)
    assert mod.SOURCE in ("device_trace", "program_span", "program_counter",
                          "host_clock")


def test_benchmark_json_names_existing_files():
    assert BENCH["paths"] == ["benchmarks/serving"]
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        loaded = spec.load_config(c["name"])
        assert c["file"] == f"benchmarks/serving/configs/{c['name']}.json"
        assert c["source"] == loaded["source"]
        assert c["reduced"] == loaded["reduced"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for w in BENCH["workloads"]:
        cell = spec.load_cell(w["name"])
        assert w["config"] == cell["config"] in configs
        assert spec.cell_metrics(BENCH, w["name"], False)
        assert spec.cell_metrics(BENCH, w["name"], True)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert (mod.UNIT, mod.SOURCE) == (m["unit"], m["source"])
    for m in BENCH["per_layer"]:
        mod = spec.load_metric(m["name"])
        assert (mod.LAYER, mod.MOVES) == (m["layer"], m["moves"])
        assert m["moves"] in e2e


def test_peaks_known_and_unknown_device():
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    with pytest.raises(spec.SpecError):
        spec.load_peaks("cpu")


def test_files_added_elsewhere_load_by_name(tmp_path):
    base = kit.tiny_bench(tmp_path)
    (base / "metrics" / "tokens_total.py").write_text(
        'NAME = "tokens_total"\nUNIT = "tokens"\nLAYER = "engine"\n'
        'MOVES = "output_tok_s"\nSOURCE = "program_counter"\n'
        'def compute(record):\n    return 7\n')
    assert spec.load_config("tiny_dense", base)["hidden_size"] == 128
    assert spec.load_cell("tiny_dense.mixed", base)["config"] == "tiny_dense"
    assert spec.load_metric("tokens_total", base).compute({}) == 7
    with pytest.raises(spec.SpecError):
        spec.load_config("tiny_dense")          # not in the real directory
    with pytest.raises(spec.SpecError):
        spec.load_cell("no/such")


def test_cell_metrics_follow_workload_lists():
    bench = {"end_to_end": [{"name": "a"}, {"name": "b", "workloads": ["x"]}],
             "per_layer": [{"name": "p", "moves": "b"},
                           {"name": "q", "moves": "a", "workloads": ["y"]}]}
    assert spec.cell_metrics(bench, "x", False) == ["a", "b"]
    assert spec.cell_metrics(bench, "y", False) == ["a"]
    assert spec.cell_metrics(bench, "x", True) == ["p"]
    assert spec.cell_metrics(bench, "y", True) == ["q"]


@pytest.mark.parametrize("name", _stems("traffic", ".json"))
def test_every_seed_gets_the_same_sizes(name):
    cell = spec.load_cell(name)
    c = spec.load_config(cell["config"])
    gen = spec.load_generator(cell["arrivals"]["kind"])
    a = workload.plan(cell, c, 3, gen)
    b = workload.plan(cell, c, 2**40 + 11, gen)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    if "order_seed" in cell:                 # one schedule, other tokens
        assert [len(r.prompt) for r in a] == [len(r.prompt) for r in b]
        assert [r.gap_s for r in a] == [r.gap_s for r in b]
        assert any((r.prompt != q.prompt).any() for r, q in zip(a, b))
    else:
        assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    if not gen.CLOSED:
        assert sorted(r.gap_s for r in a) == sorted(r.gap_s for r in b)
    for r in a:
        assert len(r.prompt) + r.max_new <= c["engine"]["max_seq"]
        assert 0 <= r.prompt.min() and r.prompt.max() < c["vocab_size"]


def test_strata_spread_every_block():
    rng = np.random.default_rng(0)
    vals = np.arange(64)
    out = workload.stratified(vals, 16, rng)
    assert sorted(out) == list(vals)
    for b in range(4):
        assert sorted(out[16 * b:16 * b + 16] // 4) == list(range(16))


def test_output_strata_give_every_block_the_same_spread():
    cell = dict(kit.TINY_CELL, strata=8, strata_by="output_len")
    gen = spec.load_generator("closed")
    blocks = []
    for seed in (1, 2**40 + 3):
        out = [r.max_new for r in workload.plan(cell, kit.TINY_CONFIG, seed,
                                                gen)]
        blocks.append(sorted(out[:8]))
    octiles = np.sort(workload.lengths(cell["output_len"], 1024))
    for first in blocks:
        for j, v in enumerate(first):       # one answer from each octile
            assert octiles[128 * j] <= v <= octiles[128 * j + 127]


def test_prefix_sessions_share_their_heads():
    cell = dict(kit.TINY_CELL, prefix={"count": 4, "tokens": 32,
                                       "zipf_a": 1.1})
    c = kit.TINY_CONFIG
    gen = spec.load_generator("closed")
    reqs = workload.plan(cell, c, 5, gen)
    heads = {tuple(r.prompt[:32]) for r in reqs}
    assert len(heads) == 4
    first = [tuple(r.prompt[:32]) for r in reqs]
    top = max(heads, key=first.count)
    assert first.count(top) > len(reqs) / 4


def test_open_loop_rate():
    gen = spec.load_generator("poisson")
    cell = dict(kit.TINY_CELL, arrivals={"kind": "poisson", "rate_rps": 4.0})
    reqs = workload.plan(cell, kit.TINY_CONFIG, 1, gen)
    mean_gap = np.mean([r.gap_s for r in reqs])
    assert abs(mean_gap - 0.25) < 0.01
    gen = spec.load_generator("gamma")
    cell = dict(kit.TINY_CELL, arrivals={"kind": "gamma", "rate_rps": 4.0,
                                         "cv": 2.0})
    gaps = np.array([r.gap_s for r in workload.plan(cell, kit.TINY_CONFIG,
                                                    1, gen)])
    assert abs(gaps.mean() - 0.25) < 0.02
    assert 1.6 < gaps.std() / gaps.mean() < 2.2

"""The per-family seam: a configuration names its family, whose module
(``families/<family>.py``) gives the weights' layout, the plain
reference's forward and the work counts, read from the directory the
configuration was loaded from.

Pinned: what the dense code read before it moved behind the seam (the
weights drawn, the reference's logits, the work counts, every metric on
the two chip recordings) reads the same after.  And a second family,
``testdata_families/tiny_moe.py`` (Mixtral's layout, tiny), joins a copy
of the benchmark as new files only and runs a cell to ``correct``, while
a program that drops routed rows comes out not correct."""
import dataclasses
import hashlib
import json
import pathlib
import shutil
import types

import numpy as np
import pytest

import bench
import bench_serving_testkit as kit
import harness
import reference
import spec
import trace_reduce
import weights
import work

HERE = pathlib.Path(__file__).resolve().parent
TOY = HERE / "testdata_families"
PEAKS = spec.load_peaks("TPU v5 lite")
SECONDS = 2.0


def _digest(arrays: dict) -> str:
    h = hashlib.sha256()
    for k in sorted(arrays):
        a = np.asarray(arrays[k])
        h.update(k.encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _write_config(base, **change):
    c = dict(kit.TINY_CONFIG, name="tiny_x", **change)
    c = {k: v for k, v in c.items() if v is not None}
    (base / "configs" / "tiny_x.json").write_text(json.dumps(c))


# ------------------------------------------------------------------ seam

@pytest.mark.parametrize("family", [None, "no_such_family", "lacking"])
def test_a_config_without_a_sound_family_is_refused(tmp_path, family):
    base = kit.tiny_bench(tmp_path)
    text = (base / "families" / "dense.py").read_text()
    (base / "families" / "lacking.py").write_text(
        text.replace("def prefill_chunk(", "def _prefill_chunk("))
    _write_config(base, family=family)
    with pytest.raises(spec.SpecError):
        spec.load_config("tiny_x", base)


def test_the_family_is_read_beside_the_config(tmp_path):
    """A copy's own ``families/dense.py`` serves the copy's configs."""
    base = kit.tiny_bench(tmp_path)
    dense = base / "families" / "dense.py"
    dense.write_text(dense.read_text().replace(
        "KEYS = ()", 'KEYS = ("copy_only_key",)'))
    spec.load_config("phi3_mini")
    with pytest.raises(spec.SpecError, match="copy_only_key"):
        spec.load_config("phi3_mini", base)


# ------------------------------------------------- what the seam keeps

@pytest.mark.parametrize("seed, digest", [
    (3, "a83e37b8387d85a5b1771c3f739601cc4ab670e225bc66952684eb2b1877e746"),
    (2**40 + 11,
     "06b70e2e1c0521852e3fb3f3364fdbd95edb9956fb1ffeba2ed0a529546efed3"),
])
def test_weights_are_drawn_as_before(seed, digest):
    assert _digest(weights.init(kit.TINY_CONFIG, seed)) == digest


@pytest.mark.parametrize("fp8, digest", [
    (False,
     "7c4abeb96ceb8da672f570b6a26007603e5ef121276cfac85a71066341f5d327"),
    (True,
     "7750917c7377abee6081a5896f07a60200284101b694275d11b942d0869188c4"),
])
def test_reference_logits_are_as_before(fp8, digest):
    c = kit.TINY_CONFIG
    w = weights.init(c, 7)
    toks = np.random.default_rng(4).integers(0, c["vocab_size"],
                                             (2, 40)).astype(np.int32)
    rows = np.stack([np.arange(0, 36, 3), np.arange(1, 37, 3)])
    logits = np.asarray(reference.forward_rows(w, c, toks,
                                               rows.astype(np.int32),
                                               fp8=fp8))
    assert hashlib.sha256(logits.tobytes()).hexdigest() == digest


#: (rows, ctx) -> decode_step; (start, valid, final) -> prefill_chunk
WORK = {
    "phi3_mini": {
        (1, 1): (7445151744, 7449356928),
        (8, 2400): (60501786624, 8422118400),
        (5, 7000): (39976304640, 10218296448),
        (0, 256, False): (1868361105408, 8306294784),
        (256, 100, True): (737024802816, 7958983296),
        (1024, 512, True): (3968847446016, 9964556928)},
    "deepseek7b_15l": {
        (1, 1): (6910361600, 6913170944),
        (8, 2400): (55870750720, 7522414592),
        (5, 7000): (36270899200, 8644482560),
        (0, 256, False): (1562325811200, 6735921152),
        (256, 100, True): (615496908800, 7232878592),
        (1024, 512, True): (3270445629440, 8491319296)},
}


@pytest.mark.parametrize("name, at", [(n, a) for n in WORK for a in WORK[n]])
def test_work_counts_are_as_before(name, at):
    c = spec.load_config(name)
    fn = work.decode_step if len(at) == 2 else work.prefill_chunk
    assert fn(c, *at) == WORK[name][at]


def _recordings():
    phi3 = spec.load_config("phi3_mini")
    data = HERE / "testdata"
    steps = json.loads((data / "phi3_decode_steps.json").read_text())
    run = {"t0": steps[0]["t0"], "t_end": steps[-1]["t1"], "steps": steps,
           "tracks": []}
    before = {"trace": trace_reduce.reduce(str(data), run), "serve": run,
              "config": phi3, "peaks": PEAKS}
    data = HERE / "testdata_spans"
    side = json.loads((data / "phi3_spans.json").read_text())
    tracks = [types.SimpleNamespace(req=types.SimpleNamespace(**r),
                                    arrival=r["t_submit"])
              for r in side["requests"]]
    run = {"t0": min(tr.arrival for tr in tracks),
           "t_end": side["steps"][-1]["t1"], "steps": side["steps"],
           "tracks": tracks}
    spans = {"trace": trace_reduce.reduce(str(data), run), "serve": run,
             "config": phi3, "peaks": PEAKS}
    return before, spans


#: metric -> (on testdata/, on testdata_spans/), as read before the seam
METRICS = {
    "admit_wait_p90_ms": (None, 1.0447029999980373),
    "chunk_turn_wait_p90_ms": (None, 1447.165777599993),
    "decode_batch_mean": (8.0, 7.333333333333333),
    "decode_step_ms": (132.3179075, 132.28334600000002),
    "device_idle_share": (2.297957803414119, 11.179878263304932),
    "host_gap_ms.decode": (3.1002924999894987, 3.3692204999992286),
    "matmul_roofline.decode": (18.82767489555296, 18.834955220577083),
    "matmul_roofline.prefill": (None, 11.141817486544465),
    "mfu.decode": (7.286077577628317, 7.073449343910223),
    "mfu.prefill": (None, 5.032686562845593),
    "own_prefill_p90_ms": (None, 153.5369478999968),
    "prefill_chunk_ms": (None, 134.007965),
    "queue_wait_p90_ms": (None, 1448.210480599991),
}


@pytest.fixture(scope="module")
def recordings():
    return _recordings()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metrics_on_the_recordings_are_as_before(recordings, name):
    reader = spec.load_metric(name)
    assert tuple(reader.compute(r) for r in recordings) == METRICS[name]


# ------------------------------------------ a second family, from a copy

def _moe_bench(tmp_path) -> pathlib.Path:
    base = kit.tiny_bench(tmp_path)
    shutil.copy(TOY / "tiny_moe.py", base / "families")
    shutil.copy(TOY / "tiny_moe.json", base / "configs")
    shutil.copy(TOY / "tiny_moe.mixed.json", base / "traffic")
    return base


def _run_moe(base, monkeypatch, seed=3):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    return bench.run_cell("tiny_moe.mixed", seed, SECONDS, False,
                          ["output_tok_s", "itl_p95_ms"], base=base)


def test_second_family_joins_a_copy_and_runs_correct(tmp_path, monkeypatch):
    base = _moe_bench(tmp_path)
    c = spec.load_config("tiny_moe", base)
    assert spec.family(c).__file__ == str(base.resolve() / "families"
                                         / "tiny_moe.py")
    assert spec.family(c).program_config(c).n_experts == 8
    out = _run_moe(base, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8


def test_second_family_dropping_routed_rows_is_not_correct(tmp_path,
                                                           monkeypatch):
    """Capacity at half the rows' share: each expert keeps ceil(N k / E /
    2) rows of a call's N and drops the rest."""
    base = _moe_bench(tmp_path)
    fam = spec.family(spec.load_config("tiny_moe", base))
    sound = fam.program_config
    monkeypatch.setattr(fam, "program_config", lambda c: dataclasses.replace(
        sound(c), capacity_factor=0.5))
    out = _run_moe(base, monkeypatch)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]

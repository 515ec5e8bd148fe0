"""The trace reduction on a trace recorded on the chip: two decode-only
steps of ``phi3_mini.decode_heavy`` (8 live slots) through the harness
on a TPU v5e, committed under ``testdata/``.

By hand, for phi3-mini (32 layers; q, k, v, o of 3072 x 3072, gate and
up of 3072 x 8192, down of 8192 x 3072; the head of 3072 x 32064, held
padded to 32256 rows): each decode step computes 7 x 32 + 1 = 225
projections, and the reduction must find exactly those, whichever ops
the compiler wrapped around them."""
import collections
import json
import pathlib

import pytest

import spec
import trace_reduce
import work

HERE = pathlib.Path(__file__).resolve().parent
DATA = HERE / "testdata"
PHI3 = spec.load_config("phi3_mini")
PEAKS = spec.load_peaks("TPU v5 lite")


@pytest.fixture(scope="module")
def recorded():
    path = trace_reduce.xplane_path(str(DATA))
    devices, spans = trace_reduce.read(path)
    steps = json.loads((DATA / "phi3_decode_steps.json").read_text())
    run = {"t0": steps[0]["t0"], "t_end": steps[-1]["t1"], "steps": steps,
           "tracks": []}
    return devices, spans, run, trace_reduce.reduce(str(DATA), run)


def test_one_device_and_a_span_per_step(recorded):
    devices, spans, run, _ = recorded
    assert list(devices) == ["/device:TPU:0"]
    assert [k for k, _, _ in spans] == [s["k"] for s in run["steps"]]


def test_every_projection_found_once_per_step(recorded):
    _, _, _, tr = recorded
    want = collections.Counter({(3072, 3072): 4 * 32, (3072, 8192): 2 * 32,
                                (8192, 3072): 32, (3072, 32064): 1})
    weights = work.weight_map(PHI3)
    for k, ops in tr["ops"].items():
        found = collections.Counter(
            kn for kn in (trace_reduce.projection(o, weights) for o in ops)
            if kn)
        assert found == want, k
        # each step ran one decode program, and every projection in it
        assert len({o.module for o in ops
                    if trace_reduce.projection(o, weights)}) == 1


def test_loop_envelopes_are_not_work(recorded):
    devices, _, _, tr = recorded
    ops = devices["/device:TPU:0"]
    loops = [o for o in ops if trace_reduce.signature(o)
             and trace_reduce.signature(o)[0] == "while"]
    assert loops and not set(map(id, loops)) & set(
        map(id, trace_reduce.leaves(ops)))
    for name, _ in tr["breakdown"]["device_ops"]:
        assert "while" not in name
    assert len(tr["breakdown"]["device_ops"]) <= trace_reduce.TOP
    assert len(tr["breakdown"]["idle_gaps"]) <= trace_reduce.TOP


def test_busy_time_and_metrics(recorded):
    _, _, run, tr = recorded
    assert 0 < tr["busy_s"] <= tr["window_s"]
    record = {"trace": tr, "serve": run, "config": PHI3, "peaks": PEAKS}
    step_ms = spec.load_metric("decode_step_ms").compute(record)
    host_ms = 1e3 * (run["steps"][0]["t1"] - run["steps"][0]["t0"])
    assert 0 < step_ms <= host_ms
    idle = spec.load_metric("device_idle_share").compute(record)
    assert 0 <= idle < 100
    share = spec.load_metric("matmul_roofline.decode").compute(record)
    # by hand: the step's projections at 8 live rows, over the device time
    # of the 225 events that compute them
    weights = work.weight_map(PHI3)
    took = sum(o.dur for ops in tr["ops"].values() for o in ops
               if trace_reduce.projection(o, weights)) * 1e-9
    need = 2 * work.roofline_s(work.matmuls(PHI3, 8, 8), PEAKS)
    assert share == pytest.approx(100 * need / took)
    assert 0 < share <= 100
    # nothing of a prefill chunk was traced
    assert spec.load_metric("matmul_roofline.prefill").compute(record) is None
    assert spec.load_metric("prefill_chunk_ms").compute(record) is None

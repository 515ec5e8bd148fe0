"""The readers of what the program writes into the trace
(``trace_spans.py``) and the per-layer metrics that read the engine's
stamps and its host gap: on records made by hand, on the chip recording
of the program before it had scopes, spans and stamps (``testdata/``),
where each of these readings is nothing, and on a recording of the
program with them (``testdata_spans/``)."""
import json
import pathlib
import types

import pytest

import spec
import trace_reduce
import trace_spans

HERE = pathlib.Path(__file__).resolve().parent
DATA = HERE / "testdata"
PHI3 = spec.load_config("phi3_mini")
PEAKS = spec.load_peaks("TPU v5 lite")
#: the metrics that read the engine's request stamps
STAMPED = ("queue_wait_p90_ms", "admit_wait_p90_ms",
           "chunk_turn_wait_p90_ms", "own_prefill_p90_ms")


def named_share(leaves: list, names: dict[str, str]) -> float:
    """Share of the leaves' device time whose op_name is known, in %."""
    total = sum(op.dur for op in leaves)
    named = sum(op.dur for op in leaves if names.get(op.name))
    return 100.0 * named / total if total else 0.0


@pytest.mark.parametrize("op_name, scope", [
    ("jit(decode_step_paged)/while/body/attn.core/dot_general", "attn.core"),
    ("jit(decode_step_paged)/while/body/mlp/jit(matmul)/pallas_call", "mlp"),
    ("jit(prefill_chunk)/embed/gather", "embed"),
    ("jit(decode_step_paged)/while/body/dynamic_slice", trace_spans.LOOP),
    ("jit(decode_step_paged)/dot_general", trace_spans.OTHER),
    ("", None),
    (None, None),
])
def test_scope_of_an_op_name(op_name, scope):
    assert trace_spans.scope_of(op_name) == scope


def test_an_op_without_a_name_takes_the_next_named_ones_scope():
    ops = [trace_reduce.Op(n, i, 1) for i, n in enumerate(
        ["a", "cast.1", "cast.2", "b", "c", "cast.3"])]
    names = {"a": "jit(f)/while/body/attn.kv_write/scatter",
             "b": "jit(f)/while/body/attn.core/dot_general",
             "c": "jit(f)/head/dot_general"}
    assert trace_spans.scopes(ops, names) == [
        "attn.kv_write", "attn.core", "attn.core", "attn.core", "head",
        None]


@pytest.fixture(scope="module")
def before_scopes():
    """The committed recording of two decode steps of the program before
    it had names, scopes or engine spans."""
    path = trace_reduce.xplane_path(str(DATA))
    steps = json.loads((DATA / "phi3_decode_steps.json").read_text())
    run = {"t0": steps[0]["t0"], "t_end": steps[-1]["t1"], "steps": steps,
           "tracks": []}
    record = {"trace": trace_reduce.reduce(str(DATA), run), "serve": run,
              "config": PHI3, "peaks": PEAKS}
    return path, record


def test_op_names_cover_most_of_the_recorded_device_time(before_scopes):
    """82.5 % of the two steps' leaf device time has an op_name; the
    rest is mostly the two float32 casts of the gathered keys and
    values, which XLA makes without metadata."""
    path, record = before_scopes
    got = trace_spans.read(path)
    leaves = trace_reduce.leaves([o for k in sorted(record["trace"]["ops"])
                                  for o in record["trace"]["ops"][k]])
    share = named_share(leaves, got["names"])
    assert 80 <= share < 100
    # the anonymous decode program and the eager argmax after it
    assert {n.split("/")[0] for n in got["names"].values()} == {
        "jit(<lambda>)", "jit(_argmax)"}


def test_a_program_without_scopes_or_spans_reads_nothing(before_scopes):
    path, record = before_scopes
    got = trace_spans.read(path)
    assert got["spans"] == []
    assert {trace_spans.scope_of(n) for n in got["names"].values()} <= {
        trace_spans.LOOP, trace_spans.OTHER}
    track = types.SimpleNamespace(req=types.SimpleNamespace(rid=0),
                                  arrival=record["serve"]["t0"])
    record = dict(record, serve=dict(record["serve"], tracks=[track]))
    for name in STAMPED:
        assert spec.load_metric(name).compute(record) is None, name


def _track(arrival, submit, prefill_start, admit=None, first=None):
    req = types.SimpleNamespace(t_submit=submit, t_admit=admit,
                                t_prefill_start=prefill_start, t_first=first)
    return types.SimpleNamespace(req=req, arrival=arrival)


def test_queue_wait_is_the_p90_of_submit_to_first_prefill():
    # waits of 0, 100, ..., 900 ms for requests due in [0, 10) s; one due
    # after the window and one never prefilled are left out
    tracks = [_track(i, i + 0.001, i + 0.001 + 0.1 * i) for i in range(10)]
    tracks += [_track(11.0, 11.0, 20.0), _track(5.0, 5.0, None)]
    record = {"serve": {"t0": 0.0, "t_end": 10.0, "tracks": tracks}}
    assert spec.load_metric("queue_wait_p90_ms").compute(record) == \
        pytest.approx(810.0)


@pytest.mark.parametrize("name, p90", [
    ("admit_wait_p90_ms", 81.0),        # waits of 0, 10, ..., 90 ms
    ("chunk_turn_wait_p90_ms", 729.0),  # 0, 90, ..., 810 ms
    ("own_prefill_p90_ms", 300.0),      # 300 ms each
])
def test_the_queue_wait_splits_at_admission_and_first_chunk(name, p90):
    """Request i, due at i s, is admitted 10·i ms after it was sent and
    starts its prefill 100·i ms after it was sent (so 90·i ms after
    admission); its first token comes 300 ms later.  A request due after
    the window, and one not admitted, are left out."""
    tracks = [_track(i, i, i + 0.1 * i, admit=i + 0.01 * i,
                     first=i + 0.1 * i + 0.3) for i in range(10)]
    tracks += [_track(11.0, 11.0, 20.0, admit=19.0, first=21.0),
               _track(5.0, 5.0, None)]
    record = {"serve": {"t0": 0.0, "t_end": 10.0, "tracks": tracks}}
    assert spec.load_metric(name).compute(record) == pytest.approx(p90)
    # the whole wait is the queue wait, and is no sum of the parts' p90s
    assert spec.load_metric("queue_wait_p90_ms").compute(record) == \
        pytest.approx(810.0)


def test_host_gap_is_host_step_time_less_device_busy():
    def op(start_ms, dur_ms):
        return trace_reduce.Op("fusion.1", start_ms * 1e6, dur_ms * 1e6)

    steps = [{"k": 0, "t0": 10.0, "t1": 10.050},      # decode, 50 ms
             {"k": 1, "t0": 10.050, "t1": 10.120},    # decode, 70 ms
             {"k": 2, "t0": 10.120, "t1": 10.400},    # chunk + decode
             {"k": 3, "t0": 10.400, "t1": 10.550}]    # decode, stalled
    tr = {"kind": {0: "decode", 1: "decode", 2: "chunk+decode",
                   3: "decode"},
          "ops": {0: [op(1, 20), op(15, 30)],        # union 44: 6 idle
                  1: [op(60, 66)],                   # 66: 4 idle
                  2: [op(130, 200)],
                  3: [op(470, 66)]}}                 # 66: 84 idle
    record = {"trace": tr, "serve": {"steps": steps}}
    assert spec.load_metric("host_gap_ms.decode").compute(record) == \
        pytest.approx(6.0)
    assert spec.load_metric("host_gap_ms.decode").compute(
        {"trace": None, "serve": {"steps": steps}}) is None


SPANS = HERE / "testdata_spans"


@pytest.fixture(scope="module")
def recorded():
    """The chip recording of the program with its names, scopes, spans
    and stamps: two decode-only steps of ``phi3_mini`` at 7 live slots,
    then one step that admits an eighth request, runs its only chunk
    (100 prompt rows) and decodes all 8 (TPU v5 lite).  It was made while
    the chunk and retire spans also carried the request's id (``rid``)
    and requests a retirement stamp, which the program no longer
    writes."""
    side = json.loads((SPANS / "phi3_spans.json").read_text())
    steps = side["steps"]
    tracks = [types.SimpleNamespace(req=types.SimpleNamespace(**r),
                                    arrival=r["t_submit"])
              for r in side["requests"]]
    run = {"t0": min(tr.arrival for tr in tracks), "t_end": steps[-1]["t1"],
           "steps": steps, "tracks": tracks}
    record = {"trace": trace_reduce.reduce(str(SPANS), run), "serve": run,
              "config": PHI3, "peaks": PEAKS}
    path = trace_reduce.xplane_path(str(SPANS))
    return path, record, trace_spans.read(path)


def test_recording_has_named_programs_and_nested_spans(recorded):
    path, record, got = recorded
    modules = {op.module.split("(")[0]
               for ops in record["trace"]["ops"].values() for op in ops}
    assert {"jit_decode_step_paged", "jit_prefill_chunk"} <= modules
    assert not any("lambda" in m for m in modules)
    spans = got["spans"]
    steps = [i for i, s in enumerate(spans) if s.name == "serve.step"]
    assert len(steps) == 3
    for s in spans:
        assert (s.parent is None) == (s.name == "serve.step")
        if s.parent is not None:
            p = spans[s.parent]
            assert p.name == "serve.step" and p.start <= s.start <= s.end \
                <= p.end
    # inside each step: admit, [the chunk], prepare, dispatch, readback
    order = [[s.name for s in spans if s.parent == i] for i in steps]
    decode = ["serve.decode.prepare", "serve.decode.dispatch",
              "serve.decode.readback"]
    assert order == [["serve.admit"] + decode] * 2 + [
        ["serve.admit", "serve.prefill_chunk"] + decode]
    chunk = next(s for s in spans if s.name == "serve.prefill_chunk")
    assert (chunk.args["start"], chunk.args["valid"]) == (0, 100)
    assert [s.args["rows"] for s in spans
            if s.name == "serve.decode.dispatch"] == [7, 7, 8]
    assert spans[steps[2] + 1].args == {"admitted": 1, "blocked": 0}


def test_recording_scopes_and_the_rule_for_unnamed_ops(recorded):
    """The float32 casts of the gathered keys and values (``convert.82``,
    ``convert.83``) carry no op_name; the next op after each is the
    scores einsum, so they count to ``attn.core``."""
    path, record, got = recorded
    names = got["names"]
    assert set(trace_spans.SCOPES) <= {
        trace_spans.scope_of(n) for n in names.values()}
    leaves = trace_reduce.leaves([o for k in sorted(record["trace"]["ops"])
                                  for o in record["trace"]["ops"][k]])
    assert named_share(leaves, names) >= 80
    casts = {trace_reduce.short_name(o): sc for o, sc in
             zip(leaves, trace_spans.scopes(leaves, names))
             if trace_reduce.short_name(o).startswith("convert.8")}
    assert casts == {"convert.82 (convert)": "attn.core",
                     "convert.83 (convert)": "attn.core"}


def test_recording_reduces_to_the_four_readings(recorded):
    """By hand, from the stamps in ``phi3_spans.json``: the first seven
    requests were sent together and prefilled one chunk per step, the
    eighth alone in the last step.  Each reading is the 90th percentile
    of eight values, 0.3 of the way from the 7th to the 8th smallest:

    * ``admit_wait_p90_ms`` (submit to admit): 0.163, 0.285, 0.410,
      0.533, 0.743, 0.852, 0.995, 1.160 ms: 1.045 ms.
    * ``chunk_turn_wait_p90_ms`` (admit to first chunk): 0.097, 0.921,
      274.405, 547.483, 820.036, 1092.589, 1365.228, 1638.353 ms (one
      more step for each slot ahead): 1447.166 ms.
    * ``queue_wait_p90_ms`` (submit to first chunk): 0.260, 1.206,
      274.814, 548.017, 820.779, 1093.441, 1366.224, 1639.513 ms:
      1448.210 ms.
    * ``own_prefill_p90_ms`` (first chunk to first token): 137.135 to
      137.691 ms for the first seven, 190.511 ms for the eighth (its
      readback waited 54.7 ms after the chunk's last operation):
      153.537 ms.
    """
    _, record, _ = recorded
    want = {"admit_wait_p90_ms": 1.045, "chunk_turn_wait_p90_ms": 1447.166,
            "queue_wait_p90_ms": 1448.210, "own_prefill_p90_ms": 153.537}
    got = {name: spec.load_metric(name).compute(record) for name in STAMPED}
    assert got == pytest.approx(want, abs=0.001)


def test_recording_host_gap_is_the_median_decode_step_gap(recorded):
    """By hand: the two decode-only steps took 135.665 and 135.640 ms on
    the host clock, and their device operations covered 132.290 and
    132.277 ms: the median of 3.375 and 3.363 is 3.369 ms.  The
    chunk+decode step is not a decode-only step."""
    _, record, _ = recorded
    assert spec.load_metric("host_gap_ms.decode").compute(record) == \
        pytest.approx(3.369, abs=0.001)

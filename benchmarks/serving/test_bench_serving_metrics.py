"""The per-layer readers on records made by hand: which steps each reads,
what it subtracts, and that each returns nothing where it finds nothing
to read."""
import pytest

import spec
import trace_reduce
import work

PHI3 = spec.load_config("phi3_mini")
DENSE = spec.family(PHI3)
PEAKS = spec.load_peaks("TPU v5 lite")


def _step(k, t0, t1, chunks=0, decode=1, rows=8, ctx=2400, spans=None):
    return {"k": k, "t0": t0, "t1": t1, "d_chunks": chunks,
            "d_decode": decode, "decode_rows": rows if decode else 0,
            "decode_ctx": ctx if decode else 0, "first": 0,
            "chunks": spans if spans is not None else
            ([(0, 256, True)] if chunks else [])}


def _op(start_ms, dur_ms, name="fusion.1"):
    return trace_reduce.Op(name, start_ms * 1e6, dur_ms * 1e6)


def _record(steps, trace=None):
    return {"serve": {"t0": 0.0, "t_end": steps[-1]["t1"], "steps": steps,
                      "tracks": []},
            "config": PHI3, "peaks": PEAKS, "trace": trace, "setup_s": 1.0}


def _trace(kinds, ops):
    busy = sum(trace_reduce.union_ns((o.start, o.end) for o in v)
               for v in ops.values())
    return {"kind": kinds, "ops": ops, "busy_s": busy * 1e-9,
            "window_s": 0.5}


def test_decode_and_chunk_device_time():
    ops = {0: [_op(0, 20), _op(19, 11)],          # union 30 ms
           1: [_op(100, 40)],                      # decode 40 ms
           2: [_op(200, 90)]}                      # chunk + decode 90 ms
    tr = _trace({0: "decode", 1: "decode", 2: "chunk+decode"}, ops)
    rec = _record([_step(0, 0, .05), _step(1, .1, .15),
                   _step(2, .2, .3, chunks=1)], tr)
    assert spec.load_metric("decode_step_ms").compute(rec) == \
        pytest.approx(35.0)
    assert spec.load_metric("prefill_chunk_ms").compute(rec) == \
        pytest.approx(55.0)
    assert spec.load_metric("device_idle_share").compute(rec) == \
        pytest.approx(100 * (1 - 0.16 / 0.5))


def test_readers_return_nothing_without_a_trace_or_steps():
    rec = _record([_step(0, 0, .05, chunks=1, decode=0)])
    for name in ("decode_step_ms", "prefill_chunk_ms", "device_idle_share",
                 "mfu.decode", "mfu.prefill"):
        assert spec.load_metric(name).compute(rec) is None, name


def test_mfu_decode_is_the_step_roofline_over_host_time():
    rec = _record([_step(0, 0.0, 0.05), _step(1, 0.05, 0.1)])
    need = work.step_roofline_s(*work.decode_step(PHI3, 8, 2400), PEAKS)
    assert spec.load_metric("mfu.decode").compute(rec) == \
        pytest.approx(100 * 2 * need / 0.1)


def test_mfu_prefill_takes_the_decode_part_out():
    rec = _record([_step(0, 0.0, 0.05), _step(1, 0.05, 0.15, chunks=1),
                   _step(2, 0.15, 0.2, chunks=1, decode=0,
                         spans=[(256, 100, True)])])
    need = work.step_roofline_s(*work.prefill_chunk(PHI3, 0, 256, True),
                                PEAKS) + \
        work.step_roofline_s(*work.prefill_chunk(PHI3, 256, 100, True),
                             PEAKS)
    # the decode part of step 1 is the 50 ms decode-only step
    assert spec.load_metric("mfu.prefill").compute(rec) == \
        pytest.approx(100 * need / (0.05 + 0.05))


def _pallas(K, N, rows=128):
    return (f"%matmul.7 = bf16[{rows},{N}]{{1,0:T(8,128)(2,1)}} custom-call("
            f"bf16[{rows},{K}]{{1,0}} %pad.3, bf16[{K},{N}]{{1,0}} "
            f"%dynamic-slice_bitcast_fusion.1), custom_call_target="
            f"\"tpu_custom_call\"")


def _xla(K, N, rows=8):
    return (f"%fusion.9 = bf16[{rows},{N}]{{1,0}} fusion(bf16[{rows},{K}]"
            f"{{1,0}} %x, bf16[32,{K},{N}]{{2,1,0}} %w, s32[] %i), "
            f"kind=kOutput, calls=%fused_dot")


def _decode_trace(text_of, program="jit__lambda(1)"):
    """Two decode steps, each with every projection of phi3 once, 1 ms
    each, and a weight-slice copy of 1 ms that is no projection."""
    mats = [(K, N) for _, K, N in DENSE.layer_mats(PHI3)] + [(3072, 32256)]
    ops, kinds = {}, {}
    for k in range(2):
        t = 100.0 * k
        ops[k] = [trace_reduce.Op(
            "%fusion.2 = bf16[3072,8192]{1,0} fusion(bf16[32,3072,8192]"
            "{2,1,0} %w, s32[] %i)", t * 1e6, 1e6, program)]
        for j, (K, N) in enumerate(mats):
            ops[k].append(trace_reduce.Op(text_of(K, N), (t + 1 + j) * 1e6,
                                          1e6, program))
        kinds[k] = "decode"
    return _trace(kinds, ops)


def test_matmul_roofline_reads_the_same_for_pallas_and_xla():
    steps = [_step(0, 0, .05, rows=5), _step(1, .1, .15, rows=5)]
    reader = spec.load_metric("matmul_roofline.decode")
    pallas = reader.compute(_record(steps, _decode_trace(_pallas)))
    xla = reader.compute(_record(steps, _decode_trace(_xla)))
    mats = [(K, N) for _, K, N in DENSE.layer_mats(PHI3)] + [(3072, 32064)]
    need = work.roofline_s([work.matmul(5, K, N) for K, N in mats], PEAKS)
    assert pallas == pytest.approx(xla)
    assert pallas == pytest.approx(100 * need / (len(mats) * 1e-3))


def test_prefill_roofline_leaves_the_decode_program_out():
    tr = _decode_trace(_pallas)
    chunk = _decode_trace(lambda K, N: _pallas(K, N, rows=256),
                           program="jit__lambda(2)")
    for op in chunk["ops"][0]:
        op.start += 50e6
    tr["ops"][1] = tr["ops"][1] + chunk["ops"][0]
    tr["kind"][1] = "chunk+decode"
    steps = [_step(0, 0, .05), _step(1, .1, .2, chunks=1,
                                     spans=[(0, 200, False)])]
    got = spec.load_metric("matmul_roofline.prefill").compute(
        _record(steps, tr))
    layer = [(K, N) for _, K, N in DENSE.layer_mats(PHI3)]
    need = work.roofline_s([work.matmul(200, K, N) for K, N in layer],
                           PEAKS)
    # the head of a chunk that does not end its prompt is needed by no one
    assert got == pytest.approx(100 * need / ((len(layer) + 1) * 1e-3))

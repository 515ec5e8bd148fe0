"""The FLOP and byte counts behind the rooflines and ``mfu.*``, against
counts made by hand for phi3-mini (d 3072, F 8192, 32 heads of 96, 32
layers, vocabulary 32064) at a decode step of 8 live rows."""
import pytest

import spec
import work

PHI3 = spec.load_config("phi3_mini")


def test_phi3_decode_projection_counts_by_hand():
    calls = work.matmuls(PHI3, 8, 8)
    assert len(calls) == 7 * 32 + 1
    # wq: 2*8*3072*3072 FLOPs; bytes 2*(3072*3072 + 8*3072 + 8*3072)
    assert calls[0] == (150_994_944, 18_972_672)
    # w_gate: 2*8*3072*8192; 2*(3072*8192 + 8*3072 + 8*8192)
    assert calls[4] == (402_653_184, 50_511_872)
    # head: 2*8*3072*32064; 2*(3072*32064 + 8*3072 + 8*32064)
    assert calls[-1] == (1_576_009_728, 197_563_392)
    assert sum(f for f, _ in calls) == 59_558_068_224
    assert sum(b for _, b in calls) == 7_475_205_120


def test_phi3_decode_step_counts_by_hand():
    flops, byts = work.decode_step(PHI3, rows=8, ctx=2400)
    # + attention 4 * 32 heads * 96 * 2400 keys * 32 layers
    assert flops == 59_558_068_224 + 943_718_400
    # + 393216 B of keys and values per token over 2400 read + 8 written,
    # + the 8 embedded rows
    assert byts == 7_475_205_120 + 393_216 * 2408 + 8 * 3072 * 2
    peaks = spec.load_peaks("TPU v5 lite")
    assert work.step_roofline_s(flops, byts, peaks) == \
        pytest.approx(byts / 819e9)


def test_prefill_chunk_counts_the_head_once_per_prompt():
    f_mid, _ = work.prefill_chunk(PHI3, start=0, valid=256, final=False)
    f_end, _ = work.prefill_chunk(PHI3, start=256, valid=256, final=True)
    head = 2 * 3072 * 32064
    # the second chunk's rows each see 256 more keys
    extra_attn = 4 * 32 * 96 * 256 * 256 * 32
    assert f_end - f_mid == head + extra_attn


def test_roofline_takes_each_calls_bound():
    peaks = {"flops_bf16": 100.0, "hbm_bytes_s": 10.0}
    assert work.roofline_s([(100.0, 1.0), (1.0, 100.0)], peaks) == 11.0


def test_weight_map_finds_every_projection():
    assert work.weight_map(PHI3) == {
        (3072, 3072): (3072, 3072), (3072, 8192): (3072, 8192),
        (8192, 3072): (8192, 3072), (3072, 32064): (3072, 32064),
        (3072, 32256): (3072, 32064)}


def test_no_row_needs_no_work():
    assert work.matmul(0, 3072, 32064) == (0.0, 0.0)

"""The plain reference computes what the program's paged serving steps
compute: at a small width in float32 on the CPU, the logits of a chunked
prefill (``lm.prefill_chunk``) and of paged decode steps
(``lm.decode_step_paged``) agree with the reference's teacher-forced
forward to float32 rounding."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

import bench_serving_testkit as kit
import reference
import spec
import weights

#: float32 rounding over two layers of width 128 and a 512-row head
TOL = 2e-4


def test_reference_matches_paged_prefill_and_decode():
    from repro.models import lm
    from repro.parallel.sharding import default_rules

    c = kit.TINY_CONFIG
    dense = spec.family(c)
    cfg = dataclasses.replace(dense.program_config(c), dtype=jnp.float32)
    w = {k: v.astype(jnp.float32) for k, v in weights.init(c, 11).items()}
    params = dense.to_program(w, cfg)
    rules = default_rules(None)
    bt, chunk, max_seq = 8, 32, 128
    nblk = max_seq // bt
    pool = jax.tree.map(lambda pv: jnp.zeros(pv.shape, pv.dtype),
                        lm.pool_defs(cfg, 2 * nblk + 1, bt),
                        is_leaf=lambda x: hasattr(x, "logical"))
    rng = np.random.default_rng(0)
    plen = 45                           # two chunks, the second partial
    prompt = rng.integers(0, c["vocab_size"], plen).astype(np.int32)
    table = np.zeros(nblk, np.int32)
    table[:8] = np.arange(1, 9)
    got = []
    for start in (0, chunk):
        valid = min(chunk, plen - start)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, :valid] = prompt[start:start + valid]
        logits, pool = lm.prefill_chunk(params, jnp.asarray(toks), pool,
                                        jnp.asarray(table), jnp.int32(start),
                                        jnp.int32(valid), cfg, rules)
        got.append(np.asarray(logits[0, :valid, :c["vocab_size"]]))
    served = [int(np.argmax(got[-1][-1]))]
    tables = np.zeros((2, nblk), np.int32)
    tables[0] = table
    for step in range(5):
        pos = plen + step
        tok = np.array([[served[-1]], [0]], np.int32)
        logits, pool = lm.decode_step_paged(
            params, jnp.asarray(tok), pool, jnp.asarray(tables),
            jnp.asarray([pos, 0], jnp.int32), jnp.asarray([True, False]),
            cfg, rules)
        row = np.asarray(logits[0, 0, :c["vocab_size"]])
        got.append(row[None])
        served.append(int(np.argmax(row)))
    program = np.concatenate(got)                # rows 0 .. plen + 4
    seq = np.concatenate([prompt, served[:-1]])
    ref = reference.forward_rows(w, c, seq[None], np.arange(len(seq))[None])
    np.testing.assert_allclose(np.asarray(ref[0]), program, atol=TOL,
                               rtol=TOL)


def test_gaps_are_zero_for_reference_tokens_and_not_for_others():
    c = kit.TINY_CONFIG
    w = weights.init(c, 5)
    rng = np.random.default_rng(1)
    prompt = rng.integers(0, c["vocab_size"], 20).astype(np.int32)
    served = []
    seq = list(prompt)
    for _ in range(6):                      # greedy through the reference
        logits = reference.forward_rows(w, c, np.array([seq], np.int32),
                                        np.array([[len(seq) - 1]]))
        served.append(int(jnp.argmax(logits[0, 0])))
        seq.append(served[-1])
    g = reference.gaps(w, c, [prompt], [served], length=64, rows_max=8)
    assert np.all(g["served"][0] == 0)
    wrong = list(served)
    wrong[3] = (wrong[3] + 1) % c["vocab_size"]
    g = reference.gaps(w, c, [prompt], [wrong], length=64, rows_max=8)
    assert g["served"][0][3] > 0


def test_fp8_control_departs_from_the_reference():
    c = kit.TINY_CONFIG
    w = weights.init(c, 9)
    toks = np.random.default_rng(2).integers(0, c["vocab_size"],
                                             (1, 64)).astype(np.int32)
    rows = np.arange(64)[None]
    ref = np.asarray(reference.forward_rows(w, c, toks, rows))
    ctl = np.asarray(reference.forward_rows(w, c, toks, rows, fp8=True))
    err = np.abs(ctl - ref).max()
    assert 1e-2 < err < 1.0

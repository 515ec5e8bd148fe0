"""A whole run of the harness on the CPU (past its look for a chip) at a
size a test can hold: sound, it is correct; with the timed path broken
underneath in each way a serving cell can break, ``correct`` comes out
false; and the fp8 control, put in the program's place, fails the limit
that the program meets.  (A one-chip cell has no exchange between chips
to leave out.)"""
import jax.numpy as jnp
import pytest

import bench
import bench_serving_testkit as kit
import harness

SECONDS = 2.0


def _run(tmp_path, monkeypatch, seed=3, control=False):
    monkeypatch.setattr(harness, "enable_compile_cache", lambda: "off")
    base = kit.tiny_bench(tmp_path)
    return bench.run_cell(kit.TINY_CELL["name"], seed, SECONDS, False,
                          ["output_tok_s", "itl_p95_ms"], base=base,
                          control=control)


def state_unchanged(step):
    """The decode step hands back the pool it was given: no key or value
    is ever written for a generated token."""
    def broken(params, token, pool, *a, **k):
        logits, _ = step(params, token, pool, *a, **k)
        return logits, pool
    return broken


def half_batch_left_out(step):
    """The rows at odd positions, half of every request's decode steps,
    are not computed; they take the mean of the rows that were.  Chosen
    by position and not by slot, so that every request the check samples
    holds broken tokens."""
    def broken(params, token, pool, tables, pos, live, *a, **k):
        odd = pos % 2 == 1
        kept = live & ~odd
        logits, new_pool = step(params, token, pool, tables, pos, kept,
                                *a, **k)
        n = jnp.maximum(kept.sum(), 1).astype(logits.dtype)
        rest = jnp.sum(jnp.where(kept[:, None, None], logits, 0), axis=0,
                       keepdims=True) / n
        return jnp.where(odd[:, None, None], rest, logits), new_pool
    return broken


def token_altered(step):
    """Every fifth position of every slot emits the token after the one
    the model chose."""
    def broken(params, token, pool, tables, pos, live, *a, **k):
        logits, new_pool = step(params, token, pool, tables, pos, live,
                                *a, **k)
        top = jnp.argmax(logits[:, 0], axis=-1)
        bump = jnp.where(pos % 5 == 0, 1e4, 0.0).astype(logits.dtype)
        nxt = (top + 1) % 512
        return logits.at[jnp.arange(logits.shape[0]), 0, nxt].add(bump), \
            new_pool
    return broken


def test_sound_run_is_correct(tmp_path, monkeypatch):
    out = _run(tmp_path, monkeypatch)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 8
    assert set(out["metrics"]) == {"output_tok_s", "itl_p95_ms"}
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   token_altered])
def test_broken_decode_step_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.models import lm
    monkeypatch.setattr(lm, "decode_step_paged",
                        fault(lm.decode_step_paged))
    out = _run(tmp_path, monkeypatch)
    assert not out["correct"], out["checks"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [5, 6, 7])
def test_fp8_control_fails_the_limit_the_program_meets(tmp_path, monkeypatch,
                                                       seed):
    out = _run(tmp_path, monkeypatch, seed=seed, control=True)
    limit = out["checks"]["logit_gap"]["limit"]
    assert out["checks"]["logit_gap"]["value"] <= limit
    assert out["_control"]["logit_gap"] > limit

"""The paged engine's tracing: its three programs lower under their own
names, every ``step()`` writes nested ``serve.*`` spans into the
profiler's trace, each with the work it timed, and each request's
stamps come in the order its life runs."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.models import lm
from repro.parallel.sharding import default_rules, init_params
from repro.serve import PagedServeConfig, PagedServingEngine, Request

CFG = get_smoke_config("llama3-8b")
PLEN, CHUNK = 20, 16


@pytest.fixture(scope="module")
def params():
    return init_params(lm.model_defs(CFG), jax.random.key(0))


def _engine(params, chunk: int) -> PagedServingEngine:
    return PagedServingEngine(
        CFG, params, default_rules(None),
        PagedServeConfig(max_batch=2, max_seq=64, block_tokens=8,
                         n_blocks=16, chunk=chunk))


def _requests(n: int = 3) -> list[Request]:
    rng = np.random.default_rng(0)
    return [Request(rid=r, prompt=rng.integers(1, 100, PLEN).astype(np.int32),
                    max_new_tokens=3) for r in range(n)]


def test_programs_lower_under_their_names(params):
    eng = _engine(params, CHUNK)
    i32 = jnp.int32
    step = eng._step.lower(params, jnp.zeros((2, 1), i32), eng.pool,
                           jnp.asarray(eng.tables),
                           jnp.asarray(eng.slot_pos), jnp.zeros(2, bool))
    chunk = eng._chunk.lower(params, jnp.zeros((1, CHUNK), i32), eng.pool,
                             jnp.asarray(eng.tables[0]), i32(0), i32(PLEN))
    prefill = eng._prefill.lower(params, jnp.zeros((1, PLEN), i32))
    assert "module @jit_decode_step_paged" in step.as_text()
    assert "module @jit_prefill_chunk" in chunk.as_text()
    assert "module @jit_prefill " in prefill.as_text()


def _spans(directory: str) -> list[tuple[float, float, str, dict]]:
    from jax.profiler import ProfileData
    path = glob.glob(f"{directory}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path[0])
    return sorted((float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                   ev.name, dict(ev.stats))
                  for plane in data.planes if plane.name.startswith("/host:")
                  for line in plane.lines for ev in line.events
                  if ev.name.startswith("serve."))


def test_engine_spans_nest_under_each_step(params, tmp_path):
    eng = _engine(params, CHUNK)
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        n_steps = 0
        while eng.step() or eng.waiting:
            n_steps += 1
        n_steps += 1                        # the last, idle step
    finally:
        jax.profiler.stop_trace()
    spans = _spans(str(tmp_path))
    steps = [s for s in spans if s[2] == "serve.step"]
    assert len(steps) == n_steps
    inner = [s for s in spans if s[2] != "serve.step"]
    for s0, s1, name, args in inner:
        assert any(a <= s0 and s1 <= b for a, b, _, _ in steps), name
    # each step admits once, then decodes in order: prepare, dispatch,
    # readback
    for a, b, _, _ in steps:
        within = [(name, args) for s0, s1, name, args in inner
                  if a <= s0 and s1 <= b]
        names = [n for n, _ in within]
        assert names[0] == "serve.admit"
        assert set(within[0][1]) == {"admitted", "blocked"}
        dec = [n for n in names if n.startswith("serve.decode.")]
        assert dec in ([], ["serve.decode.prepare", "serve.decode.dispatch",
                            "serve.decode.readback"])
    admitted = sum(args["admitted"] for _, _, n, args in inner
                   if n == "serve.admit")
    assert admitted == len(reqs)
    # every prompt in two chunks, each chunk span naming its rows
    chunks = [(args["start"], args["valid"])
              for _, _, n, args in inner if n == "serve.prefill_chunk"]
    assert sorted(chunks) == sorted(
        (s, v) for _ in reqs for s, v in ((0, CHUNK), (CHUNK, PLEN - CHUNK)))
    retired = [args for _, _, n, args in inner if n == "serve.retire"]
    assert retired == [{}] * len(reqs)
    rows = [args["rows"] for _, _, n, args in inner
            if n == "serve.decode.dispatch"]
    assert len(rows) == eng.decode_steps and max(rows) == 2


@pytest.mark.parametrize("chunk", [0, CHUNK], ids=["whole", "chunked"])
def test_request_stamps_follow_its_life(params, chunk):
    eng = _engine(params, chunk)
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    eng.run()
    for r in reqs:
        assert r.t_submit <= r.t_admit <= r.t_prefill_start <= r.t_first, \
            r.rid
    # two slots: the third request waits in the queue until the first
    # one, which has its first token, retires
    assert reqs[2].t_admit > reqs[0].t_first > reqs[0].t_submit
    assert reqs[2].t_admit - reqs[2].t_submit > \
        reqs[0].t_admit - reqs[0].t_submit
    if chunk:
        # one chunk per step, lowest slot first: the second request's
        # prefill starts after the first one's has finished
        assert reqs[1].t_prefill_start >= reqs[0].t_first

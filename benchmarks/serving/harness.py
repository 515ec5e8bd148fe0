"""One run of one cell: set-up, the measured window, the check.

The window drives the program's serving path as a user's server would:
``repro.serve.PagedServingEngine`` with chunked prefill, stepped through
its public ``submit()`` / ``step()``.  The engine's ``eos_id`` is -1, a
token no argmax yields, so each request ends at its drawn length.

Around every ``engine.step()`` the harness keeps one host span on its own
clock (and, in a traced run, a ``bench.step.<k>`` annotation in the
profiler's trace, closed only once the step's device work is done) and
reads the engine's public state:

* the counters ``prefill_chunks`` and ``decode_steps``;
* each request's ``out`` list: every token is stamped with the end of the
  step that made it;
* ``slots`` / ``slot_fill``: which prompt rows the step's prefill chunk
  covered, for the work a chunk does.

The check runs after the window has closed and the engine is freed: the
plain reference (``reference.py``) scores a seeded sample of the
finished requests, the longest among them.
"""
from __future__ import annotations

import dataclasses
import os
import pathlib
import sys
import time

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import spec                                      # noqa: E402

#: how long after the window the harness keeps stepping so that every
#: request that arrived in it gets its first token (a late token is late,
#: not wrong; one that never comes is a failure)
DRAIN_S = 60.0
#: the sample of finished requests the reference scores, at most
SAMPLE_MAX = 16
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def clock() -> float:
    # repro: the yardstick keeps its own clock, so that a change to
    # repro.testing.timing cannot move it
    return time.perf_counter()  # repro: noqa(L4)


class CompileClock:
    """XLA compilations (or loads from the persistent cache) since it was
    started: their count and seconds."""

    def __init__(self):
        import jax
        self.n, self.secs = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event == COMPILE_EVENT:
            self.n += 1
            self.secs += secs


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path: ``JAX_COMPILATION_CACHE_DIR``
    where it is set, else ``.jax_cache`` in the checkout.  Every program
    is kept, however fast it compiled, so that a warm run compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT
                                                              / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_engine(c: dict, w: dict):
    """The paged engine over the weights ``w`` (``weights.init``), in the
    program's configuration and parameter tree that the family of ``c``
    gives."""
    from repro.parallel.sharding import default_rules
    from repro.serve import PagedServeConfig, PagedServingEngine
    fam = spec.family(c)
    cfg = fam.program_config(c)
    e = c["engine"]
    scfg = PagedServeConfig(max_batch=e["max_batch"], max_seq=e["max_seq"],
                            eos_id=-1, block_tokens=e["block_tokens"],
                            n_blocks=e["pool_tokens"] // e["block_tokens"],
                            chunk=e["chunk"])
    return PagedServingEngine(cfg, fam.to_program(w, cfg),
                              default_rules(None), scfg)


@dataclasses.dataclass
class Track:
    """One request as the client sees it."""
    req: object                 # repro.serve.Request
    plen: int
    arrival: float              # when it was due (host clock)
    client: int | None = None
    times: list = dataclasses.field(default_factory=list)
    seen: int = 0
    fill: int = 0               # prompt rows prefilled so far
    done_at: float | None = None


class Tracer:
    """The profiler over ``[start_s, start_s + seconds)`` of the window,
    host Python tracing off (it would swamp the host plane)."""

    def __init__(self, start_s: float, seconds: float, directory: str):
        self.start_s, self.seconds = start_s, seconds
        self.begin = self.end = None
        self.dir = directory
        self.on = self.done = False

    def arm(self, t0: float):
        self.begin, self.end = t0 + self.start_s, t0 + self.start_s \
            + self.seconds

    def poll(self, t: float):
        import jax
        if not self.on and not self.done and t >= self.begin:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=opts)
            self.on = True
        elif self.on and t >= self.end:
            jax.profiler.stop_trace()
            self.on, self.done = False, True

    def close(self):
        import jax
        if self.on:
            jax.profiler.stop_trace()
            self.on, self.done = False, True


def serve(engine, planned: list, cell: dict, closed: bool, seconds: float,
          tracer: Tracer | None = None) -> dict:
    """Drive ``engine`` for ``seconds`` under the cell's traffic; returns
    the run record (window, per-step spans, per-request tracks).

    A closed loop starts in its steady state: each client's first
    request is sent and prefilled before the window opens (so the
    window does not begin with every slot in prefill at once); the
    window, and ``t0``, start when each has its first token.  An open
    loop starts empty at ``t0``, with its first arrival one gap later."""
    import jax
    from repro.serve import Request
    queue = list(planned)
    tracks: list[Track] = []
    inflight: dict[int, Track] = {}
    steps: list[dict] = []
    late = []

    def submit(p, due: float, client=None):
        tr = Track(Request(rid=p.rid, prompt=p.prompt,
                           max_new_tokens=p.max_new),
                   plen=len(p.prompt), arrival=due, client=client)
        engine.submit(tr.req)
        tracks.append(tr)
        inflight[id(tr.req)] = tr

    def one_step(k: int):
        a_chunks, a_decode = engine.prefill_chunks, engine.decode_steps
        ts = clock()
        with jax.profiler.TraceAnnotation(f"bench.step.{k}"):
            engine.step()
            if tracer is not None:
                # the engine reads an argmax back in every step but one
                # that runs a prompt's non-final chunk alone; waiting here
                # keeps each step's device work inside its span
                jax.block_until_ready(engine.pool)
        te = clock()
        rec = {"k": k, "t0": ts, "t1": te,
               "d_chunks": engine.prefill_chunks - a_chunks,
               "d_decode": engine.decode_steps - a_decode,
               "decode_rows": 0, "decode_ctx": 0, "first": 0,
               "chunks": []}
        slots = getattr(engine, "slots", None)
        fill = getattr(engine, "slot_fill", None)
        if slots is not None and fill is not None:
            for i, r in enumerate(slots):
                tr = inflight.get(id(r)) if r is not None else None
                if tr is not None and int(fill[i]) > tr.fill:
                    f = int(fill[i])
                    rec["chunks"].append((tr.fill, f - tr.fill, f >= tr.plen))
                    tr.fill = f
        finished = []
        for tr in inflight.values():
            n = len(tr.req.out)
            if n == tr.seen:
                continue
            if tr.seen == 0:
                rec["first"] += 1
                if tr.fill < tr.plen and slots is not None:
                    # prefilled and retired within this one step
                    rec["chunks"].append((tr.fill, tr.plen - tr.fill, True))
                    tr.fill = tr.plen
            for j in range(tr.seen, n):
                tr.times.append(te)
                if j >= 1:
                    rec["decode_rows"] += 1
                    rec["decode_ctx"] += tr.plen + j
            tr.seen = n
            if tr.req.done:
                tr.done_at = te
                finished.append(tr)
        if slots is None or len(rec["chunks"]) != rec["d_chunks"]:
            rec["chunks"] = None            # rows of the chunk not known
        steps.append(rec)
        return finished

    k = 0
    if closed:
        for client in range(cell["arrivals"]["clients"]):
            submit(queue.pop(0), clock(), client)
        while any(tr.seen == 0 for tr in tracks):
            for tr in one_step(k):
                del inflight[id(tr.req)]
                submit(queue.pop(0), clock(), tr.client)
            k += 1
        steps.clear()
        t0 = clock()
        next_due = None
    else:
        t0 = clock()
        next_due = t0 + queue[0].gap_s
    t_close = t0 + seconds
    if tracer is not None:
        tracer.arm(t0)

    while True:
        t = clock()
        if t >= t_close:
            break
        if tracer is not None:
            tracer.poll(t)
        while next_due is not None and next_due <= t and queue:
            p = queue.pop(0)
            late.append(t - next_due)
            submit(p, next_due)
            next_due = next_due + queue[0].gap_s if queue else None
        if not inflight:
            if next_due is None:
                break
            time.sleep(max(0.0, min(next_due, t_close) - clock()))
            continue
        for tr in one_step(k):
            del inflight[id(tr.req)]
            if closed and queue:
                submit(queue.pop(0), clock(), tr.client)
        k += 1
    t_end = steps[-1]["t1"] if steps else clock()
    if tracer is not None:
        tracer.close()
    # drain: no new arrivals; every request that came in the window gets
    # its first token, or counts as failed
    n_window = len(tracks)
    drain_until = clock() + DRAIN_S
    while any(tr.seen == 0 for tr in tracks) and clock() < drain_until:
        for tr in one_step(k):
            del inflight[id(tr.req)]
        k += 1
    return {"t0": t0, "t_end": t_end, "seconds": t_end - t0,
            "steps": steps, "tracks": tracks[:n_window],
            "lateness_s": late, "queue_left": len(queue)}


def warm_up(engine, c: dict):
    """Compile (or load) every program the window runs: a prompt of two
    chunks, the second partial, then decode steps."""
    from repro.serve import Request
    e = c["engine"]
    rng = np.random.default_rng(0)
    plen = e["chunk"] + e["block_tokens"] + 1
    engine.submit(Request(rid=-1, prompt=rng.integers(
        0, c["vocab_size"], plen).astype(np.int32), max_new_tokens=4))
    while engine.step():
        pass

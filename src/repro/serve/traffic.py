"""Open-loop serving load: Poisson arrivals over a Zipf-popular prompt pool.

Open-loop means arrivals do not wait for the server (the load a fleet of
independent users generates): request i becomes submittable at a fixed
wall-clock offset drawn from exponential interarrival gaps, whether or
not the engine has kept up — so queueing delay shows up in TTFT instead
of being hidden by a closed feedback loop.  Prompt *popularity* is
Zipfian over a small pool (the same ``ranks**-a`` law as
``data/pipeline.py``'s corpus, whose Markov rows supply the prompt text),
which is what makes shared-prefix block reuse a first-class effect: the
head of the distribution hits the same prompt blocks over and over.

All wall-clock reads go through ``repro.testing.timing.now`` (lint L4);
this module records metrics and prints machine-parseable lines — the
schema-pinned BENCH artifact is written only by ``benchmarks/run.py``
(lint L3), which runs this module's CLI in an 8-fake-device subprocess.

CLI: ``python -m repro.serve.traffic --configs dense,paged,paged_chunked``
prints one ``serve/<tag>,...`` CSV line and one ``serve_json {...}`` line
per config.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys

import numpy as np

from repro.data.pipeline import DataConfig, SyntheticCorpus
from repro.serve.engine import Request
from repro.testing.timing import now


@dataclasses.dataclass(frozen=True)
class LoadConfig:
    n_requests: int = 24
    rate_rps: float = 20.0      # Poisson arrival rate (requests / second)
    zipf_a: float = 1.1         # prompt-popularity exponent over the pool
    pool_size: int = 6
    min_prompt: int = 4
    max_prompt: int = 24
    max_new: int = 16
    vocab_size: int = 512
    seed: int = 0


def prompt_pool(lc: LoadConfig) -> list[np.ndarray]:
    """Pool of distinct prompts cut from the synthetic corpus rows (Zipf
    unigrams + Markov bigrams), with per-prompt lengths drawn uniformly —
    the corpus machinery reused, not reimplemented."""
    dc = DataConfig(vocab_size=lc.vocab_size, seq_len=lc.max_prompt,
                    global_batch=lc.pool_size, seed=lc.seed)
    rows = SyntheticCorpus(dc).batch(0)
    rng = np.random.default_rng(lc.seed)
    lens = rng.integers(lc.min_prompt, lc.max_prompt + 1, lc.pool_size)
    return [r[:n].astype(np.int32).copy() for r, n in zip(rows, lens)]


def request_schedule(lc: LoadConfig) -> tuple[np.ndarray, np.ndarray]:
    """(arrival offsets seconds, pool index) per request: exponential
    interarrival gaps (Poisson process) + Zipf-ranked pool popularity."""
    rng = np.random.default_rng(lc.seed + 1)
    arrivals = np.cumsum(rng.exponential(1.0 / lc.rate_rps, lc.n_requests))
    ranks = np.arange(1, lc.pool_size + 1, dtype=np.float64)
    p = ranks ** (-lc.zipf_a)
    p /= p.sum()
    idx = rng.choice(lc.pool_size, size=lc.n_requests, p=p)
    return arrivals, idx


def run_open_loop(engine, lc: LoadConfig, *, max_steps: int = 100_000) -> dict:
    """Drive ``engine`` (any object with submit/step/n_live/n_waiting/
    capacity/peak_live) under the open-loop schedule; returns the metrics
    dict ``benchmarks/run.py`` records per config."""
    pool = prompt_pool(lc)
    arrivals, idx = request_schedule(lc)
    reqs = [Request(rid=i, prompt=pool[j], max_new_tokens=lc.max_new)
            for i, j in enumerate(idx)]
    ttft: dict[int, float] = {}
    occ: list[float] = []
    submitted = 0
    t0 = now()
    for _ in range(max_steps):
        t = now() - t0
        while submitted < len(reqs) and arrivals[submitted] <= t:
            engine.submit(reqs[submitted])
            submitted += 1
        worked = engine.step()
        tnow = now() - t0
        for r in reqs[:submitted]:
            if r.out and r.rid not in ttft:
                ttft[r.rid] = tnow
        if worked:                  # slot utilization of actual engine steps
            occ.append(engine.n_live / engine.capacity)
        if submitted == len(reqs) and not worked and engine.n_waiting == 0 \
                and engine.n_live == 0:
            break
    wall = now() - t0
    done = [r for r in reqs if r.done]
    gen_tokens = sum(len(r.out) for r in reqs)
    ttft_ms = sorted(1e3 * (ttft[r.rid] - arrivals[r.rid])
                     for r in reqs if r.rid in ttft)
    pct = (lambda q: ttft_ms[min(len(ttft_ms) - 1,
                                 int(q * (len(ttft_ms) - 1)))]) \
        if ttft_ms else (lambda q: 0.0)
    return {
        "n_requests": lc.n_requests,
        "completed": len(done),
        "ttft_p50_ms": round(pct(0.50), 3),
        "ttft_p99_ms": round(pct(0.99), 3),
        "decode_tok_s": round(gen_tokens / max(wall, 1e-9), 3),
        "occupancy": round(float(np.mean(occ)) if occ else 0.0, 4),
        "max_concurrent": int(engine.peak_live),
        "wall_s": round(wall, 3),
    }


# ---------------------------------------------------------------------------
# CLI: the ablation benchmarks/run.py records (dense vs paged vs chunked)
# ---------------------------------------------------------------------------

def _build(tag: str, args):
    """One engine per ablation arm, all at EQUAL device memory: the dense
    engine holds ``dense_batch * max_seq`` KV token-slots; the paged pool
    holds the same token count in ``n_blocks`` blocks but serves
    ``max_batch`` slots over it."""
    import jax

    from repro import substrate
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.parallel.sharding import default_rules, init_params
    from repro.serve.engine import ServeConfig, ServingEngine
    from repro.serve.paged import (PagedServeConfig, PagedServingEngine,
                                   kv_token_bytes)
    from repro.topology import Topology

    cfg = get_smoke_config(args.arch)
    mesh = topo = None
    if len(jax.devices()) >= 8:
        mesh = substrate.make_mesh((2, 2, 2), ("pod", "data", "model"))
        topo = Topology.from_levels([("pod", 2, 8.0), ("data", 2, 4.0),
                                     ("model", 2, 2.0)])
    rules = default_rules(mesh, kv_heads=cfg.n_kv_heads, batch=1)
    params = init_params(lm.model_defs(cfg), jax.random.key(args.seed))
    bt = args.block_tokens
    n_blocks = args.dense_batch * args.max_seq // bt   # equal token capacity
    per_tok = kv_token_bytes(cfg)
    if tag == "dense":
        scfg = ServeConfig(max_batch=args.dense_batch, max_seq=args.max_seq)
        eng = ServingEngine(cfg, params, rules, scfg, topology=topo)
        conf = {"max_batch": scfg.max_batch, "max_seq": scfg.max_seq,
                "block_tokens": 0, "chunk": 0}
        kv_cap = scfg.max_batch * scfg.max_seq * per_tok
        kv_peak = lambda: kv_cap                       # dense: always resident
    else:
        chunk = args.chunk if tag == "paged_chunked" else 0
        scfg = PagedServeConfig(max_batch=args.max_batch,
                                max_seq=args.max_seq, block_tokens=bt,
                                n_blocks=n_blocks, chunk=chunk)
        eng = PagedServingEngine(cfg, params, rules, scfg)
        conf = {"max_batch": scfg.max_batch, "max_seq": scfg.max_seq,
                "block_tokens": bt, "chunk": chunk}
        kv_cap = n_blocks * bt * per_tok
        kv_peak = eng.kv_bytes_resident_peak
    return eng, conf, kv_cap, kv_peak


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--configs", default="dense,paged,paged_chunked",
                    help="comma-separated: dense, paged, paged_chunked")
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=20.0)
    ap.add_argument("--pool", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-prompt", type=int, default=24)
    ap.add_argument("--max-seq", type=int, default=64)
    ap.add_argument("--max-batch", type=int, default=8,
                    help="paged engine slots")
    ap.add_argument("--dense-batch", type=int, default=2,
                    help="dense slots at the same KV memory")
    ap.add_argument("--block-tokens", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    lc = LoadConfig(n_requests=args.requests, rate_rps=args.rate,
                    pool_size=args.pool, max_prompt=args.max_prompt,
                    max_new=args.max_new, seed=args.seed)
    for tag in args.configs.split(","):
        tag = tag.strip()
        eng, conf, kv_cap, kv_peak = _build(tag, args)
        metrics = run_open_loop(eng, lc)
        if hasattr(eng, "shutdown") and eng.n_live == 0 \
                and eng.n_waiting == 0:
            eng.shutdown()      # leaked KV blocks fail the run loudly
        metrics["kv_bytes_capacity"] = int(kv_cap)
        metrics["kv_bytes_resident_peak"] = int(kv_peak())
        conf["rate_rps"] = lc.rate_rps
        rec = {"tag": tag, "config": conf, **metrics}
        print(f"serve/{tag},{metrics['ttft_p50_ms']},{metrics['ttft_p99_ms']},"
              f"{metrics['decode_tok_s']},{metrics['occupancy']},"
              f"{metrics['max_concurrent']}")
        print("serve_json " + json.dumps(rec, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())

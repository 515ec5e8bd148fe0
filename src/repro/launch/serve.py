"""Serving launcher: batched requests against a (smoke or full) model.

``--full`` serves the published configuration of ``--arch`` (random
weights from a fixed seed); without it the d_model-64 smoke variant.
Prompts come in two fixed lengths (:data:`PROMPT_LENS`), so the dense
engine compiles two prefill programs, whatever the request count.
``--paged`` swaps the dense per-slot KV cache for the block-table pool
(``repro.serve.paged``) — ``--block-tokens`` sizes the blocks (0 = ask the
autotune table via :func:`repro.kernels.ops.paged_block_tokens`) and
``--chunk`` enables chunked prefill.  ``--pods N`` splits the request
stream across N engines behind the prefix-affinity router
(``repro.serve.router``), the cross-pod scale-out path.
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch import compile_cache
from repro.models import lm
from repro.parallel.sharding import default_rules, init_params
from repro.serve import (PagedServeConfig, PagedServingEngine, PrefixRouter,
                         Request, ServeConfig, ServingEngine)
from repro.testing.timing import now

#: the prompt lengths requests cycle through
PROMPT_LENS = (8, 20)


def _make_engine(cfg, params, rules, *, paged: bool, max_batch: int,
                 max_seq: int, block_tokens: int, chunk: int):
    if not paged:
        return ServingEngine(cfg, params, rules,
                             ServeConfig(max_batch=max_batch,
                                         max_seq=max_seq))
    if block_tokens <= 0:
        from repro.kernels.ops import paged_block_tokens
        block_tokens = paged_block_tokens(
            max_batch, cfg.n_heads, cfg.n_kv_heads, max_seq,
            cfg.d_model // cfg.n_heads, cfg.dtype)
    scfg = PagedServeConfig(max_batch=max_batch, max_seq=max_seq,
                            block_tokens=block_tokens,
                            n_blocks=max_batch * max_seq // block_tokens,
                            chunk=chunk)
    return PagedServingEngine(cfg, params, rules, scfg)


def run(arch: str, *, smoke: bool = True, n_requests: int = 6,
        max_new: int = 16, max_batch: int = 4, max_seq: int = 128,
        paged: bool = False, block_tokens: int = 0, chunk: int = 0,
        pods: int = 1, seed: int = 0):
    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    rules = default_rules(None)
    params = init_params(lm.model_defs(cfg), jax.random.key(seed))
    engines = [_make_engine(cfg, params, rules, paged=paged,
                            max_batch=max_batch, max_seq=max_seq,
                            block_tokens=block_tokens, chunk=chunk)
               for _ in range(max(pods, 1))]
    front = engines[0] if len(engines) == 1 else PrefixRouter(engines)
    rng = np.random.default_rng(seed)
    t0 = now()
    for rid in range(n_requests):
        plen = PROMPT_LENS[rid % len(PROMPT_LENS)]
        prompt = rng.integers(1, cfg.vocab_size, plen).astype(np.int32)
        front.submit(Request(rid=rid, prompt=prompt, max_new_tokens=max_new))
    finished = front.run()
    dt = now() - t0
    toks = sum(len(r.out) for r in finished)
    mode = ("paged+chunked" if paged and chunk else
            "paged" if paged else "dense")
    pods_txt = f" pods={len(engines)}" if len(engines) > 1 else ""
    print(f"[serve] {len(finished)} requests, {toks} tokens in {dt:.2f}s "
          f"({toks/dt:.1f} tok/s incl. compile) [{mode}{pods_txt}]")
    return finished


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--full", action="store_true",
                    help="full published config (default: smoke widths)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=1024)
    ap.add_argument("--paged", action="store_true",
                    help="block-table KV pool instead of dense slots")
    ap.add_argument("--block-tokens", type=int, default=0,
                    help="tokens per KV block (0 = autotune table)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="chunked-prefill chunk size (0 = whole-prompt)")
    ap.add_argument("--pods", type=int, default=1,
                    help="engines behind the prefix-affinity router")
    args = ap.parse_args()
    compile_cache.enable()
    run(args.arch, smoke=not args.full, n_requests=args.requests,
        max_new=args.max_new, max_batch=args.max_batch, max_seq=args.max_seq,
        paged=args.paged, block_tokens=args.block_tokens, chunk=args.chunk,
        pods=args.pods)


if __name__ == "__main__":
    main()

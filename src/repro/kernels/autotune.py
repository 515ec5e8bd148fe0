"""Model-guided block-shape autotuner — the sim <-> kernel loop, closed.

AraXL's headline efficiency comes from matching blocking to the machine:
register-group capacity, lane count and wire level decide the winning
tile.  This module connects the repo's two halves of that story: the
calibrated sim (`repro.sim`) *prices* a candidate tiling, the Pallas
kernel library *runs* it.  Per problem signature
``(kernel, shape, dtype, topology_tag)``:

1. **enumerate** legal block-shape candidates — power-of-two divisors of
   the grid, filtered by the S3 VRF budget (every buffer fits one LMUL=8
   register group, the resident set fits the 32-vreg VRF; see
   `repro.kernels.vrf`);
2. **rank** them with the sim cost model — a representative register-group
   strip replayed through `sim.kernels` traces, scaled to the full grid,
   plus a per-grid-step dispatch charge (`glsu_lat` + `issue_gap`) and the
   HBM stream priced at the innermost `Topology.wire_bw` level;
3. **measure** only the model's top-k shortlist with
   `repro.testing.timing.measure_us` (median + IQR; noisy ranks are
   re-measured, not cached);
4. **cache** the winner in a persistent JSON table that the `kernels.ops`
   wrappers consult ambiently (the ctx-driven config plumbing idiom), so
   `launch.train` / `launch.perf` / `serve` pick up tuned blocks with
   zero call-site churn.

The model-predicted vs measured rank table is recorded into
``BENCH_kernels.json`` by ``python -m benchmarks.run kernels`` — an
ongoing calibration test of the sim against the kernels it prices.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import os
import pathlib

from .vrf import VREG_GROUP_BYTES, VRF_BYTES

#: the tunable kernel families and their static block defaults (what the
#: ops wrappers fall back to when no tuned entry exists)
DEFAULTS: dict[str, dict[str, int]] = {
    "matmul": {"bm": 128, "bn": 128, "bk": 128},
    "flash_attention": {"bq": 128, "bk": 128},
    "paged_attention": {"bt": 16, "pages": 8},
    "rmsnorm": {"bm": 8},
    "reduction": {"block": 2048},
    "stencil": {"bh": 8, "bw": 256},
}
KERNELS = tuple(DEFAULTS)

#: problem-shape conventions, documented once:
#:   matmul           (M, K, N)
#:   flash_attention  (B, Hq, Hkv, S, Sk, D)
#:   paged_attention  (B, Hq, Hkv, T, D)  — T = max tokens (nblk * bt);
#:                    bt sizes the pool, pages (blocks per copy) the call
#:   rmsnorm          (R, D)
#:   reduction        (n,)
#:   stencil          (H, W)  — interior grid, before halo padding

_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2, "float64": 8}


def _itemsize(dtype: str) -> int:
    return _ITEMSIZE.get(str(dtype), 4)


def signature(kernel: str, shape, dtype: str, topology_tag: str) -> str:
    return "|".join((kernel, "x".join(str(int(s)) for s in shape),
                     str(dtype), topology_tag))


# ---------------------------------------------------------------- candidates

def _pow2_divisors(dim: int, lo: int, hi: int) -> list[int]:
    out, b = [], 1
    while b <= min(dim, hi):
        if b >= lo and dim % b == 0:
            out.append(b)
        b *= 2
    return out or [max(1, min(lo, dim))]


def candidate_buffers(kernel: str, shape, dtype: str, cfg: dict
                      ) -> list[tuple[str, int]]:
    """The S3 view of one candidate: (buffer label, resident bytes) for
    every operand/output block and scratch the pallas_call would hold."""
    isz = _itemsize(dtype)
    if kernel == "matmul":
        bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
        return [("a", bm * bk * isz), ("b", bk * bn * isz),
                ("out", bm * bn * isz), ("acc", bm * bn * 4)]
    if kernel == "flash_attention":
        D = shape[5]
        bq, bk = cfg["bq"], cfg["bk"]
        return [("q", bq * D * isz), ("k", bk * D * isz),
                ("v", bk * D * isz), ("out", bq * D * isz),
                ("m", bq * 4), ("l", bq * 4), ("acc", bq * D * 4)]
    if kernel == "paged_attention":
        _, Hq, Hkv, _, D = shape
        span = cfg["bt"] * cfg["pages"]
        # one slot's query and output rows, the double-buffered K and V
        # copies of `pages` blocks, and the per-head softmax state
        return [("q", Hq * D * isz), ("k", 2 * span * Hkv * D * isz),
                ("v", 2 * span * Hkv * D * isz), ("out", Hq * D * 4),
                ("m", Hq * 4), ("l", Hq * 4), ("acc", Hq * D * 4)]
    if kernel == "rmsnorm":
        D = shape[1]
        bm = cfg["bm"]
        return [("x", bm * D * isz), ("gamma", D * isz),
                ("out", bm * D * isz)]
    if kernel == "reduction":
        block = cfg["block"]
        return [("a", 8 * block * isz), ("b", 8 * block * isz),
                ("out", 8 * 4), ("acc", 8 * 4)]
    if kernel == "stencil":
        bh, bw = cfg["bh"], cfg["bw"]
        return [("halo", (bh + 2) * (bw + 2) * isz), ("out", bh * bw * isz)]
    raise ValueError(f"unknown kernel {kernel!r}")


def is_legal(kernel: str, shape, dtype: str, cfg: dict) -> bool:
    bufs = candidate_buffers(kernel, shape, dtype, cfg)
    return (max(b for _, b in bufs) <= VREG_GROUP_BYTES
            and sum(b for _, b in bufs) <= VRF_BYTES)


def grid_steps(kernel: str, shape, cfg: dict) -> int:
    if kernel == "matmul":
        M, K, N = shape
        return (M // cfg["bm"]) * (N // cfg["bn"]) * (K // cfg["bk"])
    if kernel == "flash_attention":
        B, Hq, _, S, Sk, _ = shape
        return B * Hq * (S // cfg["bq"]) * (Sk // cfg["bk"])
    if kernel == "paged_attention":
        B, _, _, T, _ = shape
        return B * (T // (cfg["bt"] * cfg["pages"]))
    if kernel == "rmsnorm":
        return shape[0] // cfg["bm"]
    if kernel == "reduction":
        return shape[0] // (8 * cfg["block"])
    if kernel == "stencil":
        H, W = shape
        return (H // cfg["bh"]) * (W // cfg["bw"])
    raise ValueError(f"unknown kernel {kernel!r}")


def enumerate_candidates(kernel: str, shape, dtype: str = "float32", *,
                         min_block: int | None = None,
                         max_candidates: int = 32) -> list[dict]:
    """Legal block-shape candidates: power-of-two divisors of the grid
    dims that pass the register-group / VRF budget.  When the space
    outgrows ``max_candidates`` the fewest-grid-steps candidates are kept
    (the rest are strictly dispatch-dominated under the cost model)."""
    if kernel == "matmul":
        M, K, N = shape
        lo = min_block or 32
        cands = [{"bm": bm, "bn": bn, "bk": bk}
                 for bm in _pow2_divisors(M, lo, 256)
                 for bn in _pow2_divisors(N, lo, 256)
                 for bk in _pow2_divisors(K, lo, 256)]
    elif kernel == "flash_attention":
        _, _, _, S, Sk, _ = shape
        lo = min_block or 32
        cands = [{"bq": bq, "bk": bk}
                 for bq in _pow2_divisors(S, lo, 256)
                 for bk in _pow2_divisors(Sk, lo, 256)]
    elif kernel == "paged_attention":
        T = shape[3]
        lo = min_block or 8
        cands = [{"bt": bt, "pages": pages}
                 for bt in _pow2_divisors(T, lo, 256)
                 for pages in _pow2_divisors(T // bt, 1, 16)]
    elif kernel == "rmsnorm":
        R = shape[0]
        # the kernel rounds a row block up to the TPU sublane tile (8)
        cands = [{"bm": bm} for bm in _pow2_divisors(R, min(8, R), 64)]
    elif kernel == "reduction":
        n = shape[0]
        lo = min_block or 256
        cands = [{"block": b} for b in _pow2_divisors(n // 8, lo, 4096)
                 if n % (8 * b) == 0]
    elif kernel == "stencil":
        H, W = shape
        lo = min_block or 32
        cands = [{"bh": bh, "bw": bw}
                 for bh in _pow2_divisors(H, 2, 32)
                 for bw in _pow2_divisors(W, lo, 512)]
    else:
        raise ValueError(f"unknown kernel {kernel!r}")
    cands = [c for c in cands if is_legal(kernel, shape, dtype, c)]
    cands.sort(key=lambda c: (grid_steps(kernel, shape, c),
                              sorted(c.items())))
    return cands[:max_candidates]


# ---------------------------------------------------------------- cost model

_SIM_CACHE: dict[tuple, float] = {}


def _default_params():
    from repro.sim import araxl_params
    return araxl_params(64)


def _bpl(params, n: int) -> int:
    """bytes_per_lane for an ``n``-element row (`sim.kernels._vl` inverse)."""
    return max(1, int(n) * (params.sew_bits // 8) // params.n_lanes)


def _sim_cycles(params, kernel: str, bpl: int, **kw) -> float:
    key = (kernel, bpl, tuple(sorted(kw.items())),
           params.n_lanes, params.lanes_per_cluster, params.vlen_bits)
    if key not in _SIM_CACHE:
        from repro.sim import build_trace, simulate
        _SIM_CACHE[key] = simulate(
            build_trace(kernel, params, bpl, **kw), params).cycles
    return _SIM_CACHE[key]


def model_cost(kernel: str, shape, dtype: str, cfg: dict, *,
               params=None) -> dict:
    """Price one candidate: a representative LMUL=8 strip replayed through
    the sim, scaled to the full grid, plus per-grid-step dispatch
    (`glsu_lat` + `issue_gap`) and the HBM stream at the innermost
    `Topology.wire_bw`.  Returns the µs breakdown."""
    p = params or _default_params()
    isz = _itemsize(dtype)
    G = grid_steps(kernel, shape, cfg)

    if kernel == "matmul":
        M, K, N = shape
        bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
        strip = min(bm, 8)
        c_strip = _sim_cycles(p, "fmatmul", _bpl(p, bn),
                              M=strip, K=bk, rows_blk=strip)
        compute = c_strip * (bm / strip) * G
        stream_bytes = G * (bm * bk + bk * bn) * isz + M * N * isz
    elif kernel == "flash_attention":
        B, Hq, _, S, Sk, D = shape
        bq, bk = cfg["bq"], cfg["bk"]
        strip = min(bq, 8)
        c_strip = (_sim_cycles(p, "fmatmul", _bpl(p, bk),
                               M=strip, K=D, rows_blk=strip)
                   + _sim_cycles(p, "softmax", _bpl(p, bk), rows=strip)
                   + _sim_cycles(p, "fmatmul", _bpl(p, D),
                                 M=strip, K=bk, rows_blk=strip))
        compute = c_strip * (bq / strip) * G
        stream_bytes = G * (bq * D + 2 * bk * D) * isz + B * Hq * S * D * isz
    elif kernel == "paged_attention":
        B, Hq, Hkv, T, D = shape
        span = cfg["bt"] * cfg["pages"]
        strip = min(Hq, 8)
        # one copy's score/softmax/weighted-sum strip over `span`
        # positions for every query head of the slot (decode: one token)
        c_strip = (_sim_cycles(p, "fmatmul", _bpl(p, span),
                               M=strip, K=D, rows_blk=strip)
                   + _sim_cycles(p, "softmax", _bpl(p, span), rows=strip)
                   + _sim_cycles(p, "fmatmul", _bpl(p, D),
                                 M=strip, K=span, rows_blk=strip))
        compute = c_strip * (Hq / strip) * G
        # each loop step copies `pages` K/V blocks; q/out ride once
        stream_bytes = G * 2 * span * Hkv * D * isz + 2 * B * Hq * D * isz
    elif kernel == "rmsnorm":
        R, D = shape
        bm = cfg["bm"]
        strip = min(bm, 8)
        c_strip = _sim_cycles(p, "softmax", _bpl(p, D), rows=strip)
        compute = c_strip * (bm / strip) * G
        # gamma is re-streamed every grid step: small blocks pay for it
        stream_bytes = 2 * R * D * isz + G * D * isz
    elif kernel == "reduction":
        block = cfg["block"]
        c_strip = _sim_cycles(p, "fdotproduct", block)
        compute = c_strip * G
        stream_bytes = 2 * shape[0] * isz + G * 8 * 4
    elif kernel == "stencil":
        H, W = shape
        bh, bw = cfg["bh"], cfg["bw"]
        c_tile = _sim_cycles(p, "jacobi2d", _bpl(p, bw), rows=bh + 2)
        compute = c_tile * G
        # the halo rows/cols are re-read by every neighbouring tile
        stream_bytes = G * (bh + 2) * (bw + 2) * isz + H * W * isz
    else:
        raise ValueError(f"unknown kernel {kernel!r}")

    dispatch = G * (p.glsu_lat + p.issue_gap)
    cycles_to_us = 1.0 / (p.freq_ghz * 1e3)
    wire_bw = p.topology.wire_bw(p.topology.wire_labels()[-1])
    wire_us = stream_bytes / wire_bw * 1e6
    return {
        "compute_us": compute * cycles_to_us,
        "dispatch_us": dispatch * cycles_to_us,
        "wire_us": wire_us,
        "us": (compute + dispatch) * cycles_to_us + wire_us,
    }


def model_cost_us(kernel: str, shape, dtype: str, cfg: dict, *,
                  params=None) -> float:
    return model_cost(kernel, shape, dtype, cfg, params=params)["us"]


def rank_candidates(kernel: str, shape, dtype: str, cands, *,
                    params=None) -> list[tuple[dict, float]]:
    """Model-ranked (config, predicted µs), cheapest first; ties broken by
    config so the order is deterministic."""
    priced = [(c, model_cost_us(kernel, shape, dtype, c, params=params))
              for c in cands]
    priced.sort(key=lambda cu: (cu[1], sorted(cu[0].items())))
    return priced


# ---------------------------------------------------------------- measurement

def _measure_case(kernel: str, shape, dtype: str, cfg: dict):
    """(fn, args) for `timing.measure_us`: the interpret-mode (off-TPU)
    Pallas kernel with the candidate blocks bound statically."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    interpret = jax.devices()[0].platform != "tpu"
    rng = np.random.default_rng(0)
    jdt = jnp.dtype(dtype)

    def arr(*s):
        return jnp.asarray(rng.standard_normal(s), dtype=jdt)

    if kernel == "matmul":
        from . import matmul as _mm
        M, K, N = shape
        fn = functools.partial(_mm.matmul, interpret=interpret, **cfg)
        return fn, (arr(M, K), arr(K, N))
    if kernel == "flash_attention":
        from . import flash_attention as _fa
        B, Hq, Hkv, S, Sk, D = shape
        fn = functools.partial(_fa.flash_attention, causal=True,
                               interpret=interpret, **cfg)
        return fn, (arr(B, Hq, S, D), arr(B, Hkv, Sk, D), arr(B, Hkv, Sk, D))
    if kernel == "paged_attention":
        from repro.models.layers import pool_row
        from . import paged_attention as _pa
        B, Hq, Hkv, T, D = shape
        bt = cfg["bt"]          # baked into the pool layout, not a kwarg
        gq, nblk = Hq // Hkv, T // bt
        row = (Hkv, D) if D % 128 == 0 else (pool_row(Hkv * D),)
        kpool = arr(1, B * nblk + 1, bt, *row)
        vpool = arr(1, B * nblk + 1, bt, *row)
        tables = jnp.arange(1, B * nblk + 1, dtype=jnp.int32) \
            .reshape(B, nblk)   # disjoint full tables, block 0 reserved
        lens = jnp.full((B,), T, jnp.int32)
        fn = functools.partial(_pa.paged_attention, pages=cfg["pages"],
                               interpret=interpret)
        return fn, (arr(B, Hkv, gq, D), kpool, vpool, jnp.int32(0), tables,
                    lens)
    if kernel == "rmsnorm":
        from . import rmsnorm as _rms
        R, D = shape
        fn = functools.partial(_rms.rmsnorm, interpret=interpret, **cfg)
        return fn, (arr(R, D), arr(D))
    if kernel == "reduction":
        from . import reduction as _red
        n = shape[0]
        fn = functools.partial(_red.dotprod, interpret=interpret, **cfg)
        return fn, (arr(n), arr(n))
    if kernel == "stencil":
        from . import stencil as _st
        H, W = shape
        fn = functools.partial(_st.jacobi2d, interpret=interpret, **cfg)
        return fn, (arr(H + 2, W + 2),)
    raise ValueError(f"unknown kernel {kernel!r}")


def measure_candidate(kernel: str, shape, dtype: str, cfg: dict, *,
                      reps: int = 5, warmup: int = 1):
    """One `timing.Sample` for a candidate; a noisy sample (IQR above half
    the median) is re-measured once at double reps rather than trusted."""
    from repro.testing import timing
    fn, args = _measure_case(kernel, shape, dtype, cfg)
    s = timing.measure_us(fn, *args, reps=reps, warmup=warmup)
    if s.reps >= 2 and s.iqr_us > 0.5 * s.median_us:
        s = timing.measure_us(fn, *args, reps=2 * reps, warmup=warmup)
    return s


# ---------------------------------------------------------------- context

def _default_cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env)
    root = pathlib.Path(__file__).resolve().parents[3]
    return root / "results" / "autotune" / "cache.json"


class TuneContext:
    """Ambient autotuning state: the persistent winner table plus the
    measurement policy.  Installed with :func:`tuned`; the innermost
    context wins (the olmax ctx-plumbing idiom — config travels ambiently,
    call sites stay clean)."""

    def __init__(self, cache_path=None, *, params=None, top_k: int = 3,
                 reps: int = 5, warmup: int = 1,
                 min_block: int | None = None):
        self.cache_path = pathlib.Path(cache_path) if cache_path \
            else _default_cache_path()
        self._params = params
        self.top_k = top_k
        self.reps = reps
        self.warmup = warmup
        self.min_block = min_block
        self._table = None

    @property
    def params(self):
        if self._params is None:
            self._params = _default_params()
        return self._params

    @property
    def topology_tag(self) -> str:
        return "x".join(str(s) for s in self.params.topology.shape)

    @property
    def table(self) -> dict:
        if self._table is None:
            self._table = {}
            try:
                doc = json.loads(self.cache_path.read_text())
                if isinstance(doc, dict):
                    self._table = dict(doc.get("entries", {}))
            except (OSError, ValueError):
                pass
        return self._table

    def save(self) -> None:
        self.cache_path.parent.mkdir(parents=True, exist_ok=True)
        self.cache_path.write_text(
            json.dumps({"schema": 1, "entries": self.table},
                       indent=1, sort_keys=True))

    def lookup(self, kernel: str, shape, dtype: str) -> dict | None:
        """The cached winner config for a signature, or None."""
        sig = signature(kernel, shape, dtype, self.topology_tag)
        rec = self.table.get(sig)
        if isinstance(rec, dict) and isinstance(rec.get("winner"), dict):
            return dict(rec["winner"])
        return None


_STACK: list[TuneContext] = [TuneContext()]


def current() -> TuneContext:
    return _STACK[-1]


@contextlib.contextmanager
def tuned(cache_path=None, **kw):
    """Install a :class:`TuneContext` for the dynamic extent — every
    `kernels.ops` call (and `autotune`) inside resolves against it."""
    ctx = cache_path if isinstance(cache_path, TuneContext) \
        else TuneContext(cache_path, **kw)
    _STACK.append(ctx)
    try:
        yield ctx
    finally:
        _STACK.pop()


def tuned_config(kernel: str, shape, dtype: str) -> dict | None:
    """The ops-wrapper fast path: the ambient context's cached winner for
    this problem signature (never measures, never raises)."""
    try:
        return current().lookup(kernel, shape, str(dtype))
    except Exception:
        return None


# ---------------------------------------------------------------- autotune

def autotune(kernel: str, shape, dtype: str = "float32", *, ctx=None,
             measure_all: bool = False, min_block: int | None = None) -> dict:
    """Enumerate → model-rank → measure the top-k shortlist → cache.

    Returns (and persists) the record: every candidate with its model
    rank, the measured median+IQR for the shortlist, the winner, and
    whether the model's top-k contained it (``agreement_at_k``).  A cached
    signature short-circuits without re-measuring unless ``measure_all``
    asks for the full calibration table.
    """
    ctx = ctx or current()
    shape = tuple(int(s) for s in shape)
    sig = signature(kernel, shape, dtype, ctx.topology_tag)
    cached = ctx.table.get(sig)
    if cached is not None and not measure_all:
        return cached

    mb = min_block if min_block is not None else ctx.min_block
    cands = enumerate_candidates(kernel, shape, dtype, min_block=mb)
    ranked = rank_candidates(kernel, shape, dtype, cands, params=ctx.params)
    n_measure = len(ranked) if measure_all else min(ctx.top_k, len(ranked))

    entries = []
    for rank, (cfg, mus) in enumerate(ranked):
        e = {"config": cfg, "model_us": round(mus, 3), "model_rank": rank}
        if rank < n_measure:
            s = measure_candidate(kernel, shape, dtype, cfg,
                                  reps=ctx.reps, warmup=ctx.warmup)
            e.update(measured_us=round(s.median_us, 3),
                     iqr_us=round(s.iqr_us, 3), reps=s.reps)
        entries.append(e)

    measured = [e for e in entries if "measured_us" in e]
    measured.sort(key=lambda e: (e["measured_us"], e["model_rank"]))
    for mrank, e in enumerate(measured):
        e["measured_rank"] = mrank
    win = measured[0]
    record = {
        "kernel": kernel,
        "shape": list(shape),
        "dtype": str(dtype),
        "topology": ctx.topology_tag,
        "top_k": ctx.top_k,
        "candidates": entries,
        "winner": dict(win["config"]),
        "model_rank_of_winner": win["model_rank"],
        "agreement_at_k": win["model_rank"] < ctx.top_k,
    }
    ctx.table[sig] = record
    ctx.save()
    return record


# ---------------------------------------------------------------- CLI

#: moderate default shapes per kernel; --smoke swaps in the tiny set
CASES = {
    "matmul": [(128, 128, 128), (256, 256, 128)],
    "flash_attention": [(1, 2, 1, 128, 128, 64), (1, 2, 1, 256, 256, 64)],
    "paged_attention": [(1, 4, 2, 128, 64), (1, 4, 2, 256, 64)],
    "rmsnorm": [(64, 1024), (64, 4096)],
    "reduction": [(65536,), (262144,)],
    "stencil": [(64, 256), (128, 512)],
}
SMOKE_CASES = {
    "matmul": [(64, 64, 64)],
    "flash_attention": [(1, 2, 1, 64, 64, 32)],
    "paged_attention": [(1, 4, 2, 64, 32)],
    "rmsnorm": [(16, 256)],
    "reduction": [(16384,)],
    "stencil": [(16, 128)],
}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.kernels.autotune",
        description="model-rank -> measure-shortlist -> cache kernel blocks")
    ap.add_argument("--kernel", action="append", choices=KERNELS,
                    help="kernel family (repeatable; default: all)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes (the CI end-to-end loop)")
    ap.add_argument("--top-k", type=int, default=3)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--warmup", type=int, default=1)
    ap.add_argument("--min-block", type=int, default=None)
    ap.add_argument("--cache", type=pathlib.Path, default=None,
                    help="winner-table path (default results/autotune/)")
    args = ap.parse_args(argv)

    cases = SMOKE_CASES if args.smoke else CASES
    kernels = args.kernel or list(KERNELS)
    min_block = args.min_block if args.min_block is not None \
        else (32 if args.smoke else None)
    with tuned(args.cache, top_k=args.top_k, reps=args.reps,
               warmup=args.warmup, min_block=min_block) as ctx:
        for kernel in kernels:
            for shape in cases[kernel]:
                rec = autotune(kernel, shape, ctx=ctx)
                win = next(e for e in rec["candidates"]
                           if e["config"] == rec["winner"]
                           and "measured_us" in e)
                sig = signature(kernel, shape, "float32", ctx.topology_tag)
                print(f"autotune/{sig},{win['measured_us']:.1f},"
                      f"winner={rec['winner']} "
                      f"model_rank={rec['model_rank_of_winner']} "
                      f"agree@{rec['top_k']}={rec['agreement_at_k']}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

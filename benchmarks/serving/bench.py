"""Serving benchmark: one run of one cell on the chip.

    python benchmarks/serving/bench.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

The cell is ``traffic/<cell>.json``; its model is ``configs/<config>.json``
and its metrics are ``metrics/<name>.py``, chosen by ``BENCHMARK.json``:
the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
(read from the profiler's trace of part of the window) with ``--trace 1``.

A run: draw the weights on the chip from the seed, build the paged
engine, warm up its programs (set-up ends here), serve the traffic for
``--seconds``, read the device's peak memory, free the engine, and score
a seeded sample of the finished requests against the plain float32
reference.  The last line of stdout is one JSON object; the numbers
compared, each with its limit, are the last lines of stderr and the last
key of that object.  Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # repro: noqa(L4) -- process start, own clock

import argparse                                  # noqa: E402
import gc                                        # noqa: E402
import json                                      # noqa: E402
import pathlib                                   # noqa: E402
import shutil                                    # noqa: E402
import sys                                       # noqa: E402
import tempfile                                  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import harness                                   # noqa: E402
import spec                                      # noqa: E402
import workload                                  # noqa: E402


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def pick_sample(tracks, t_end: float, want_tokens: int, seed: int):
    """The finished requests the reference scores: the longest, then
    others in an order drawn from the seed, until ``want_tokens`` served
    tokens or :data:`harness.SAMPLE_MAX` requests."""
    import numpy as np
    done = [tr for tr in tracks
            if tr.done_at is not None and tr.done_at <= t_end]
    if not done:
        return []
    done.sort(key=lambda tr: (-len(tr.req.out), tr.req.rid))
    rest = done[1:]
    order = np.random.default_rng([int(seed), 7]).permutation(len(rest))
    sample, n = [done[0]], len(done[0].req.out)
    for i in order:
        if n >= want_tokens or len(sample) >= harness.SAMPLE_MAX:
            break
        sample.append(rest[i])
        n += len(rest[i].req.out)
    return sample


def run_cell(name: str, seed: int, seconds: float, traced: bool,
             metrics: list[str], *, base: pathlib.Path = HERE,
             t_start: float | None = None, control: bool = False) -> dict:
    """One run of cell ``name``; returns the result object (and, under
    ``"_control"``, the control's readings when ``control`` is set)."""
    import jax
    import reference
    import weights

    t_start = harness.clock() if t_start is None else t_start
    cell = spec.load_cell(name, base)
    c = spec.load_config(cell["config"], base)
    gen = spec.load_generator(cell["arrivals"]["kind"], base)
    readers = {m: spec.load_metric(m, base) for m in metrics}
    devices = jax.devices()
    dev = devices[0]
    peaks = spec.load_peaks(dev.device_kind, base) \
        if dev.platform == "tpu" else None
    cache = harness.enable_compile_cache()
    compiles = harness.CompileClock()
    planned = workload.plan(cell, c, seed, gen)
    w = weights.init(c, seed)
    engine = harness.make_engine(c, w)
    harness.warm_up(engine, c)
    jax.block_until_ready(engine.pool)
    n_compiled, s_compiled = compiles.n, compiles.secs
    tracer = None
    if traced:
        tracer = harness.Tracer(cell["trace"]["start_s"],
                                cell["trace"]["seconds"],
                                tempfile.mkdtemp(prefix="bench_trace_"))
    run = harness.serve(engine, planned, cell, gen.CLOSED, seconds,
                        tracer=tracer)
    setup_s = run["t0"] - t_start
    in_window = compiles.n - n_compiled
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    del engine
    gc.collect()
    log(f"cache={cache} setup_s={setup_s:.3f} compiles_setup={n_compiled} "
        f"({s_compiled:.3f} s) compiles_in_window={in_window}")
    late = run["lateness_s"]
    log(f"window_s={run['seconds']:.3f} steps={len(run['steps'])} "
        f"requests={len(run['tracks'])} generator_late_max_ms="
        f"{1e3 * max(late, default=0.0):.3f} generator_late_mean_ms="
        f"{1e3 * (sum(late) / len(late) if late else 0.0):.3f} "
        f"queue_left={run['queue_left']}")

    trace = None
    if tracer is not None:
        import trace_reduce
        if tracer.done:
            trace = trace_reduce.reduce(tracer.dir, run)
        shutil.rmtree(tracer.dir, ignore_errors=True)
    record = {"serve": run, "setup_s": setup_s, "trace": trace,
              "config": c, "cell": cell, "peaks": peaks}

    # the check: a seeded sample of finished requests against the reference
    tracks = run["tracks"]
    failed = sum(1 for tr in tracks if not tr.times)
    sample = pick_sample(tracks, run["t_end"], cell["check"]["sample_tokens"],
                         seed)
    worst = ctl_worst = None
    if sample:
        g = reference.gaps(w, c, [tr.req.prompt for tr in sample],
                           [tr.req.out for tr in sample],
                           length=c["engine"]["max_seq"],
                           rows_max=cell["output_len"]["max"],
                           control=control)
        worst = float(max(x.max() for x in g["served"]))
        if control:
            ctl_worst = float(max(x.max() for x in g["control"]))
    limit = float(cell["check"]["logit_gap_limit"])
    n_tok = sum(len(tr.req.out) for tr in sample)
    log(f"check sample={len(sample)} requests, {n_tok} tokens, longest "
        f"{max((len(tr.req.out) for tr in sample), default=0)}")
    checks = {"logit_gap": {"value": worst, "limit": limit},
              "no_first_token": {"value": failed, "limit": 0}}
    correct = bool(sample) and worst <= limit and failed == 0

    values = {}
    for m, mod in readers.items():
        v = mod.compute(record)
        if v is not None:
            values[m] = {"value": float(v), "unit": mod.UNIT}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak)}
    out = {"correct": correct, "attempted": len(tracks), "failed": failed,
           "metrics": values, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        out["breakdown"] = trace["breakdown"]
    out["checks"] = checks
    if control:
        out["_control"] = {"logit_gap": ctl_worst, "sample_tokens": n_tok}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = spec.benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if args.workload not in cells:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    import jax
    devices = jax.devices()
    chips = cells[args.workload]["chips"]
    if devices[0].platform != "tpu" or len(devices) < chips:
        log(f"needs {chips} TPU chip(s); JAX found {len(devices)} "
            f"{devices[0].platform} device(s) ({devices[0].device_kind})")
        return 2
    metrics = spec.cell_metrics(bench, args.workload, bool(args.trace))
    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   metrics, t_start=T_START)
    for k, v in out["checks"].items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr,
              flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

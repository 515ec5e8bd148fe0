"""Knee sweep for an open-loop cell, on the chip, in one process: serve
the cell's traffic at each of several rates and report whether the
backlog grows.  The knee is the highest rate served without a growing
backlog; a cell's fixed rate is set from it once, by hand, in its
traffic file.

    python benchmarks/serving/sweep.py --workload <cell> --seed 1 \
        --seconds 40 --rates 1.0,1.5,2.0,2.5

One JSON line per rate: arrivals and completions in the window, the
backlog (requests admitted or waiting) in the window's first and last
quarter, TTFT percentiles and output tokens per second.
"""
from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import harness
import spec
import weights
import workload


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    cell = spec.load_cell(args.workload)
    c = spec.load_config(cell["config"])
    gen = spec.load_generator(cell["arrivals"]["kind"])
    w = weights.init(c, args.seed)
    engine = harness.make_engine(c, w)
    harness.warm_up(engine, c)
    for rate in (float(r) for r in args.rates.split(",")):
        cell_r = dict(cell, arrivals=dict(cell["arrivals"], rate_rps=rate))
        planned = workload.plan(cell_r, c, args.seed, gen)
        run = harness.serve(engine, planned, cell_r, False, args.seconds)
        engine.run()
        t0, t1 = run["t0"], run["t_end"]
        tr = run["tracks"]
        due = [x for x in tr if x.arrival < t1]

        def backlog(t):
            return sum(1 for x in due if x.arrival <= t and
                       (x.done_at is None or x.done_at > t))

        q = (t1 - t0) / 4
        early = np.mean([backlog(t0 + q * f) for f in (0.25, 0.5, 0.75, 1)])
        late = np.mean([backlog(t1 - q * f) for f in (0.25, 0.5, 0.75, 1)])
        ttft = [x.times[0] - x.arrival for x in due if x.times]
        toks = sum(1 for x in tr for t in x.times if t0 < t <= t1)
        print(json.dumps({
            "rate_rps": rate, "arrived": len(due),
            "done_in_window": sum(1 for x in due if x.done_at is not None
                                  and x.done_at <= t1),
            "backlog_first_quarter": float(early),
            "backlog_last_quarter": float(late),
            "ttft_p50_ms": 1e3 * float(np.percentile(ttft, 50)),
            "ttft_p90_ms": 1e3 * float(np.percentile(ttft, 90)),
            "output_tok_s": toks / (t1 - t0)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings that set a cell's correctness limit, on the chip, in one
process: for each seed, one run of the cell (its own traffic and load)
whose sample is scored twice, once as served by the program and once with
the fp8 control in the program's place.

    python benchmarks/serving/calibrate.py --workload <cell> \
        --seeds 1,2,3 --seconds 45

Prints one JSON line per seed: the program's widest logit gap (the lower
reading comes from the largest over a dozen seeds or more), the
control's (the upper reading from the smallest), the sample's size and
the run's end-to-end metrics.  The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import bench
import spec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        bench.log("calibrate: needs a TPU")
        return 2
    metrics = spec.cell_metrics(spec.benchmark(), args.workload, False)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = bench.run_cell(args.workload, seed, args.seconds, False,
                             metrics, control=True)
        print(json.dumps({
            "seed": seed, "correct": out["correct"],
            "program_gap": out["checks"]["logit_gap"]["value"],
            "control_gap": out["_control"]["logit_gap"],
            "sample_tokens": out["_control"]["sample_tokens"],
            "attempted": out["attempted"], "failed": out["failed"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
        del out
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())

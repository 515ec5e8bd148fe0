"""95th percentile of every gap between two consecutive tokens of one
request, both inside the window (tokens are stamped when the step that
made them returns; two tokens of one step have a gap of 0)."""
import numpy as np

NAME = "itl_p95_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def compute(record):
    run = record["serve"]
    t0, t1 = run["t0"], run["t_end"]
    gaps = [b - a for tr in run["tracks"]
            for a, b in zip(tr.times, tr.times[1:]) if t0 < a and b <= t1]
    return 1e3 * float(np.percentile(gaps, 95)) if gaps else None

"""90th percentile of the time to first token over every request due in
the window, timed from when it was due (not when it was sent).  A
request that gets no first token by the end of the drain is a failure
and is left out here."""
import numpy as np

NAME = "ttft_p90_ms"
UNIT = "ms"
LAYER = "end to end"
MOVES = "ttft_p90_ms"
SOURCE = "host_clock"


def compute(record):
    run = record["serve"]
    ttft = [tr.times[0] - tr.arrival for tr in run["tracks"]
            if tr.times and run["t0"] <= tr.arrival < run["t_end"]]
    return 1e3 * float(np.percentile(ttft, 90)) if ttft else None

"""Engine: the device's idle time in one decode-only engine step: the
step's time on the host clock less the union of its device operations,
in ms, the median over the traced decode-only steps (one stalled step,
such as an 80 ms pause among 30, would move a mean by ~3 ms).  It is
the host's work inside ``engine.step()`` while the chip waits:
admission, the pre-step block bookkeeping, the uploads, the argmax
readback and retirement, each a ``serve.*`` span of the engine.  It
needs no span, so it reads the same on a program without them."""
import numpy as np

import trace_reduce

NAME = "host_gap_ms.decode"
UNIT = "ms"
LAYER = "engine (serve/paged.py)"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(record):
    tr = record["trace"]
    if not tr:
        return None
    steps = {s["k"]: s for s in record["serve"]["steps"]}
    gaps = [steps[k]["t1"] - steps[k]["t0"]
            - trace_reduce.union_ns((o.start, o.end) for o in ops) * 1e-9
            for k, ops in tr["ops"].items() if tr["kind"][k] == "decode"]
    return 1e3 * float(np.median(gaps)) if gaps else None

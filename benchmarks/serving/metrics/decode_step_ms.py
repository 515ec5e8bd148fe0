"""Model step: device time of one decode-only engine step (the union of
the device operations inside the step's span), averaged over the traced
decode-only steps."""
import trace_reduce

NAME = "decode_step_ms"
UNIT = "ms"
LAYER = "model step (models/lm.py)"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(record):
    busy = trace_reduce.busy_by_kind(record["trace"]).get("decode")
    return 1e3 * sum(busy) / len(busy) if busy else None

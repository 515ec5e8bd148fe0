"""Kernels: the projections of the traced decode-only steps (q, k, v, o,
gate, up, down and the head, found by their weight operand whichever
code computes them) against their roofline at the step's live rows: the
least time each could take over its device time, in %."""
import trace_reduce

NAME = "matmul_roofline.decode"
UNIT = "%"
LAYER = "kernels (kernels/ops.py)"
MOVES = "itl_p95_ms"
SOURCE = "device_trace"


def compute(record):
    return trace_reduce.roofline_share(
        record, ("decode",), lambda step, kn: step["decode_rows"])

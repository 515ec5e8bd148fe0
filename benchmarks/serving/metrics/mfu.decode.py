"""Whole decode step: the least time the chip could take for the work a
decode-only step needs (the larger of its FLOPs over peak FLOP/s and its
bytes over peak bytes/s: weights, live keys and values, activations;
``work.decode_step``), over the step's time on the host clock, summed
over every decode-only step of the window."""
import work

NAME = "mfu.decode"
UNIT = "%"
LAYER = "model step (models/lm.py)"
MOVES = "itl_p95_ms"
SOURCE = "host_clock"


def compute(record):
    peaks, c = record["peaks"], record["config"]
    run = record["serve"]
    steps = [s for s in run["steps"] if s["t1"] <= run["t_end"]
             and s["d_decode"] and not s["d_chunks"]]
    if peaks is None or not steps:
        return None
    need = sum(work.step_roofline_s(*work.decode_step(
        c, s["decode_rows"], s["decode_ctx"]), peaks) for s in steps)
    return 100.0 * need / sum(s["t1"] - s["t0"] for s in steps)

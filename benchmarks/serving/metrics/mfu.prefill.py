"""Prefill chunks as whole steps: the least time the chip could take for
the work of every chunk in the window (``work.prefill_chunk`` at its
valid prompt rows), over the host time of the steps that ran one, less
the mean decode-only step for those that also decoded."""
import work

NAME = "mfu.prefill"
UNIT = "%"
LAYER = "model step (models/lm.py)"
MOVES = "ttft_p90_ms"
SOURCE = "host_clock"


def compute(record):
    peaks, c = record["peaks"], record["config"]
    run = record["serve"]
    steps = [s for s in run["steps"] if s["t1"] <= run["t_end"]]
    dec = [s["t1"] - s["t0"] for s in steps
           if s["d_decode"] and not s["d_chunks"]]
    chunked = [s for s in steps if s["d_chunks"] and s["chunks"]]
    if peaks is None or not chunked or not dec:
        return None
    dec_s = sum(dec) / len(dec)
    need = sum(work.step_roofline_s(*work.prefill_chunk(c, *ch), peaks)
               for s in chunked for ch in s["chunks"])
    took = sum(s["t1"] - s["t0"] - (dec_s if s["d_decode"] else 0.0)
               for s in chunked)
    return 100.0 * need / took if took > 0 else None

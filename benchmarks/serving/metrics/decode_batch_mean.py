"""Engine: decode tokens made per decode step over the window, from the
engine's ``decode_steps`` counter and the tokens the clients received.
A full batch reads ``max_batch``; a slot that sits in prefill or waits
for admission lowers it."""
NAME = "decode_batch_mean"
UNIT = "slots"
LAYER = "engine (serve/paged.py)"
MOVES = "output_tok_s"
SOURCE = "program_counter"


def compute(record):
    run = record["serve"]
    steps = [s for s in run["steps"] if s["t1"] <= run["t_end"]]
    n = sum(s["d_decode"] for s in steps)
    return sum(s["decode_rows"] for s in steps) / n if n else None

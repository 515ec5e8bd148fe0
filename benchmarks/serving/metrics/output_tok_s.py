"""Generated tokens that reached the client in the window, over the
window's seconds: all the work and all the time of the window."""
NAME = "output_tok_s"
UNIT = "tokens/s"
LAYER = "end to end"
MOVES = "output_tok_s"
SOURCE = "host_clock"


def compute(record):
    run = record["serve"]
    t0, t1 = run["t0"], run["t_end"]
    n = sum(1 for tr in run["tracks"] for t in tr.times if t0 < t <= t1)
    return n / (t1 - t0) if n else None

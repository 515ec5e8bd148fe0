"""Engine: 90th percentile of each request's own prefill, from the
dispatch of its first chunk to its first token read back (``t_first -
t_prefill_start``), over every request due in the window.  It holds the
request's chunks and the decode steps the engine runs between them."""
import request_stamps

NAME = "own_prefill_p90_ms"
UNIT = "ms"
LAYER = "engine (serve/paged.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def compute(record):
    return request_stamps.p90_ms(record, "t_prefill_start", "t_first")

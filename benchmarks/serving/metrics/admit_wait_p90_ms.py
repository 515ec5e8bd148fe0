"""Engine: 90th percentile of each request's wait in the queue, from
``submit()`` until admission gives it a slot and its blocks (``t_admit -
t_submit``), over every request due in the window.  The head of the
queue waits for a free slot, or for the pool to cover its plan."""
import request_stamps

NAME = "admit_wait_p90_ms"
UNIT = "ms"
LAYER = "engine (serve/paged.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def compute(record):
    return request_stamps.p90_ms(record, "t_submit", "t_admit")

"""Engine: 90th percentile of each request's wait in the engine, from
``submit()`` to the dispatch of its first prefill (``t_prefill_start -
t_submit``), over every request due in the window.  It holds the wait
for a slot and for blocks (``admit_wait_p90_ms``) and the wait for the
request's turn at the one-chunk-per-step prefill
(``chunk_turn_wait_p90_ms``)."""
import request_stamps

NAME = "queue_wait_p90_ms"
UNIT = "ms"
LAYER = "engine (serve/paged.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def compute(record):
    return request_stamps.p90_ms(record, "t_submit", "t_prefill_start")

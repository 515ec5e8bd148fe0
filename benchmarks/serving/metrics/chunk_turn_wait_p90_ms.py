"""Engine: 90th percentile of each request's wait, holding a slot, for
its first prefill chunk (``t_prefill_start - t_admit``), over every
request due in the window.  The engine runs one chunk per step, for the
lowest slot still prefilling, so an admitted prompt waits for the
prompts ahead of it."""
import request_stamps

NAME = "chunk_turn_wait_p90_ms"
UNIT = "ms"
LAYER = "engine (serve/paged.py)"
MOVES = "ttft_p90_ms"
SOURCE = "program_span"


def compute(record):
    return request_stamps.p90_ms(record, "t_admit", "t_prefill_start")

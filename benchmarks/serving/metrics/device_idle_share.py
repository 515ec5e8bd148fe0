"""Device: the share of the traced window in which no operation ran on
the device (1 - the union of the operations' intervals over the window,
from the first traced step's start to the last one's end)."""
NAME = "device_idle_share"
UNIT = "%"
LAYER = "device"
MOVES = "output_tok_s"
SOURCE = "device_trace"


def compute(record):
    tr = record["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])

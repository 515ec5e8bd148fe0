"""Set-up: process start to the first timed request (imports, reaching
the chip, drawing the weights, building the engine, loading or compiling
every program the window runs)."""
NAME = "setup_s"
UNIT = "s"
LAYER = "end to end"
MOVES = "setup_s"
SOURCE = "host_clock"


def compute(record):
    return record["setup_s"]
